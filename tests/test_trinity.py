"""The Trinity decoder (ops/trinity.py) against its plain reference, its two
kinds of key-value slot state through the batched encoder step
(serving/stepper.py) and the seq app's request path, on the CPU at a small
size: 4 layers (one dense, three of 16 sigmoid-routed experts, 4 of them HELD,
beside a shared one; sliding, sliding, full, sliding), hidden 64, 4 query
heads on 2 key-value heads of 16, 300 items, seeded weights, and a sliding
window of 8 positions: SHORTER than the sessions, so the mask clips and the
sliding layers' slots wrap. `test_the_programs_compile_for_a_v5e` compiles
both programs at the published widths for a described chip.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import decoder, trinity

TYPES = ("sliding_attention", "sliding_attention", "full_attention", "sliding_attention")
CFG = trinity.TrinityConfig(
    hidden=64, heads=4, kv_heads=2, head_dim=16, intermediate=96, experts=16, held=4, expert_width=32,
    experts_per_token=4, shared_experts=1, dense_layers=1, layer_types=TYPES, vocab=300,
    sliding_window=8, first_expert=4, basket=4, max_len=24,
)
# the cell's configuration: 5 of 60 layers, 32 of each layer's 256 experts
REAL = trinity.TrinityConfig(
    hidden=3072, heads=48, kv_heads=8, head_dim=128, intermediate=12288, experts=256, held=32,
    expert_width=3072, experts_per_token=4, shared_experts=1, dense_layers=1,
    layer_types=("sliding_attention",) * 3 + ("full_attention", "sliding_attention"), vocab=200192,
)
N_ITEMS = 300
EXPERT_LAYERS = 3
# float32 served form against the float32 reference: accumulation order
# alone. Logits are about 0.5 at these weights (the stream is normalised)
F32_ATOL = 5e-6
# bfloat16 served form against the float32 reference on the same bf16
# weights: the activations' and the cache's rounding, 2^-9 relative at each
# (3.1e-3 to 5.8e-3 over three seeds of weights)
BF16_ATOL = 1.5e-2


def _weights(seed=7, dtype=jnp.float32, cfg=CFG):
    """Parameters and the untied head: the view's rows are their own draw, at
    bfloat16's values (the served view is bfloat16 whatever the weights are),
    with capacity rows past the items; row i's input embedding is E_in row i."""
    params = trinity.init_params(cfg, seed, dtype)
    rng = np.random.default_rng(seed)
    e = np.zeros((384, cfg.hidden), np.float32)
    e[:N_ITEMS] = rng.standard_normal((N_ITEMS, cfg.hidden)).astype(np.float32) * 0.02
    e = np.asarray(jnp.asarray(e, jnp.bfloat16).astype(jnp.float32))
    row_token = np.full(384, -1, np.int32)
    row_token[:N_ITEMS] = np.arange(N_ITEMS)
    return params, e, jnp.asarray(row_token)


def _sessions(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.choice(N_ITEMS, size=n, replace=False).astype(np.int32) for n in lengths]


def _generate(enc, params, head, sessions, slots_of=None, fill=(), bucket=None, state=None):
    """Prefill + the encoder's steps through the slot cache for `sessions`
    (and `fill`, more sessions sharing the dispatches) -> (the last step's
    out, the state after it, the counts summed over every dispatch)."""
    state = enc.init_state(enc.step_rows) if state is None else state
    everyone = list(sessions) + list(fill)
    slots_of = slots_of or list(range(len(everyone)))
    counts = np.zeros(4, np.int64)
    for lo in range(0, len(everyone), enc.prefill_rows):
        group = everyone[lo:lo + enc.prefill_rows]
        b = bucket or min(b for b in enc.length_buckets if b >= max(enc.length(p) for p in group))
        packed = enc.pack(group, b, slots_of[lo:lo + len(group)], enc.step_rows)
        state, _, tallied = enc.prefill(params, state, *packed)
        counts += np.asarray(tallied["counts"])
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    lengths = np.zeros(enc.step_rows, np.int32)
    live = np.zeros(enc.step_rows, bool)
    for i, p in enumerate(everyone):
        slots[i], lengths[i], live[i] = slots_of[i], enc.length(p), True
    out = None
    for step in range(enc.steps):
        state, out = enc.step(
            params, state, head, slots, lengths, live, np.full(enc.step_rows, step, np.int32)
        )
        counts += np.asarray(out["counts"])
    return {k: np.asarray(v) for k, v in out.items()}, state, counts


def _logits_of(cfg, params, e, session, rows):
    """The reference's ONE full pass over [session + the basket chosen] at the
    four positions, scored over the catalog: [items, 4]."""
    tokens = np.concatenate([session, rows[:-1]]).astype(np.int32)
    full = np.asarray(trinity.reference_forward(cfg, params, jnp.asarray(tokens)))[-cfg.basket:]
    return e[:N_ITEMS] @ full.T


# ---- the model: shapes, weights, what a slot holds ------------------------------

def test_shapes_parameter_count_and_slot_bytes_at_the_published_widths():
    count = lambda l, keys=None: sum(  # noqa: E731
        int(np.prod(s)) for k, s in trinity.layer_shapes(REAL, l).items() if keys is None or k in keys
    )
    attn = count(1, ("wq", "wk", "wv", "wgate", "wo"))
    # ISSUE 44's arithmetic: attention 62.91M a layer, the dense layer 176.16M, an expert
    # 28.31M, an expert layer with 32 of its 256 experts 998.0M, the five layers 4,168M
    assert attn == 62_914_560 and 3 * 3072 * 3072 == 28_311_552
    assert count(0) == pytest.approx(176.16e6, rel=1e-4) and count(1) == pytest.approx(998.0e6, rel=1e-4)
    assert REAL.is_dense(0) and not REAL.is_dense(1) and REAL.positions == 104
    assert [REAL.is_sliding(l) for l in range(5)] == [True, True, True, False, True]
    layers = sum(count(l) for l in range(5))
    assert layers == pytest.approx(4168e6, rel=1e-3)                    # 8.34 GB in bfloat16
    assert trinity.param_count(REAL) == layers + 200192 * 3072 + 3072   # and the input embedding
    assert trinity.layer_shapes(REAL, 2)["router"] == (3072, 256)       # the router keeps every output
    assert trinity.layer_shapes(REAL, 2)["wg"] == (32, 3072, 3072)      # the experts held
    # an expert layer WHOLE: 14.68 GB in bfloat16, which no chip holds
    whole = count(1) + (256 - 32) * 28_311_552
    assert 2 * whole == pytest.approx(14.68e9, rel=1e-3)
    # the window is wider than a slot: both kinds keep 104 rows of 2 x 8 x 128 x 2 bytes a layer
    state = trinity.state_bytes(REAL, 32)
    assert state == {"window_kv": 4 * 33 * 104 * 4096, "full_kv": 33 * 104 * 4096}
    assert sum(state.values()) == pytest.approx(70e6, rel=0.02)
    shapes = jax.eval_shape(lambda: trinity.init_state(REAL, 32))
    assert {a.shape for a in shapes["k"] + shapes["v"]} == {(33, 104, 8, 128)}
    # where it is shorter, a sliding layer's slot is the window
    assert [CFG.cache_rows(l) for l in range(4)] == [8, 8, 28, 8]
    small = jax.eval_shape(lambda: trinity.init_state(CFG, 32))
    assert [a.shape[1] for a in small["k"]] == [8, 8, 28, 8]
    assert trinity.state_bytes(CFG, 32, 4) == {"window_kv": 3 * 33 * 8 * 256, "full_kv": 33 * 28 * 256}


def test_the_configuration_is_read_from_the_sources_own_keys():
    ext = {k: str(v) for k, v in REAL.to_extensions().items()}
    assert trinity.TrinityConfig.from_extensions(ext.get) == REAL
    assert trinity.TrinityConfig.from_extensions(
        {k: json.dumps(v) if isinstance(v, list) else str(v) for k, v in CFG.to_extensions().items()}.get
    ) == CFG
    # without layer_types the pattern is global_attn_every_n_layers' (every fourth is full)
    del ext["layer_types"]
    assert trinity.TrinityConfig.from_extensions(ext.get) == REAL
    # a whole model states its experts once: every one is held
    whole = dict(ext, num_experts="256")
    del whole["num_experts_routed"]
    cfg = trinity.TrinityConfig.from_extensions(whole.get)
    assert (cfg.experts, cfg.held, cfg.first_expert) == (256, 256, 0)
    assert cfg.routing == {"scoring": "sigmoid", "scale": 2.448, "held": (0, 256)}
    with pytest.raises(ValueError, match="layer_types"):
        trinity.TrinityConfig.from_extensions(dict(ext, layer_types="full_attention").get)
    with pytest.raises(ValueError, match="holds experts"):
        trinity.TrinityConfig.from_extensions(dict(ext, first_expert="240").get)


@pytest.mark.parametrize(
    "key,value",
    [("score_func", "softmax"), ("n_group", "8"), ("rope_scaling", "{'type': 'yarn'}"), ("route_norm", "False"),
     ("tie_word_embeddings", "True"), ("mup_enabled", "False"), ("num_expert_groups", "4")],
)
def test_a_form_the_program_does_not_compute_is_refused(key, value):
    ext = dict({k: str(v) for k, v in CFG.to_extensions().items()}, rope_scaling="None", n_group="1")
    assert trinity.TrinityConfig.from_extensions(ext.get) == CFG
    with pytest.raises(ValueError, match=key):
        trinity.TrinityConfig.from_extensions(dict(ext, **{key: value}).get)


def test_the_weights_are_a_pure_function_of_the_seed_and_the_bias_is_visible():
    t = trinity.init_tensors(CFG, 5, jnp.bfloat16)
    bias = np.asarray(t["L1.router_bias"])
    assert bias.shape == (16,) and bias.dtype == np.float32
    assert bias.std() == pytest.approx(decoder.BIAS_INIT, rel=0.5)
    assert t["L1.wg"].dtype == jnp.bfloat16 and t["L1.wg"].shape == (4, 64, 32) and t["L1.router"].shape == (64, 16)
    for norm in trinity.NORM_TENSORS:
        assert np.all(np.asarray(t[f"L0.{norm}"].astype(jnp.float32)) == 1.0)
    assert "L0.router" not in t and t["L0.wg"].shape == (64, 96)  # the leading dense layer
    assert "L1.shared_wg" in t and "L0.shared_wg" not in t and t["L2.wgate"].shape == (64, 64)
    again = trinity.init_tensors(CFG, 5, jnp.bfloat16)
    assert all(np.array_equal(np.asarray(t[k]), np.asarray(again[k])) for k in t)
    other = trinity.init_tensors(CFG, 6, jnp.bfloat16)
    assert not np.array_equal(np.asarray(t["L1.router_bias"]), np.asarray(other["L1.router_bias"]))
    params = trinity.params_of(CFG, t, jnp.bfloat16)
    assert params["layers"][1]["router_bias"].dtype == jnp.float32  # whatever the weights' dtype


# ---- the window and the positions -------------------------------------------------

def test_a_prefill_keeps_the_newest_position_of_every_row_of_the_window():
    x = jnp.arange(2 * 12, dtype=jnp.float32).reshape(2, 12, 1, 1) + 1.0        # position p holds p + 1 (and 13 + p)
    lengths = jnp.asarray([11, 3])
    kept = np.asarray(trinity._kept(x, lengths, 8))[:, :, 0, 0]
    # 11 positions in 8 rows: rows 0..2 were taken again by positions 8, 9, 10
    assert kept[0].tolist() == [9, 10, 11, 4, 5, 6, 7, 8]
    assert kept[1].tolist() == [13, 14, 15, 0, 0, 0, 0, 0]
    # rows for every position and more: the positions themselves, the padded ones zeroed
    wide = np.asarray(trinity._kept(x, lengths, 16))[:, :, 0, 0]
    assert wide[0].tolist() == list(range(1, 12)) + [0] * 5 and wide[1].tolist() == [13, 14, 15] + [0] * 13


def test_the_window_clips_and_only_the_sliding_layers_read_positions():
    params, e, _ = _weights()
    tokens = jnp.asarray(_sessions((20,), seed=4)[0])
    sound = np.asarray(trinity.reference_forward(CFG, params, tokens))
    # the first 8 positions see everything they may: a wider window changes the later ones alone
    wide = np.asarray(trinity.reference_forward(CFG._replace(sliding_window=4096), params, tokens))
    np.testing.assert_allclose(sound[:8], wide[:8], atol=1e-6)
    assert np.abs(sound[8:] - wide[8:]).max() > 1e-2
    # a model of full layers alone reads no position: shifted, the same output
    shifted = jnp.arange(20) + 1000
    full = CFG._replace(layer_types=("full_attention",) * 4)
    np.testing.assert_array_equal(
        np.asarray(trinity.reference_forward(full, params, tokens)),
        np.asarray(trinity.reference_forward(full, params, tokens, pos=shifted)),
    )
    # positions far apart turn the sliding layers' queries and keys by other angles
    spread = jnp.arange(20) * 37
    assert np.abs(np.asarray(trinity.reference_forward(CFG, params, tokens, pos=spread)) - sound).max() > 1e-2
    # the served pieces the same: q and k come back rotated on a sliding layer alone
    p = params["layers"][1]
    a = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 64))
    q0, k0, v0 = trinity._qkv(CFG, p, a, jnp.arange(20)[None], False)
    q1, k1, v1 = trinity._qkv(CFG, p, a, shifted[None], False)
    assert np.array_equal(np.asarray(q0), np.asarray(q1)) and np.array_equal(np.asarray(k0), np.asarray(k1))
    q2, k2, v2 = trinity._qkv(CFG, p, a, shifted[None], True)
    assert np.abs(np.asarray(q2 - q0)).max() > 0.1 and np.array_equal(np.asarray(v2), np.asarray(v0))


# ---- prefill and decode through the cache against the full pass ---------------------

@pytest.mark.parametrize("n", [2, 7, 9, 12, 24])
def test_prefill_then_step_is_the_full_pass_at_the_last_position(n):
    params, e, row_token = _weights()
    enc = trinity.TrinityEncoder(CFG, jnp.float32)
    session = _sessions((n,), seed=n)[0]
    state = enc.init_state(enc.step_rows)
    state, hidden, tallied = enc.prefill(params, state, *enc.pack([session], 24, [3], enc.step_rows))
    counts = tallied["counts"]
    full = np.asarray(trinity.reference_forward(CFG, params, jnp.asarray(session)))
    z, k_cache, v_cache, step_counts = trinity._token_hidden(
        CFG, params, state, jnp.asarray([3]), jnp.asarray([n - 1]), jnp.asarray([True])
    )
    np.testing.assert_allclose(np.asarray(z[0]), full[-1], atol=F32_ATOL)
    # three expert layers: every real token's pairs, here or sent elsewhere, and the step's
    assert int(counts[0] + counts[3]) == (n - 1) * CFG.experts_per_token * EXPERT_LAYERS
    assert int(step_counts[0] + step_counts[3]) == CFG.experts_per_token * EXPERT_LAYERS
    # the caches: n positions, a sliding layer's in 8 rows written modulo 8, nothing behind
    for l in range(CFG.layers):
        rows = CFG.cache_rows(l)
        filled = np.abs(np.asarray(k_cache[l][3])).sum((-1, -2)) > 0
        assert filled.tolist() == [c < n for c in range(rows)]
        assert (np.abs(np.asarray(v_cache[l][3])).sum((-1, -2)) > 0).tolist() == filled.tolist()


@pytest.mark.parametrize(
    "dtype,atol", [(jnp.float32, F32_ATOL), (jnp.bfloat16, BF16_ATOL)], ids=["float32", "bfloat16"]
)
def test_cached_generation_against_the_references_full_forward(dtype, atol):
    """Sessions of 12-24 events and 4 generated positions against a window of
    8: every sliding slot wraps, a prefill's mask clips, and a step reads
    exactly the 8 newest positions."""
    params, e, row_token = _weights(dtype=dtype)
    enc = trinity.TrinityEncoder(CFG, dtype)
    sessions = _sessions((13, 24, 12, 18, 2))
    out, _, counts = _generate(enc, params, (jnp.asarray(e, dtype), N_ITEMS, row_token), sessions)
    tokens_run = sum(len(s) - 1 for s in sessions) + 4 * len(sessions)
    # no pair dropped, a prefill's or a step's: each is computed here or was sent elsewhere
    assert counts[0] + counts[3] == tokens_run * CFG.experts_per_token * EXPERT_LAYERS
    assert 0 < counts[0] < counts[3]  # 4 of 16 experts are held
    for i, session in enumerate(sessions):
        np.testing.assert_array_equal(out["step"][i], np.arange(4))
        logits = _logits_of(CFG, params, e, session, out["row"][i])
        np.testing.assert_allclose(e[:N_ITEMS] @ out["z"][i].T, logits, atol=atol)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(out["row"][i], logits.argmax(0))
    # and the reference's own generation, a full pass a position (one session: each length compiles)
    if dtype == jnp.float32:
        ref = trinity.reference_generate(CFG, params, e[:N_ITEMS], sessions[0])
        np.testing.assert_array_equal(out["row"][0], ref["row"])


def test_a_lower_precision_than_stated_fails_the_float32_tolerance():
    """The cache kept in bfloat16 under float32 weights: the served scores
    leave the reference by more than the float32 tolerance allows."""
    params, e, row_token = _weights()
    enc = trinity.TrinityEncoder(CFG, jnp.float32)
    session = _sessions((13,))[0]
    head = (jnp.asarray(e), N_ITEMS, row_token)
    low = enc.init_state(enc.step_rows)
    low = dict(low, k=[a.astype(jnp.bfloat16) for a in low["k"]], v=[a.astype(jnp.bfloat16) for a in low["v"]])
    out, _, _ = _generate(enc, params, head, [session], state=low)
    err = np.abs(e[:N_ITEMS] @ out["z"][0].T - _logits_of(CFG, params, e, session, out["row"][0])).max()
    assert err > 10 * F32_ATOL


@pytest.mark.parametrize("how", ["full_dispatch", "other_bucket", "both"])
def test_an_answer_is_the_same_alone_in_a_full_dispatch_and_in_either_bucket(how):
    params, e, row_token = _weights()  # no tensor's shape depends on max_len
    assert trinity.TrinityEncoder(CFG, jnp.float32).length_buckets == (24,)  # under 32: one bucket
    enc = trinity.TrinityEncoder(CFG._replace(max_len=40), jnp.float32)
    assert enc.length_buckets == (32, 40)
    head = (jnp.asarray(e), N_ITEMS, row_token)
    mine = _sessions((13,))
    alone, _, _ = _generate(enc, params, head, mine)
    fill, slots_of, bucket = (), None, None
    if how in ("full_dispatch", "both"):
        fill = _sessions([3 + (5 * j) % 30 for j in range(enc.step_rows - 1)], seed=5)
        slots_of = [enc.step_rows - 1] + list(range(enc.step_rows - 1))  # and another slot
    if how in ("other_bucket", "both"):
        bucket = 40
    shared, _, _ = _generate(enc, params, head, mine, slots_of=slots_of, fill=fill, bucket=bucket)
    np.testing.assert_array_equal(alone["row"][0], shared["row"][0])
    np.testing.assert_allclose(e @ alone["z"][0].T, e @ shared["z"][0].T, atol=F32_ATOL)


def test_a_slot_taken_again_starts_empty_and_a_padded_position_writes_nothing():
    params, e, row_token = _weights()
    enc = trinity.TrinityEncoder(CFG, jnp.float32)
    head = (jnp.asarray(e), N_ITEMS, row_token)
    first, second = _sessions((24, 4), seed=9)
    fresh, _, _ = _generate(enc, params, head, [second], slots_of=[5])
    _, used, _ = _generate(enc, params, head, [first], slots_of=[5])
    # the longer session filled every row of a window and 27 of the full layer's 28
    assert all(float(jnp.abs(used[k][l][5]).min(axis=(-1, -2)).max()) > 0 for k in ("k", "v") for l in (0, 1, 3))
    assert float(jnp.abs(used["k"][2][5, 26]).max()) > 0
    again, after, _ = _generate(enc, params, head, [second], slots_of=[5], state=used)
    np.testing.assert_array_equal(fresh["row"][0], again["row"][0])
    np.testing.assert_array_equal(fresh["z"][0], again["z"][0])
    # 3 positions prefilled and 4 generated: what the longer session left behind is gone,
    # and the bucket's padded positions wrote nothing
    for k in ("k", "v"):
        for l in range(CFG.layers):
            filled = np.abs(np.asarray(after[k][l][5])).sum((-1, -2)) > 0
            assert filled.tolist() == [c < 7 for c in range(CFG.cache_rows(l))]
    # a session of ONE event prefills nothing: its slot starts empty all the same
    one = _sessions((1,), seed=2)
    lone, _, _ = _generate(enc, params, head, one, slots_of=[5])
    reused, _, _ = _generate(enc, params, head, one, slots_of=[5], state=after)
    np.testing.assert_array_equal(lone["z"][0], reused["z"][0])
    ref = trinity.reference_generate(CFG, params, e[:N_ITEMS], one[0])
    np.testing.assert_array_equal(lone["row"][0], ref["row"])


def test_padding_rows_touch_only_the_scratch_slot():
    params, e, row_token = _weights()
    enc = trinity.TrinityEncoder(CFG, jnp.float32)
    out, state, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, row_token), _sessions((9,)), slots_of=[4])
    untouched = [s for s in range(enc.step_rows) if s != 4]
    for k in ("k", "v"):
        for l in range(CFG.layers):
            a = np.asarray(state[k][l])
            assert np.abs(a[4]).max() > 0 and np.abs(a[untouched]).max() == 0
    assert np.all(np.asarray(state["row"])[untouched] == -1) and np.all(out["row"][1:] == -1)


def test_a_view_row_with_no_input_embedding_feeds_zeros():
    """An item that came by UP after the model has a head row and no E_in
    row: chosen, it is fed back as zeros, and the basket goes on."""
    params, e, row_token = _weights()
    enc = trinity.TrinityEncoder(CFG, jnp.float32)
    session = _sessions((9,))[0]
    known, _, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, row_token), [session])
    first = int(known["row"][0][0])
    unknown = row_token.at[first].set(-1)
    got, state, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, unknown), [session])
    assert got["row"][0][0] == first and not np.array_equal(got["z"][0][1], known["z"][0][1])
    assert np.isfinite(got["z"]).all()
    tokens = jnp.asarray(np.concatenate([session, [first]]).astype(np.int32))
    blank = dict(params, E_in=params["E_in"].at[first].set(0.0))
    assert first not in session.tolist()
    full = np.asarray(trinity.reference_forward(CFG, blank, tokens))[-1]
    np.testing.assert_allclose(got["z"][0][1], full, atol=F32_ATOL)


# ---- every new part weighs in the output at this initialisation -------------------

def _without(part):
    """The reference's parameters or configuration with one part taken out,
    as a fault would."""
    params, e, _ = _weights()
    cfg = CFG
    layers = [dict(p) for p in params["layers"]]
    for p in layers:
        if part == "attention_gate":    # sigmoid(0) = a half everywhere: the gate no longer chooses
            p["wgate"] = jnp.zeros_like(p["wgate"])
        elif part == "norm_after_attention":  # its gain halved: a norm left out moves the scale as much
            p["ln1_post"] = p["ln1_post"] * 0.5
        elif part == "norm_after_mlp":
            p["ln2_post"] = p["ln2_post"] * 0.5
        elif part == "key_norm":
            p["k_norm"] = p["k_norm"] * 0.5
    for p in layers[1:]:
        if part == "shared_expert":
            p["shared_wd"] = jnp.zeros_like(p["shared_wd"])
        elif part == "selecting_bias":
            p["router_bias"] = jnp.zeros_like(p["router_bias"])
    if part == "route_scale":
        cfg = CFG._replace(route_scale=1.0)
    elif part == "other_share":  # the next chip's four experts under this chip's weights
        cfg = CFG._replace(first_expert=8)
    elif part == "rotation":     # every layer position-free
        cfg = CFG._replace(layer_types=("full_attention",) * 4)
    elif part == "embedding_scale":  # E_in as it lies, not times sqrt(hidden): a tenth of a layer's output
        params = dict(params, E_in=params["E_in"] / np.sqrt(CFG.hidden))
    return cfg, dict(params, layers=layers), e


@pytest.mark.parametrize(
    "part", ["attention_gate", "norm_after_attention", "norm_after_mlp", "key_norm", "shared_expert",
             "selecting_bias", "route_scale", "other_share", "rotation", "embedding_scale"],
)
def test_each_part_weighs_in_the_logits(part):
    """A part the initialisation drowns would be left out of everything the
    comparison sees: each one taken out moves the logits by more than the
    bfloat16 tolerance."""
    params, e, _ = _weights()
    tokens = jnp.asarray(_sessions((16,), seed=4)[0])
    sound = e[:N_ITEMS] @ np.asarray(trinity.reference_forward(CFG, params, tokens))[-1]
    cfg, broken, _ = _without(part)
    moved = e[:N_ITEMS] @ np.asarray(trinity.reference_forward(cfg, broken, tokens))[-1]
    assert np.abs(moved - sound).max() > 2 * BF16_ATOL, np.abs(moved - sound).max()  # the least, the scale's: 0.035


# ---- through the seam, the stepper and the app ------------------------------------

def _trinity_message(seed=7):
    from oryx_tpu.common.artifact import ModelArtifact

    tensors = {k: np.asarray(v) for k, v in trinity.init_tensors(CFG, seed, jnp.float32).items()}
    tensors["E"] = _weights(seed)[1][:N_ITEMS]  # the untied head is the catalog
    art = ModelArtifact("seq", tensors=tensors)
    for k, v in CFG.to_extensions().items():
        art.set_extension(k, v)
    art.set_extension("encoder", "trinity")
    art.set_extension("dtype", "float32")
    art.set_extension("ItemIDs", [f"i{j}" for j in range(N_ITEMS)])
    return art.to_string()


def test_trinity_artifact_answers_recommend_next_end_to_end():
    """MODEL message -> apply_seq_update -> ServingLayer -> GET
    /recommend-next: through the seam, the batched encoder step and
    TopKBatcher, against the plain reference's generation."""
    from oryx_tpu.apps.seq.serving import SeqServingModelManager
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://trinity-e2e"
    cfg = load_config(overlay={
        "oryx.id": "trinity-e2e",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common", "oryx_tpu.serving.resources.seq",
        ],
    })
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = SeqServingModelManager(cfg)
    manager.consume_key_message("MODEL", _trinity_message())
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    try:
        base = f"http://127.0.0.1:{serving.port}"
        reg = get_registry()
        value = lambda name: reg.counter(name).value()  # noqa: E731
        blocks0, routed0 = value("oryx_seq_blocks_total"), value("oryx_moe_routed_total")
        elsewhere0 = value("oryx_moe_routed_elsewhere_total")
        session = [3, 141, 59, 26, 5, 258, 97, 11, 200, 73, 88, 150]  # 12 events: past the window of 8
        path = "/".join(f"i{j}" for j in session)

        def get(p):
            req = urllib.request.Request(f"{base}{p}", headers={"Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read())

        answer = get(f"/recommend-next/{path}?howMany=10")
        params, e, _ = _weights()
        ref = trinity.reference_generate(CFG, params, e[:N_ITEMS], np.asarray(session, np.int32))
        assert len(answer) == CFG.basket
        for b, entry in enumerate(answer):
            assert entry["item"] == f"i{ref['row'][b]}" and entry["step"] == b
            logits = ref["logits"][b].copy()
            logits[session] = -np.inf
            want = np.argsort(-logits, kind="stable")[:10]
            assert [i for i, _ in entry["next"]] == [f"i{r}" for r in want]
            np.testing.assert_allclose([s for _, s in entry["next"]], logits[want], atol=F32_ATOL)
        # the expert layers' pairs, counted on the device by a prefill and by the decode steps: 11 + 4
        # tokens through three expert layers, 4 experts each, computed here or sent elsewhere
        here = value("oryx_moe_routed_total") - routed0
        elsewhere = value("oryx_moe_routed_elsewhere_total") - elsewhere0
        assert here + elsewhere == (11 + 4) * EXPERT_LAYERS * 4 and 0 < here < elsewhere
        # an item the model does not know is skipped as context
        again = get(f"/recommend-next/nobody/{path}?howMany=10")
        assert [e_["item"] for e_ in again] == [e_["item"] for e_ in answer]
        assert value("oryx_seq_blocks_total") - blocks0 == 2
        # several at once share dispatches and give what they give alone
        results = {}

        def one(j):
            results[j] = get(f"/recommend-next/{path}?howMany=10")

        threads = [threading.Thread(target=one, args=(j,)) for j in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results[j] == answer for j in range(6))
        page = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        for name in (
            'oryx_seq_steps_total{kind="decode"}', 'oryx_seq_step_tokens_total{kind="decode",tokens="real"}',
            'oryx_seq_step_tokens_total{kind="prefill",tokens="padded"}', "oryx_seq_denoise_steps_total",
            'oryx_seq_slot_state_bytes{state="window_kv"}', 'oryx_seq_slot_state_bytes{state="full_kv"}',
            "oryx_moe_routed_total", "oryx_moe_routed_elsewhere_total", "oryx_moe_experts_touched_total",
            "oryx_moe_expert_tokens_max_total", 'oryx_request_phase_seconds_count{phase="encode"}',
            'oryx_post_stage_seconds_count{stage="rerank"}',
        ):
            assert name in page, name
        steps = value("oryx_seq_denoise_steps_total")
        assert steps / value("oryx_seq_blocks_total") == 4  # four steps a basket
    finally:
        serving.close()


# ---- the chip's compiler, without the chip ------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["prefill_32", "step"])
def test_the_programs_compile_for_a_v5e(one_chip, program, monkeypatch):
    """Both programs at the published widths (the dense layer and ONE expert
    layer with 32 of its 256 experts held, a sliding layer each) through the
    chip's own compiler, the grouped product as the Pallas kernel the chip
    runs: what Mosaic or the memory refuses, it refuses here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the grouped product's compiled form
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    real = REAL._replace(layer_types=REAL.layer_types[:2])
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: trinity.init_params(real, 1)))
    state = on_chip(jax.eval_shape(lambda: trinity.init_state(real, 32)))
    rows = lambda n, dt=jnp.int32: sds((n,), dt)  # noqa: E731
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if program == "step":
            compiled = trinity.decode_step.lower(
                real, params, state, sds((229376, 3072), jnp.bfloat16), sds((), jnp.int32), rows(229376),
                rows(32), rows(32), rows(32, jnp.bool_), rows(32),
            ).compile()
        else:
            compiled = trinity.prefill.lower(
                real, params, state, sds((4, 32), jnp.int32), rows(4), rows(4), rows(4)
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    assert "gmm" in text and "trinity.moe" in text and "trinity.attn" in text and "trinity.shared" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 2.3e9       # the dense layer and a share of an expert layer
    assert memory.temp_size_in_bytes < 1.5e9           # and nothing of their size beside them
