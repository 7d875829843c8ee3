"""The JoyAI decoder (ops/joyai.py) against its plain reference, its latent
cache slot through the batched encoder step (serving/stepper.py) and the seq
app's request path, on the CPU at a small size: 3 layers (one dense, two of 16
sigmoid-routed experts beside a shared one), hidden 64, 4 heads of 16 + 8 query
dimensions over a 32-wide latent and an 8-wide rotated key, 300 items, seeded
weights. The expert layer's second routing rule (ops/moe.py) is held to the
plain form beside the first. `test_the_programs_compile_for_a_v5e` compiles
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

from oryx_tpu.ops import decoder, joyai, moe

CFG = joyai.JoyaiConfig(
    hidden=64, heads=4, q_rank=32, kv_rank=32, nope=16, rope=8, v_dim=16, intermediate=96,
    experts=16, expert_width=32, experts_per_token=4, shared_experts=1, first_dense=1,
    layers=3, vocab=300, basket=4, max_len=24,
)
REAL = joyai.JoyaiConfig(
    hidden=2048, heads=32, q_rank=1536, kv_rank=512, nope=128, rope=64, v_dim=128, intermediate=7168,
    experts=256, expert_width=768, experts_per_token=8, shared_experts=1, first_dense=1,
    layers=5, vocab=129280,
)
N_ITEMS = 300
# float32 served form against the float32 reference: accumulation order
# alone. Logits are about 0.05 at these weights
F32_ATOL = 2e-6
# bfloat16 served form against the float32 reference on the same bf16
# weights: the activations' and the cache's rounding, 2^-9 relative at each
BF16_ATOL = 3e-3


def _weights(seed=7, dtype=jnp.float32):
    """Parameters and the untied head: the view's rows are their own draw, at
    bfloat16's values (the served view is bfloat16 whatever the weights are),
    with capacity rows past the items; row i's input embedding is E_in row i."""
    params = joyai.init_params(CFG, seed, dtype)
    rng = np.random.default_rng(seed)
    e = np.zeros((384, CFG.hidden), np.float32)
    e[:N_ITEMS] = rng.standard_normal((N_ITEMS, CFG.hidden)).astype(np.float32) * 0.02
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
    counts = np.zeros(3, np.int64)
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


# ---- the model: shapes, weights, what a slot holds ------------------------------

def test_shapes_parameter_count_and_slot_bytes_at_the_published_widths():
    count = lambda l: sum(int(np.prod(s)) for k, s in joyai.layer_shapes(REAL, l).items())  # noqa: E731
    attn = sum(
        int(np.prod(s)) for k, s in joyai.layer_shapes(REAL, 1).items()
        if k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    )
    # ISSUE 41: attention 26.35M a layer, an expert layer 1,239.55M, the dense one 70.39M
    assert attn == pytest.approx(26.35e6, rel=1e-3)
    assert count(1) == pytest.approx(1239.55e6, rel=1e-4) and count(0) == pytest.approx(70.39e6, rel=1e-4)
    assert REAL.is_dense(0) and not REAL.is_dense(1) and REAL.qk_dim == 192 and REAL.positions == 104
    layers = sum(count(l) for l in range(5))
    assert layers == pytest.approx(5028.6e6, rel=1e-4)                  # 10.06 GB in bfloat16
    assert joyai.param_count(REAL) == layers + 129280 * 2048 + 2048     # and the input embedding
    # the whole published model: 40 layers, both embeddings
    whole = count(0) + 39 * count(1) + 2 * 129280 * 2048
    assert whole == pytest.approx(48.94e9, rel=1e-3)
    # a position a layer: a 512-wide latent and a 64-wide key for all 32 heads, 1,152 bytes
    # where keys and values a head would be 32 x (192 + 128) x 2 = 20,480
    state = joyai.state_bytes(REAL, 32)
    assert state == {"latent": 5 * 33 * 104 * 512 * 2, "rope_key": 5 * 33 * 104 * 64 * 2}
    assert sum(state.values()) == 5 * 33 * 104 * 1152
    shapes = jax.eval_shape(lambda: joyai.init_state(REAL, 32))
    assert shapes["latent"][0].shape == (33, 104, 512) and shapes["rope_key"][4].shape == (33, 104, 64)
    assert joyai.JoyaiConfig.from_extensions(
        {k: str(v) for k, v in REAL.to_extensions().items()}.get
    ) == REAL


@pytest.mark.parametrize(
    "key,value",
    [("scoring_func", "softmax"), ("n_group", "8"), ("rope_scaling", "{'type': 'yarn'}"),
     ("rope_interleave", "False"), ("tie_word_embeddings", "True"), ("qk_head_dim", "128")],
)
def test_a_form_the_program_does_not_compute_is_refused(key, value):
    ext = dict({k: str(v) for k, v in CFG.to_extensions().items()}, rope_scaling="None", n_group="1")
    assert joyai.JoyaiConfig.from_extensions(ext.get) == CFG
    with pytest.raises(ValueError, match=key):
        joyai.JoyaiConfig.from_extensions(dict(ext, **{key: value}).get)


def test_the_weights_are_a_pure_function_of_the_seed_and_the_bias_is_visible():
    t = joyai.init_tensors(CFG, 5, jnp.bfloat16)
    bias = np.asarray(t["L1.router_bias"])
    assert bias.dtype == np.float32 and bias.std() == pytest.approx(decoder.BIAS_INIT, rel=0.5)
    assert t["L1.wg"].dtype == jnp.bfloat16 and t["L1.wg"].shape == (16, 64, 32)
    assert np.all(np.asarray(t["L0.kv_norm"].astype(jnp.float32)) == 1.0)
    assert "L0.router" not in t and "L0.wg" in t and t["L0.wg"].shape == (64, 96)  # the leading dense layer
    assert "L1.shared_wg" in t and "L0.shared_wg" not in t
    again = joyai.init_tensors(CFG, 5, jnp.bfloat16)
    assert all(np.array_equal(np.asarray(t[k]), np.asarray(again[k])) for k in t)
    other = joyai.init_tensors(CFG, 6, jnp.bfloat16)
    assert not np.array_equal(np.asarray(t["L1.router_bias"]), np.asarray(other["L1.router_bias"]))
    params = joyai.params_of(CFG, t, jnp.bfloat16)
    assert params["layers"][1]["router_bias"].dtype == jnp.float32  # whatever the weights' dtype


def test_rope_turns_the_interleaved_pairs():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 3, 8)).astype(np.float32)
    pos = np.asarray([0, 1, 7, 50, 103])
    got = np.asarray(joyai.rope_interleaved(jnp.asarray(x), jnp.asarray(pos)[:, None], 32e6))
    pairs = x[..., 0::2] + 1j * x[..., 1::2]
    ang = pos[:, None, None] * (32e6 ** (-np.arange(0, 8, 2) / 8))[None, None, :]
    want = pairs * np.exp(1j * ang)
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=1e-5)
    np.testing.assert_array_equal(got[0], x[0])  # position 0 turns nothing


# ---- the expert layer's two routing rules, one path ------------------------------

def _moe_weights(seed=3, n_experts=8, h=64, f=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    wr = jax.random.normal(ks[0], (h, n_experts)) * 0.02
    wg = jax.random.normal(ks[1], (n_experts, h, f)) * 0.02
    wu = jax.random.normal(ks[2], (n_experts, h, f)) * 0.02
    wd = jax.random.normal(ks[3], (n_experts, f, h)) * 0.02
    return wr, wg, wu, wd, jax.random.normal(ks[4], (n_experts,)) * 0.1


RULES = {
    "softmax": {},
    "sigmoid": {"scoring": "sigmoid"},
    "sigmoid_bias_scale": {"scoring": "sigmoid", "scale": 2.5, "bias": True},
}


@pytest.mark.parametrize("load", ["even", "one_expert_takes_most", "an_expert_takes_none"])
@pytest.mark.parametrize("rule", list(RULES))
def test_expert_layer_against_the_plain_form_under_either_rule(rule, load):
    wr, wg, wu, wd, bias = _moe_weights()
    kw = dict(RULES[rule])
    if kw.pop("bias", False):
        kw["bias"] = bias
    u = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
    if load == "one_expert_takes_most":
        wr = wr.at[:, 5].set(0.0)
        u = u.at[:, 0].set(30.0)
        wr = wr.at[0, 5].set(1.0)
    elif load == "an_expert_takes_none":
        u = u.at[:, 0].set(30.0)
        wr = wr.at[0, 2].set(-1.0)
    y, counts = jax.jit(lambda u: moe.moe_apply(u, wr, wg, wu, wd, 4, **kw))(u)
    with jax.default_matmul_precision("highest"):
        ref = moe.moe_reference(u, wr, wg, wu, wd, 4, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-6)
    routed, touched, busiest = np.asarray(counts).tolist()
    assert routed == 40 * 4  # no token is dropped, whatever the load or the rule
    w, e = moe.route(u, wr, 4, **kw)
    np.testing.assert_allclose(np.asarray(w).sum(-1), kw.get("scale", 1.0), rtol=1e-6)
    sizes = np.bincount(np.asarray(e).ravel(), minlength=8)
    assert touched == int((sizes > 0).sum()) and busiest == int(sizes.max())
    if load == "one_expert_takes_most":
        assert busiest == 40
    if load == "an_expert_takes_none":
        assert sizes[2] == 0 and touched < 8


def test_the_correction_bias_selects_and_never_weighs():
    wr, _, _, _, _ = _moe_weights()
    u = jax.random.normal(jax.random.PRNGKey(4), (12, 64)) * 5.0
    s = np.asarray(jax.nn.sigmoid(jnp.dot(u, wr, precision="highest")))
    bias = jnp.zeros(8).at[6].set(10.0).at[1].set(-10.0)  # 6 always chosen, 1 never
    w, e = (np.asarray(a) for a in moe.route(u, wr, 4, scoring="sigmoid", bias=bias, scale=2.5))
    assert np.all((e == 6).any(-1)) and not np.any(e == 1)
    chosen = np.take_along_axis(s, e, axis=-1)
    np.testing.assert_allclose(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    plain, _ = moe.route(u, wr, 4, scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError):
        moe.route(u, wr, 4, scoring="tanh")


def test_padding_tokens_reach_no_expert_under_the_sigmoid_rule():
    wr, wg, wu, wd, bias = _moe_weights()
    kw = {"scoring": "sigmoid", "bias": bias, "scale": 2.5}
    u = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    live = jnp.arange(16) < 5
    y, counts = moe.moe_apply(u, wr, wg, wu, wd, 4, live, **kw)
    y5, counts5 = moe.moe_apply(u[:5], wr, wg, wu, wd, 4, **kw)
    np.testing.assert_allclose(np.asarray(y[:5]), np.asarray(y5), atol=1e-7)
    assert np.asarray(y[5:]).max() == 0.0
    assert np.asarray(counts).tolist() == np.asarray(counts5).tolist()


# ---- latent attention's two forms ------------------------------------------------

def test_the_absorbed_step_is_the_written_attention():
    """One query a row over a cache of latents, W_kvb absorbed, against the
    attention as written over the same positions (keys and values
    decompressed), float32: the same function."""
    params, _, _ = _weights()
    p = params["layers"][1]
    rng = np.random.default_rng(3)
    d, s = 5, 20
    c = jnp.asarray(rng.standard_normal((d, s, CFG.kv_rank)).astype(np.float32))
    k_rope = jnp.asarray(rng.standard_normal((d, s, CFG.rope)).astype(np.float32))
    q_nope = jnp.asarray(rng.standard_normal((d, CFG.heads, CFG.nope)).astype(np.float32))
    q_rope = jnp.asarray(rng.standard_normal((d, CFG.heads, CFG.rope)).astype(np.float32))
    seen = jnp.asarray(rng.integers(1, s + 1, d))
    allowed = jnp.arange(s)[None, :] < seen[:, None]
    absorbed = joyai._attend_absorbed(CFG, p, q_nope, q_rope, c, k_rope, allowed)
    written = joyai._attend_written(CFG, p, q_nope[:, None], q_rope[:, None], c, k_rope, allowed[:, None, :])
    assert absorbed.shape == (d, CFG.heads * CFG.v_dim)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(written[:, 0]), atol=1e-6)
    assert float(jnp.abs(absorbed).max()) > 1e-3


@pytest.mark.parametrize("n", [2, 7, 12, 24])
def test_prefill_then_step_is_the_full_pass_at_the_last_position(n):
    params, e, row_token = _weights()
    enc = joyai.JoyaiEncoder(CFG, jnp.float32)
    session = _sessions((n,), seed=n)[0]
    state = enc.init_state(enc.step_rows)
    state, hidden, tallied = enc.prefill(params, state, *enc.pack([session], 24, [3], enc.step_rows))
    counts = tallied["counts"]
    full = np.asarray(joyai.reference_forward(CFG, params, jnp.asarray(session)))
    z, latent, rope_key, step_counts = joyai._token_hidden(
        CFG, params, state, jnp.asarray([3]), jnp.asarray([n - 1]), jnp.asarray([True])
    )
    np.testing.assert_allclose(np.asarray(z[0]), full[-1], atol=F32_ATOL)
    # two expert layers: every real token's pairs, and the step's one token's
    assert int(counts[0]) == (n - 1) * CFG.experts_per_token * 2
    assert int(step_counts[0]) == CFG.experts_per_token * 2
    # the cache: n - 1 positions from the prefill, the n-th from the step, nothing behind
    for l in range(CFG.layers):
        filled = np.abs(np.asarray(latent[l][3])).sum(-1) > 0
        assert filled.tolist() == [True] * n + [False] * (CFG.positions - n)
        assert (np.abs(np.asarray(rope_key[l][3])).sum(-1) > 0).tolist() == filled.tolist()


@pytest.mark.parametrize(
    "dtype,atol", [(jnp.float32, F32_ATOL), (jnp.bfloat16, BF16_ATOL)], ids=["float32", "bfloat16"]
)
def test_cached_generation_against_the_references_full_forward(dtype, atol):
    params, e, row_token = _weights(dtype=dtype)
    enc = joyai.JoyaiEncoder(CFG, dtype)
    sessions = _sessions((13, 24, 2))
    out, _, counts = _generate(enc, params, (jnp.asarray(e, dtype), N_ITEMS, row_token), sessions)
    tokens_run = sum(len(s) - 1 for s in sessions) + 4 * len(sessions)
    assert counts[0] == tokens_run * CFG.experts_per_token * 2  # no pair dropped, a prefill's or a step's
    for i, session in enumerate(sessions):
        np.testing.assert_array_equal(out["step"][i], np.arange(4))
        # the reference's ONE full pass over [session + the basket the system
        # chose] at the four positions: its logits, and that each item fed
        # back was its argmax
        tokens = np.concatenate([session, out["row"][i][:-1]]).astype(np.int32)
        full = np.asarray(joyai.reference_forward(CFG, params, jnp.asarray(tokens)))[-4:]
        logits = e[:N_ITEMS] @ full.T
        np.testing.assert_allclose(e[:N_ITEMS] @ out["z"][i].T, logits, atol=atol)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(out["row"][i], logits.argmax(0))
            ref = joyai.reference_generate(CFG, params, e[:N_ITEMS], session)
            np.testing.assert_array_equal(out["row"][i], ref["row"])


def test_a_lower_precision_than_stated_fails_the_float32_tolerance():
    """The cache kept in bfloat16 under float32 weights: the served scores
    leave the reference by more than the float32 tolerance allows."""
    params, e, row_token = _weights()
    enc = joyai.JoyaiEncoder(CFG, jnp.float32)
    session = _sessions((13,))[0]
    head = (jnp.asarray(e), N_ITEMS, row_token)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim == 3 and a.shape[1] == CFG.positions else a,
        enc.init_state(enc.step_rows),
    )
    out, _, _ = _generate(enc, params, head, [session], state=low)
    tokens = np.concatenate([session, out["row"][0][:-1]]).astype(np.int32)
    full = np.asarray(joyai.reference_forward(CFG, params, jnp.asarray(tokens)))[-4:]
    err = np.abs(e[:N_ITEMS] @ out["z"][0].T - e[:N_ITEMS] @ full.T).max()
    assert err > 10 * F32_ATOL


@pytest.mark.parametrize("how", ["full_dispatch", "other_bucket", "both"])
def test_an_answer_is_the_same_alone_in_a_full_dispatch_and_in_either_bucket(how):
    params, e, row_token = _weights()  # no tensor's shape depends on max_len
    assert joyai.JoyaiEncoder(CFG, jnp.float32).length_buckets == (24,)  # under 32: one bucket
    enc = joyai.JoyaiEncoder(CFG._replace(max_len=40), jnp.float32)
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
    enc = joyai.JoyaiEncoder(CFG, jnp.float32)
    head = (jnp.asarray(e), N_ITEMS, row_token)
    first, second = _sessions((24, 4), seed=9)
    fresh, _, _ = _generate(enc, params, head, [second], slots_of=[5])
    _, used, _ = _generate(enc, params, head, [first], slots_of=[5])
    assert all(float(jnp.abs(used[k][l][5, 20]).max()) > 0 for k in ("latent", "rope_key") for l in range(3))
    again, after, _ = _generate(enc, params, head, [second], slots_of=[5], state=used)
    np.testing.assert_array_equal(fresh["row"][0], again["row"][0])
    np.testing.assert_array_equal(fresh["z"][0], again["z"][0])
    # 3 positions prefilled and 4 generated; the 17 the longer session left behind are gone,
    # and the bucket's padded positions wrote nothing
    for k in ("latent", "rope_key"):
        for l in range(CFG.layers):
            filled = np.abs(np.asarray(after[k][l][5])).sum(-1) > 0
            assert filled.tolist() == [True] * 7 + [False] * (CFG.positions - 7)
    # a session of ONE event prefills nothing: its slot starts empty all the same
    one = _sessions((1,), seed=2)
    lone, _, _ = _generate(enc, params, head, one, slots_of=[5])
    reused, _, _ = _generate(enc, params, head, one, slots_of=[5], state=after)
    np.testing.assert_array_equal(lone["z"][0], reused["z"][0])
    ref = joyai.reference_generate(CFG, params, e[:N_ITEMS], one[0])
    np.testing.assert_array_equal(lone["row"][0], ref["row"])


def test_padding_rows_touch_only_the_scratch_slot():
    params, e, row_token = _weights()
    enc = joyai.JoyaiEncoder(CFG, jnp.float32)
    out, state, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, row_token), _sessions((9,)), slots_of=[4])
    untouched = [s for s in range(enc.step_rows) if s != 4]
    for k in ("latent", "rope_key"):
        for l in range(CFG.layers):
            a = np.asarray(state[k][l])
            assert np.abs(a[4]).max() > 0 and np.abs(a[untouched]).max() == 0
    assert np.all(np.asarray(state["row"])[untouched] == -1) and np.all(out["row"][1:] == -1)


def test_a_view_row_with_no_input_embedding_feeds_zeros():
    """An item that came by UP after the model has a head row and no E_in
    row: chosen, it is fed back as zeros (the model has no id to stand for
    it), and the basket goes on."""
    params, e, row_token = _weights()
    enc = joyai.JoyaiEncoder(CFG, jnp.float32)
    session = _sessions((9,))[0]
    known, _, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, row_token), [session])
    first = int(known["row"][0][0])
    unknown = row_token.at[first].set(-1)
    got, state, _ = _generate(enc, params, (jnp.asarray(e), N_ITEMS, unknown), [session])
    assert got["row"][0][0] == first and not np.array_equal(got["z"][0][1], known["z"][0][1])
    assert np.isfinite(got["z"]).all()
    # what step 1 ran over: zeros where the known model fed E_in[first]
    tokens = jnp.asarray(np.concatenate([session, [first]]).astype(np.int32))
    blank = dict(params, E_in=params["E_in"].at[first].set(0.0))
    assert first not in session.tolist()
    full = np.asarray(joyai.reference_forward(CFG, blank, tokens))[-1]
    np.testing.assert_allclose(got["z"][0][1], full, atol=F32_ATOL)


# ---- every new part weighs in the output at this initialisation -------------------

def _without(part):
    """The reference's parameters or configuration with one new part taken
    out, as a fault would."""
    params, e, _ = _weights()
    cfg = CFG
    layers = [dict(p) for p in params["layers"]]
    for p in layers[1:]:
        if part == "shared_expert":
            p["shared_wd"] = jnp.zeros_like(p["shared_wd"])
        elif part == "correction_bias":
            p["router_bias"] = jnp.zeros_like(p["router_bias"])
    for p in layers:
        if part == "latent_norm":  # its weight halved: a norm left out moves the scale as much
            p["kv_norm"] = p["kv_norm"] * 0.5
    if part == "routed_scale":
        cfg = CFG._replace(routed_scale=1.0)
    return cfg, dict(params, layers=layers), e


@pytest.mark.parametrize(
    "part", ["shared_expert", "correction_bias", "routed_scale", "latent_norm"]
)
def test_each_new_part_weighs_in_the_logits(part):
    """Zeros for the bias or an expert's output the routing drowns would leave
    a part of the model out of everything the comparison sees: each one taken
    out moves the logits by far more than the bfloat16 tolerance. (The
    rotation is the next test's: at THESE widths the scores are flat.)"""
    params, e, _ = _weights()
    tokens = jnp.asarray(_sessions((16,), seed=4)[0])
    sound = e[:N_ITEMS] @ np.asarray(joyai.reference_forward(CFG, params, tokens))[-1]
    cfg, broken, _ = _without(part)
    moved = e[:N_ITEMS] @ np.asarray(joyai.reference_forward(cfg, broken, tokens))[-1]
    assert np.abs(moved - sound).max() > 5 * BF16_ATOL, np.abs(moved - sound).max()


def test_the_rotation_weighs_in_the_attention_at_the_published_widths():
    """normal x 0.02 at a hidden size of 64 gives scores of 1e-3 and a uniform
    softmax whatever the keys hold; at the published widths (one attention
    layer of them, 24 positions) the rotated part of the score spreads by 0.4
    and a cached key left unrotated moves the attention's output by a third."""
    names = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b")
    shapes = joyai.layer_shapes(REAL, 1)
    p = {
        k: jnp.ones(shapes[k], jnp.float32) if k.endswith("norm")
        else decoder.normal(jax.random.PRNGKey(i), shapes[k], jnp.float32)
        for i, k in enumerate(names)
    }
    u = jax.random.normal(jax.random.PRNGKey(9), (1, 24, REAL.hidden))
    pos = jnp.arange(24)[None, :]
    allowed = jnp.tril(jnp.ones((24, 24), bool))[None]
    q_nope, q_rope = joyai._queries(REAL, p, u, pos)
    c, k_rope = joyai._latent(REAL, p, u, pos)
    _, unrotated = joyai._latent(REAL, p, u, jnp.zeros_like(pos))
    rotated_part = jnp.einsum("rthd,rsd->rhts", q_rope, k_rope) / np.sqrt(REAL.qk_dim)
    assert 0.2 < float(jnp.std(rotated_part)) < 0.8
    sound = joyai._attend_written(REAL, p, q_nope, q_rope, c, k_rope, allowed)
    broken = joyai._attend_written(REAL, p, q_nope, q_rope, c, unrotated, allowed)
    moved = float(jnp.linalg.norm(sound[0, 8:] - broken[0, 8:]) / jnp.linalg.norm(sound[0, 8:]))
    assert moved > 0.1, moved


# ---- through the seam, the stepper and the app ------------------------------------

def _joyai_message(seed=7):
    from oryx_tpu.common.artifact import ModelArtifact

    tensors = {k: np.asarray(v) for k, v in joyai.init_tensors(CFG, seed, jnp.float32).items()}
    tensors["E"] = _weights(seed)[1][:N_ITEMS]  # the untied head is the catalog
    art = ModelArtifact("seq", tensors=tensors)
    for k, v in CFG.to_extensions().items():
        art.set_extension(k, v)
    art.set_extension("encoder", "joyai")
    art.set_extension("dtype", "float32")
    art.set_extension("ItemIDs", [f"i{j}" for j in range(N_ITEMS)])
    return art.to_string()


def test_joyai_artifact_answers_recommend_next_end_to_end():
    """MODEL message -> apply_seq_update -> ServingLayer -> GET
    /recommend-next: through the seam, the batched encoder step and
    TopKBatcher, against the plain reference's generation."""
    from oryx_tpu.apps.seq.serving import SeqServingModelManager
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://joyai-e2e"
    cfg = load_config(overlay={
        "oryx.id": "joyai-e2e",
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
    manager.consume_key_message("MODEL", _joyai_message())
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    try:
        base = f"http://127.0.0.1:{serving.port}"
        reg = get_registry()
        blocks0 = reg.counter("oryx_seq_blocks_total").value()
        routed0 = reg.counter("oryx_moe_routed_total").value()
        session = [3, 141, 59, 26, 5, 258, 97]
        path = "/".join(f"i{j}" for j in session)

        def get(p):
            req = urllib.request.Request(f"{base}{p}", headers={"Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read())

        answer = get(f"/recommend-next/{path}?howMany=10")
        params, e, _ = _weights()
        ref = joyai.reference_generate(CFG, params, e[:N_ITEMS], np.asarray(session, np.int32))
        assert len(answer) == CFG.basket
        for b, entry in enumerate(answer):
            assert entry["item"] == f"i{ref['row'][b]}" and entry["step"] == b
            logits = ref["logits"][b].copy()
            logits[session] = -np.inf
            want = np.argsort(-logits, kind="stable")[:10]
            assert [i for i, _ in entry["next"]] == [f"i{r}" for r in want]
            np.testing.assert_allclose([s for _, s in entry["next"]], logits[want], atol=F32_ATOL)
        # the expert layers' pairs, counted on the device by a prefill and by the decode steps:
        # 6 + 4 tokens through two expert layers, 4 experts each
        assert reg.counter("oryx_moe_routed_total").value() - routed0 == (6 + 4) * 2 * 4
        # an item the model does not know is skipped as context
        again = get(f"/recommend-next/nobody/{path}?howMany=10")
        assert [e_["item"] for e_ in again] == [e_["item"] for e_ in answer]
        assert reg.counter("oryx_seq_blocks_total").value() - blocks0 == 2
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
            'oryx_seq_slot_state_bytes{state="latent"}', 'oryx_seq_slot_state_bytes{state="rope_key"}',
            "oryx_moe_routed_total", "oryx_moe_experts_touched_total", "oryx_moe_expert_tokens_max_total",
            'oryx_request_phase_seconds_count{phase="encode"}',
            'oryx_post_stage_seconds_count{stage="rerank"}',
        ):
            assert name in page, name
        steps = reg.counter("oryx_seq_denoise_steps_total").value()
        assert steps / reg.counter("oryx_seq_blocks_total").value() == 4  # four steps a basket
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
    layer of 256 experts) through the chip's own compiler, the grouped
    product as the Pallas kernel the chip runs: what Mosaic or the memory
    refuses, it refuses here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the grouped product's compiled form
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    real = REAL._replace(layers=2)
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: joyai.init_params(real, 1)))
    state = on_chip(jax.eval_shape(lambda: joyai.init_state(real, 32)))
    rows = lambda n, dt=jnp.int32: sds((n,), dt)  # noqa: E731
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if program == "step":
            compiled = joyai.decode_step.lower(
                real, params, state, sds((163840, 2048), jnp.bfloat16), sds((), jnp.int32), rows(163840),
                rows(32), rows(32), rows(32, jnp.bool_), rows(32),
            ).compile()
        else:
            compiled = joyai.prefill.lower(
                real, params, state, sds((4, 32), jnp.int32), rows(4), rows(4), rows(4)
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    assert "gmm" in text and "joyai.moe" in text and "joyai.attn" in text and "joyai.shared" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 2.4e9       # one whole expert layer among them
    assert memory.temp_size_in_bytes < 1.5e9           # and nothing of its size beside it
