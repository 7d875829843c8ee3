"""The Xing decoder (ops/xing.py) against its plain reference, its latent cache
slot through the batched encoder step (serving/stepper.py) and the seq app's
request path, on the CPU at a small size: 3 layers (one dense, two of 8
sigmoid-routed experts, 4 a token, beside a shared one), hidden 64, four
residual streams mixed by a Sinkhorn-projected matrix around every sublayer,
4 heads of 16 + 8 query dimensions over a 32-wide latent and an 8-wide
rotated key at YaRN's frequencies, 300 items, seeded weights.
`test_the_programs_compile_for_a_v5e` compiles both programs at the
published widths for a described chip.
"""

from __future__ import annotations

import json
import math
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import decoder, mla, xing

YARN = (64.0, 4096, 32.0, 1.0, 1.0)  # factor, original window, beta_fast, beta_slow, mscale_all_dim
CFG = xing.XingConfig(
    hidden=64, heads=4, q_rank=32, kv_rank=32, nope=16, rope=8, v_dim=16, intermediate=96,
    experts=8, expert_width=32, experts_per_token=4, shared_experts=1, first_dense=1,
    layers=3, vocab=300, yarn=YARN, routed_scale=2.0, basket=4, max_len=24,
)
REAL = xing.XingConfig(
    hidden=3584, heads=32, q_rank=768, kv_rank=512, nope=128, rope=64, v_dim=128, intermediate=9216,
    experts=64, expert_width=1024, experts_per_token=4, shared_experts=1, first_dense=1,
    layers=7, vocab=131072, yarn=YARN,
)
N_ITEMS = 300
# float32 served form against the float32 reference: accumulation order
# alone (the streams' mixes and the maps are float32 on both sides)
F32_ATOL = 3e-6
# bfloat16 served form against the float32 reference on the same bf16
# weights: the activations' and the cache's rounding, 2^-9 relative at each
BF16_ATOL = 3e-3


# phi drawn normal x 0.02 makes `a` spread by 0.02 x sqrt(4 x 3,584) = 2.4 at
# the published widths and by 0.32 at a hidden size of 64, where every map
# would be nearly constant and the Sinkhorn converge in one iteration: here
# phi is scaled so that `a` spreads as it does at the published widths
PHI_SCALE = math.sqrt(3584 / 64)


def _weights(seed=7, dtype=jnp.float32, cfg=CFG):
    """Parameters (phi at the published spread) and the untied head: the
    view's rows are their own draw, at bfloat16's values, with capacity rows
    past the items; row i's input embedding is E_in row i."""
    params = xing.init_params(cfg, seed, dtype)
    params["layers"] = [
        {k: v * PHI_SCALE if k.endswith("_phi") else v for k, v in p.items()} for p in params["layers"]
    ]
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
    out, the state after it, the counts summed over every dispatch, the
    largest Sinkhorn error of any dispatch, the matrices left unconverged)."""
    state = enc.init_state(enc.step_rows) if state is None else state
    everyone = list(sessions) + list(fill)
    slots_of = slots_of or list(range(len(everyone)))
    counts, err, unconverged = np.zeros(3, np.int64), 0.0, 0
    for lo in range(0, len(everyone), enc.prefill_rows):
        group = everyone[lo:lo + enc.prefill_rows]
        b = bucket or min(b for b in enc.length_buckets if b >= max(enc.length(p) for p in group))
        packed = enc.pack(group, b, slots_of[lo:lo + len(group)], enc.step_rows)
        state, _, counted = enc.prefill(params, state, *packed)
        counts += np.asarray(counted["counts"])
        err = max(err, float(counted["hc_error"]))
        unconverged += int(counted["hc_unconverged"])
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
        err = max(err, float(out["hc_error"]))
        unconverged += int(out["hc_unconverged"])
    return {k: np.asarray(v) for k, v in out.items()}, state, counts, err, unconverged


# ---- the model: shapes, weights, what a slot holds ------------------------------

def test_shapes_parameter_count_and_slot_bytes_at_the_published_widths():
    count = lambda l: sum(int(np.prod(s)) for s in xing.layer_shapes(REAL, l).values())  # noqa: E731
    shapes = xing.layer_shapes(REAL, 1)
    part = lambda names: sum(int(np.prod(shapes[k])) for k in names)  # noqa: E731
    # the cut's arithmetic: MLA 28.41M a layer, mHC 0.69M a layer, an expert 11.01M,
    # a dense layer 128.2M, an expert layer whole 745.0M
    assert part(("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")) == pytest.approx(28.41e6, rel=1e-3)
    maps = [k for k in shapes if k.startswith("hc_")]
    assert part(maps) == 2 * (4 * 3584 * 24 + 3 + 24) and part(maps) == pytest.approx(0.69e6, rel=1e-2)
    assert shapes["hc_attn_phi"] == (14_336, 24) and shapes["hc_ffn_bias"] == (24,)
    assert 3 * 3584 * 1024 == pytest.approx(11.01e6, rel=1e-3)
    assert count(0) == pytest.approx(128.2e6, rel=1e-3) and count(1) == pytest.approx(745.0e6, rel=1e-3)
    layers = count(0) + 6 * count(1)
    assert layers == pytest.approx(4598e6, rel=1e-3)                    # 9.196 GB in bfloat16
    assert xing.param_count(REAL) == layers + 131072 * 3584 + 3584     # and the input embedding
    # the whole published model: 2 dense and 38 expert layers, both embeddings
    assert 2 * count(0) + 38 * count(1) + 2 * 131072 * 3584 == pytest.approx(29.5e9, rel=2e-3)
    # a slot holds what a JoyAI slot holds: the streams are never cached
    state = xing.state_bytes(REAL, 32)
    assert state == {"latent": 7 * 33 * 104 * 512 * 2, "rope_key": 7 * 33 * 104 * 64 * 2}
    assert sum(state.values()) == pytest.approx(27.7e6, rel=2e-3)
    shaped = jax.eval_shape(lambda: xing.init_state(REAL, 32))
    assert set(shaped) == {"latent", "rope_key", "x_in", "z", "row", "step"}
    assert shaped["latent"][6].shape == (33, 104, 512)
    assert xing.XingConfig.from_extensions({k: str(v) for k, v in REAL.to_extensions().items()}.get) == REAL


def test_the_published_rope_scaling_reads_as_yarn():
    """The source's own `rope_scaling`, as JSON or as a Python dict's text."""
    stated = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
              "original_max_position_embeddings": 4096, "type": "yarn"}
    ext = {k: str(v) for k, v in REAL.to_extensions().items()}
    for text in (json.dumps(stated), str(stated)):
        assert xing.XingConfig.from_extensions(dict(ext, rope_scaling=text).get).yarn == YARN
    assert xing.XingConfig.from_extensions(dict(ext, rope_scaling="null").get).yarn is None


@pytest.mark.parametrize(
    "key,value",
    [("scoring_func", "softmax"), ("n_group", "8"), ("rope_interleave", "False"), ("tie_word_embeddings", "True"),
     ("qk_head_dim", "128"), ("rope_scaling", '{"type": "linear", "factor": 4}'),
     ("rope_scaling", '{"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "mscale": 1, '
                      '"mscale_all_dim": 0}')],
    ids=["scoring", "groups", "rotate_half", "tied", "qk_head_dim", "linear_scaling", "scaled_cos_sin"],
)
def test_a_form_the_program_does_not_compute_is_refused(key, value):
    ext = {k: str(v) for k, v in CFG.to_extensions().items()}
    assert xing.XingConfig.from_extensions(ext.get) == CFG
    with pytest.raises(ValueError, match=key):
        xing.XingConfig.from_extensions(dict(ext, **{key: value}).get)


def test_the_weights_are_a_pure_function_of_the_seed_and_the_maps_are_float32():
    t = xing.init_tensors(CFG, 5, jnp.bfloat16)
    phi = np.asarray(t["L1.hc_attn_phi"])
    assert phi.dtype == np.float32 and phi.shape == (256, 24) and phi.std() == pytest.approx(0.02, rel=0.1)
    assert np.all(np.asarray(t["L2.hc_ffn_alpha"]) == 1.0) and t["L2.hc_ffn_alpha"].dtype == jnp.float32
    bias = np.asarray(t["L0.hc_ffn_bias"])
    assert bias.dtype == np.float32 and bias.std() == pytest.approx(decoder.BIAS_INIT, rel=0.5)
    assert t["L1.wg"].dtype == jnp.bfloat16 and t["L1.wg"].shape == (8, 64, 32)
    assert "L0.router" not in t and t["L0.wg"].shape == (64, 96) and "L1.shared_wg" in t
    again = xing.init_tensors(CFG, 5, jnp.bfloat16)
    assert all(np.array_equal(np.asarray(t[k]), np.asarray(again[k])) for k in t)
    params = xing.params_of(CFG, t, jnp.bfloat16)
    assert params["layers"][1]["hc_attn_phi"].dtype == jnp.float32  # whatever the weights' dtype


# ---- YaRN at the published numbers -----------------------------------------------

def test_yarns_frequencies_and_softmax_scale_at_the_published_numbers():
    """64 rotated dimensions, theta 10,000, factor 64 over an original window
    of 4,096, beta_fast 32, beta_slow 1: pairs 0-9 keep theta^(-2i/64), pairs
    23-31 are divided by 64, the pairs between blend along a linear ramp from
    10 to 23; the softmax scale is 192^-0.5 x (0.1 ln 64 + 1)^2."""
    inv = REAL.frequencies.astype(np.float64)
    i = np.arange(32)
    base = 10_000.0 ** (-2.0 * i / 64)
    kept = 1.0 - np.clip((i - 10) / (23 - 10), 0.0, 1.0)
    np.testing.assert_allclose(inv, base * (kept + (1.0 - kept) / 64), rtol=1e-6)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 64, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 64))
    mscale = 0.1 * math.log(64) + 1.0
    assert mscale == pytest.approx(1.41589, abs=1e-5)
    assert 1.0 / REAL.divisor == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert math.sqrt(192) / REAL.divisor == pytest.approx(2.0047, abs=1e-4)
    # no scaling: the plain rotation and 1 / sqrt(192)
    plain = REAL._replace(yarn=None)
    np.testing.assert_allclose(plain.frequencies, base, rtol=1e-6)
    assert plain.divisor == math.sqrt(192)
    # the frequencies turn the interleaved pairs
    x = np.random.default_rng(0).standard_normal((3, 64)).astype(np.float32)
    pos = np.asarray([0, 5, 103])
    got = np.asarray(mla.rope_interleaved(jnp.asarray(x), jnp.asarray(pos), jnp.asarray(REAL.frequencies)))
    want = (x[:, 0::2] + 1j * x[:, 1::2]) * np.exp(1j * pos[:, None] * REAL.frequencies[None, :])
    np.testing.assert_allclose(got[:, 0::2], want.real, atol=2e-5)
    np.testing.assert_allclose(got[:, 1::2], want.imag, atol=2e-5)


# ---- the Sinkhorn projection -------------------------------------------------------

def test_the_sinkhorn_projection_is_doubly_stochastic_and_the_clamp_keeps_it_finite():
    """At logits spread as the published widths spread them (0.02 x sqrt(4 x
    3,584) = 2.4), 20 iterations leave most matrices doubly stochastic within
    1e-3 and every one within 0.1; 5 iterations leave it far looser. Logits of
    +-1e4 are clamped to +-30 first: the matrix stays finite and
    normalised."""
    r = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 20_000)) * 2.4
    err = np.asarray(xing.sinkhorn_error(xing.sinkhorn(r, 20, 1e-6)))
    assert np.median(err) < 1e-4 and np.mean(err < 1e-3) > 0.75 and err.max() < 0.1
    few = np.asarray(xing.sinkhorn_error(xing.sinkhorn(r, 5, 1e-6)))
    assert np.median(few) > 10 * np.median(err) and few.max() > 2 * err.max()
    # the same function, row i column j, as a plain loop over one matrix
    one = np.exp(np.asarray(r[:, :, 0], np.float64))
    for _ in range(20):
        one = one / (one.sum(0, keepdims=True) + 1e-6)
        one = one / (one.sum(1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(xing.sinkhorn(r, 20, 1e-6))[:, :, 0], one, rtol=1e-4)
    huge = jnp.clip(jnp.asarray(np.random.default_rng(1).choice([-1e4, 1e4], (4, 4, 64)), jnp.float32), -30, 30)
    m = np.asarray(xing.sinkhorn(huge, 20, 1e-6))
    assert np.isfinite(m).all() and np.abs(m.sum(1) - 1).max() < 1e-3


def test_the_maps_are_the_references_and_an_identity_mix_is_a_plain_residual():
    """The served maps over streams laid out [n, tokens, H] against the
    reference's, written token by token over [tokens, n, H]."""
    params, _, _ = _weights()
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 6, CFG.hidden))
    pre, post, m = xing._maps(CFG, p, "ffn", x)
    with jax.default_matmul_precision("highest"):
        r_pre, r_post, r_m = xing._reference_maps(CFG, p, "ffn", jnp.swapaxes(x, 0, 1))
    np.testing.assert_allclose(np.asarray(pre).T, np.asarray(r_pre), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(post).T, np.asarray(r_post), rtol=1e-5)
    np.testing.assert_allclose(np.moveaxis(np.asarray(m), -1, 0), np.asarray(r_m), rtol=1e-4, atol=1e-7)
    assert 0 < float(post.min()) and float(post.max()) < 2.0
    # what the mix does: M X + Hpost f(h); with M = I and Hpost = 1 every stream is x + f(h)
    y = jax.random.normal(jax.random.PRNGKey(4), (6, CFG.hidden))
    eye = jnp.broadcast_to(jnp.eye(4)[:, :, None], (4, 4, 6))
    out = xing._write(x, (jnp.ones((4, 6)), eye), y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x + y[None]), rtol=1e-6)


# ---- generation through the cache against the reference ------------------------------

@pytest.mark.parametrize("n", [2, 7, 12, 24])
def test_prefill_then_step_is_the_full_pass_at_the_last_position(n):
    params, _, _ = _weights()
    enc = xing.XingEncoder(CFG, jnp.float32)
    session = _sessions((n,), seed=n)[0]
    state = enc.init_state(enc.step_rows)
    state, _, counted = enc.prefill(params, state, *enc.pack([session], 24, [3], enc.step_rows))
    full = np.asarray(xing.reference_forward(CFG, params, jnp.asarray(session)))
    z, latent, rope_key, tallies = xing._token_hidden(
        CFG, params, state, jnp.asarray([3]), jnp.asarray([n - 1]), jnp.asarray([True])
    )
    step_counts, err = tallies["counts"], tallies["hc_error"]
    np.testing.assert_allclose(np.asarray(z[0]), full[-1], atol=F32_ATOL)
    # two expert layers: every real token's pairs, and the step's one token's
    assert int(counted["counts"][0]) == (n - 1) * CFG.experts_per_token * 2
    assert int(step_counts[0]) == CFG.experts_per_token * 2
    assert 0.0 <= float(err) < 0.1 and (n == 2 or 0.0 < float(counted["hc_error"]) < 0.1)
    for l in range(CFG.layers):
        filled = np.abs(np.asarray(latent[l][3])).sum(-1) > 0
        assert filled.tolist() == [True] * n + [False] * (CFG.positions - n)
        assert (np.abs(np.asarray(rope_key[l][3])).sum(-1) > 0).tolist() == filled.tolist()


@pytest.mark.parametrize(
    "dtype,atol", [(jnp.float32, F32_ATOL), (jnp.bfloat16, BF16_ATOL)], ids=["float32", "bfloat16"]
)
def test_cached_generation_against_the_references_full_forward(dtype, atol):
    params, e, row_token = _weights(dtype=dtype)
    enc = xing.XingEncoder(CFG, dtype)
    sessions = _sessions((13, 24, 2))
    out, _, counts, err, unconverged = _generate(enc, params, (jnp.asarray(e, dtype), N_ITEMS, row_token), sessions)
    tokens_run = sum(len(s) - 1 for s in sessions) + 4 * len(sessions)
    assert counts[0] == tokens_run * CFG.experts_per_token * 2  # no pair dropped, a prefill's or a step's
    assert 0.0 < err < 0.1
    # a real token's matrices, one a sublayer: some of the tail left unconverged, far from all
    assert 0 < unconverged < 0.5 * tokens_run * 2 * CFG.layers
    for i, session in enumerate(sessions):
        np.testing.assert_array_equal(out["step"][i], np.arange(4))
        # the reference's ONE full pass over [session + the basket the system
        # chose] at the four positions: its logits, and that each item fed
        # back was its argmax
        tokens = np.concatenate([session, out["row"][i][:-1]]).astype(np.int32)
        full = np.asarray(xing.reference_forward(CFG, params, jnp.asarray(tokens)))[-4:]
        logits = e[:N_ITEMS] @ full.T
        np.testing.assert_allclose(e[:N_ITEMS] @ out["z"][i].T, logits, atol=atol)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(out["row"][i], logits.argmax(0))
    if dtype == jnp.float32:  # and the reference's own generation, a pass a position
        ref = xing.reference_generate(CFG, params, e[:N_ITEMS], sessions[0])
        np.testing.assert_array_equal(out["row"][0], ref["row"])


def _served_error(monkeypatch=None, patch=None):
    """The served float32 scores' largest distance from the reference's over a
    basket, the program broken underneath by `patch` (module attribute ->
    replacement) where one is given; the largest Sinkhorn error and the share
    of the real tokens' matrices left unconverged."""
    params, e, row_token = _weights()
    if patch:
        for name, value in patch.items():
            monkeypatch.setattr(xing, name, value)
        jax.clear_caches()
    try:
        enc = xing.XingEncoder(CFG, jnp.float32)
        session = _sessions((13,))[0]
        out, _, _, err, unconverged = _generate(enc, params, (jnp.asarray(e), N_ITEMS, row_token), [session])
    finally:
        if patch:
            monkeypatch.undo()
            jax.clear_caches()
    tokens = np.concatenate([session, out["row"][0][:-1]]).astype(np.int32)
    full = np.asarray(xing.reference_forward(CFG, params, jnp.asarray(tokens)))[-4:]
    matrices = (len(session) - 1 + enc.steps) * 2 * CFG.layers
    return np.abs(e[:N_ITEMS] @ out["z"][0].T - e[:N_ITEMS] @ full.T).max(), err, unconverged / matrices


def _bf16_maps(cfg, p, sub, x):
    """The maps' product and the Sinkhorn in bfloat16 where float32 is stated."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    q = {k: low(v) if k.startswith(f"hc_{sub}") else v for k, v in p.items()}
    pre, post, m = xing_maps(cfg, q, sub, low(x))
    return low(pre), low(post), low(m)


xing_maps = xing._maps


@pytest.mark.parametrize("fault", ["none", "maps_in_bfloat16", "sinkhorn_5_iterations", "streams_collapsed_to_one"])
def test_a_lower_precision_or_a_fault_fails_the_float32_tolerance(fault, monkeypatch):
    """bfloat16 where float32 is stated (the maps' product and the Sinkhorn),
    the Sinkhorn cut to 5 iterations, the four streams collapsed to one: each
    moves the served scores off the reference by more than the float32
    tolerance allows; the first two leave most matrices unconverged, which 20
    iterations in float32 do not, and the cut shows in the gauge too."""
    def five(r, iters, eps):
        return SINKHORN(r, 5, eps)

    def one_stream(cfg, p, sub, x):
        n, rest = cfg.hc_mult, x.shape[1:-1]
        pre = jnp.zeros((n, *rest)).at[0].set(1.0)
        return pre, jnp.ones((n, *rest)), jnp.broadcast_to(jnp.eye(n).reshape(n, n, *[1] * len(rest)), (n, n, *rest))

    patches = {
        "none": None, "maps_in_bfloat16": {"_maps": _bf16_maps}, "sinkhorn_5_iterations": {"sinkhorn": five},
        "streams_collapsed_to_one": {"_maps": one_stream},
    }
    moved, err, unconverged = _served_error(monkeypatch, patches[fault])
    if fault == "none":
        assert moved < F32_ATOL and 0 < err < 0.1 and unconverged < 0.5
    else:
        assert moved > 10 * F32_ATOL, (fault, moved)
    if fault in ("maps_in_bfloat16", "sinkhorn_5_iterations"):
        assert unconverged > 0.5, (fault, unconverged)
    if fault == "sinkhorn_5_iterations":
        assert err > 0.1  # the gauge sees the cut
    if fault == "streams_collapsed_to_one":
        assert err == 0.0 and unconverged == 0.0


SINKHORN = xing.sinkhorn


@pytest.mark.parametrize("how", ["full_dispatch", "other_bucket", "both"])
def test_an_answer_is_the_same_alone_in_a_full_dispatch_and_in_either_bucket(how):
    params, e, row_token = _weights()  # no tensor's shape depends on max_len
    assert xing.XingEncoder(CFG, jnp.float32).length_buckets == (24,)  # under 32: one bucket
    enc = xing.XingEncoder(CFG._replace(max_len=40), jnp.float32)
    assert enc.length_buckets == (32, 40)
    head = (jnp.asarray(e), N_ITEMS, row_token)
    mine = _sessions((13,))
    alone, *_ = _generate(enc, params, head, mine)
    fill, slots_of, bucket = (), None, None
    if how in ("full_dispatch", "both"):
        fill = _sessions([3 + (5 * j) % 30 for j in range(enc.step_rows - 1)], seed=5)
        slots_of = [enc.step_rows - 1] + list(range(enc.step_rows - 1))  # and another slot
    if how in ("other_bucket", "both"):
        bucket = 40
    shared, *_ = _generate(enc, params, head, mine, slots_of=slots_of, fill=fill, bucket=bucket)
    np.testing.assert_array_equal(alone["row"][0], shared["row"][0])
    np.testing.assert_allclose(e @ alone["z"][0].T, e @ shared["z"][0].T, atol=F32_ATOL)


def test_a_slot_taken_again_starts_empty_and_a_padded_position_writes_nothing():
    params, e, row_token = _weights()
    enc = xing.XingEncoder(CFG, jnp.float32)
    head = (jnp.asarray(e), N_ITEMS, row_token)
    first, second = _sessions((24, 4), seed=9)
    fresh, *_ = _generate(enc, params, head, [second], slots_of=[5])
    _, used, *_ = _generate(enc, params, head, [first], slots_of=[5])
    assert all(float(jnp.abs(used[k][l][5, 20]).max()) > 0 for k in ("latent", "rope_key") for l in range(3))
    again, after, *_ = _generate(enc, params, head, [second], slots_of=[5], state=used)
    np.testing.assert_array_equal(fresh["row"][0], again["row"][0])
    np.testing.assert_array_equal(fresh["z"][0], again["z"][0])
    # 3 positions prefilled and 4 generated; the 17 the longer session left behind are gone
    for k in ("latent", "rope_key"):
        for l in range(CFG.layers):
            filled = np.abs(np.asarray(after[k][l][5])).sum(-1) > 0
            assert filled.tolist() == [True] * 7 + [False] * (CFG.positions - 7)
    # padding rows touch the scratch slot alone
    untouched = [s for s in range(enc.step_rows) if s != 5]
    assert np.abs(np.asarray(after["latent"][0])[untouched]).max() == 0


# ---- every new part weighs in the output at this initialisation -------------------

def _without(part):
    params, e, _ = _weights()
    cfg = CFG
    layers = [dict(p) for p in params["layers"]]
    for p in layers:
        if part == "maps_bias":
            for sub in xing.SUBLAYERS:
                p[f"hc_{sub}_bias"] = jnp.zeros_like(p[f"hc_{sub}_bias"])
        elif part == "maps_phi":
            for sub in xing.SUBLAYERS:
                p[f"hc_{sub}_phi"] = jnp.zeros_like(p[f"hc_{sub}_phi"])
    if part == "yarn":
        cfg = CFG._replace(yarn=None)
    if part == "routed_scale":
        cfg = CFG._replace(routed_scale=1.0)
    return cfg, dict(params, layers=layers), e


@pytest.mark.parametrize("part", ["maps_bias", "maps_phi", "yarn", "routed_scale"])
def test_each_new_part_weighs_in_the_logits(part):
    """The maps' biases, their input-dependent part (phi), YaRN's rotation
    and scale, the routed scale: each taken out moves the logits by more than
    the float32 tolerance."""
    params, e, _ = _weights()
    tokens = jnp.asarray(_sessions((16,), seed=4)[0])
    sound = e[:N_ITEMS] @ np.asarray(xing.reference_forward(CFG, params, tokens))[-1]
    cfg, broken, _ = _without(part)
    moved = e[:N_ITEMS] @ np.asarray(xing.reference_forward(cfg, broken, tokens))[-1]
    assert np.abs(moved - sound).max() > 10 * F32_ATOL, np.abs(moved - sound).max()


# ---- through the seam, the stepper and the app ------------------------------------

def _xing_message(seed=7):
    from oryx_tpu.common.artifact import ModelArtifact

    tensors = {
        k: np.asarray(v) * (PHI_SCALE if k.endswith("_phi") else 1.0)
        for k, v in xing.init_tensors(CFG, seed, jnp.float32).items()
    }
    tensors["E"] = _weights(seed)[1][:N_ITEMS]  # the untied head is the catalog
    art = ModelArtifact("seq", tensors=tensors)
    for k, v in CFG.to_extensions().items():
        art.set_extension(k, v)
    art.set_extension("encoder", "xing")
    art.set_extension("dtype", "float32")
    art.set_extension("ItemIDs", [f"i{j}" for j in range(N_ITEMS)])
    return art.to_string()


def test_xing_artifact_answers_recommend_next_end_to_end():
    """MODEL message -> apply_seq_update -> ServingLayer -> GET
    /recommend-next: through the seam, the batched encoder step and
    TopKBatcher, against the plain reference's generation; the Sinkhorn
    error reaches /metrics as the largest since the last scrape."""
    from oryx_tpu.apps.seq.serving import SeqServingModelManager
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://xing-e2e"
    cfg = load_config(overlay={
        "oryx.id": "xing-e2e",
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
    manager.consume_key_message("MODEL", _xing_message())
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    try:
        base = f"http://127.0.0.1:{serving.port}"
        reg = get_registry()
        routed0 = reg.counter("oryx_moe_routed_total").value()
        gauge = reg.gauge("oryx_seq_hc_sinkhorn_error")
        gauge.value()  # read: what earlier tests' dispatches left is gone
        session = [3, 141, 59, 26, 5, 258, 97]
        path = "/".join(f"i{j}" for j in session)

        def get(p):
            req = urllib.request.Request(f"{base}{p}", headers={"Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read())

        answer = get(f"/recommend-next/{path}?howMany=10")
        params, e, _ = _weights()
        ref = xing.reference_generate(CFG, params, e[:N_ITEMS], np.asarray(session, np.int32))
        assert len(answer) == CFG.basket
        for b, entry in enumerate(answer):
            assert entry["item"] == f"i{ref['row'][b]}" and entry["step"] == b
            logits = ref["logits"][b].copy()
            logits[session] = -np.inf
            want = np.argsort(-logits, kind="stable")[:10]
            assert [i for i, _ in entry["next"]] == [f"i{r}" for r in want]
            np.testing.assert_allclose([s for _, s in entry["next"]], logits[want], atol=F32_ATOL)
        # 6 + 4 tokens through two expert layers, 4 experts each
        assert reg.counter("oryx_moe_routed_total").value() - routed0 == (6 + 4) * 2 * 4
        page = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        line = [ln for ln in page.splitlines() if ln.startswith("oryx_seq_hc_sinkhorn_error ")]
        assert len(line) == 1 and 0.0 < float(line[0].split()[1]) < 0.1
        assert gauge.value() == 0.0  # the scrape read it: nothing dispatched since
        results = {}

        def one(j):
            results[j] = get(f"/recommend-next/{path}?howMany=10")

        threads = [threading.Thread(target=one, args=(j,)) for j in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results[j] == answer for j in range(6))
        assert 0.0 < gauge.value() < 0.1
        page = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        for name in (
            'oryx_seq_steps_total{kind="decode"}', 'oryx_seq_slot_state_bytes{state="latent"}',
            'oryx_seq_slot_state_bytes{state="rope_key"}', "oryx_moe_experts_touched_total",
            "oryx_seq_hc_sinkhorn_error", "oryx_seq_hc_unconverged_total",
        ):
            assert name in page, name
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


@pytest.mark.parametrize("program", ["prefill_100", "step"])
def test_the_programs_compile_for_a_v5e(one_chip, program, monkeypatch):
    """Both programs at the published widths (the dense layer and ONE expert
    layer of 64 experts, four streams and 20 Sinkhorn iterations) through
    the chip's own compiler, the grouped product as the Pallas kernel the
    chip runs: what Mosaic or the memory refuses, it refuses here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the grouped product's compiled form
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    real = REAL._replace(layers=2)
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: xing.init_params(real, 1)))
    state = on_chip(jax.eval_shape(lambda: xing.init_state(real, 32)))
    rows = lambda n, dt=jnp.int32: sds((n,), dt)  # noqa: E731
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if program == "step":
            compiled = xing.decode_step.lower(
                real, params, state, sds((163840, 3584), jnp.bfloat16), sds((), jnp.int32), rows(163840),
                rows(32), rows(32), rows(32, jnp.bool_), rows(32),
            ).compile()
        else:
            compiled = xing.prefill.lower(
                real, params, state, sds((4, 100), jnp.int32), rows(4), rows(4), rows(4)
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    for scope in ("gmm", "xing.moe", "xing.attn", "xing.shared", "xing.hc", "xing.dense"):
        assert scope in text, scope
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 1.4e9       # one whole expert layer among them
    assert memory.temp_size_in_bytes < 1.0e9           # and nothing of its size beside it
