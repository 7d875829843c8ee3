"""The Jamba decoder (ops/jamba.py) against its plain reference, its two
kinds of slot state through the batched encoder step (serving/stepper.py) and
the seq app's request path, on the CPU at a small size: 4 layers (Mamba,
attention, Mamba, Mamba), hidden 64, d_inner 128, d_state 16, 4 query heads
on one key-value head, 300 items, seeded weights. The selective scan and the
one-token step's two in-place kernels are Pallas kernels in the interpreter
here; `test_the_scan_compiles_for_a_v5e` also compiles them at the published
widths for a described chip.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import jamba, sdar
from oryx_tpu.ops.seq import catalog_head

CFG = jamba.JambaConfig(
    hidden=64, heads=4, kv_heads=1, intermediate=96, layers=4, vocab=300,
    attn_period=4, attn_offset=1, d_state=16, d_conv=4, dt_rank=8, expand=2,
    basket=4, max_len=24,
)
N_ITEMS = 300
# float32 served form against the float32 reference: accumulation order
# alone. Logits are about 1 at these weights (a token scores itself highest)
F32_ATOL = 5e-6
# bfloat16 served form against the float32 reference on the same bf16
# weights: the activations' rounding, 2^-9 relative at each product
BF16_ATOL = 3e-2


def _weights(seed=7, dtype=jnp.float32):
    """Parameters and the tied catalog: the view's rows ARE E_in's, at
    bfloat16's values (the served view is bfloat16 whatever the weights are)."""
    params = jamba.init_params(CFG, seed, dtype)
    params["E_in"] = params["E_in"].astype(jnp.bfloat16).astype(dtype)
    e = np.zeros((384, CFG.hidden), np.float32)  # capacity rows past the items
    e[:N_ITEMS] = np.asarray(params["E_in"].astype(jnp.float32))
    return params, e


def _sessions(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.choice(N_ITEMS, size=n, replace=False).astype(np.int32) for n in lengths]


def _generate(enc, params, view, sessions, slots_of=None, fill=(), bucket=None, state=None):
    """Prefill + the encoder's steps through the slot cache for `sessions`
    (and `fill`, more sessions sharing the dispatches) -> (the last step's
    out, the state after it)."""
    head = (view, N_ITEMS, None)
    state = enc.init_state(enc.step_rows) if state is None else state
    everyone = list(sessions) + list(fill)
    slots_of = slots_of or list(range(len(everyone)))
    for lo in range(0, len(everyone), enc.prefill_rows):
        group = everyone[lo:lo + enc.prefill_rows]
        b = bucket or min(b for b in enc.length_buckets if b >= max(enc.length(p) for p in group))
        packed = enc.pack(group, b, slots_of[lo:lo + len(group)], enc.step_rows)
        state, _, _ = enc.prefill(params, state, *packed)
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
    return {k: np.asarray(v) for k, v in out.items()}, state


def _sequential(x, dt, b, c, a, d, h0, n):
    """The recurrence one position after another, float64, for one row."""
    h = np.array(h0, np.float64)
    ys = []
    for t in range(n):
        h = np.exp(dt[t][None, :] * a) * h + (dt[t] * x[t])[None, :] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0) + d * x[t])
    return np.asarray(ys).reshape(n, x.shape[1]), h


# ---- the selective scan alone ------------------------------------------------

@pytest.mark.parametrize(
    "t,lengths",
    [
        (32, (32, 16, 1)),    # two whole chunks; exactly one; a single position
        (21, (21, 17, 5)),    # no multiple of the chunk: the walk pads, the length masks
        (100, (100, 33, 0)),  # the long bucket; a row with nothing real
        (1, (1, 0, 1)),       # a one-token step
    ],
    ids=["multiple_of_the_chunk", "not_a_multiple", "long_bucket", "one_token"],
)
def test_the_scan_against_the_sequential_recurrence(t, lengths):
    rng = np.random.default_rng(t)
    rows, ch, n = len(lengths), 256, 16
    x = rng.standard_normal((rows, t, ch)).astype(np.float32)
    dt = rng.uniform(1e-3, 1e-1, (rows, t, ch)).astype(np.float32)
    b = rng.standard_normal((rows, t, n)).astype(np.float32)
    c = rng.standard_normal((rows, t, n)).astype(np.float32)
    a = -np.exp(np.log(np.arange(1, n + 1, dtype=np.float32))[:, None] * np.ones((n, ch), np.float32))
    d = rng.standard_normal(ch).astype(np.float32)
    h0 = rng.standard_normal((rows, n, ch)).astype(np.float32)
    y, h = jamba.selective_scan(*(jnp.asarray(v) for v in (x, dt, b, c, a, d, h0)), jnp.asarray(lengths, jnp.int32))
    assert y.shape == (rows, t, ch) and h.shape == (rows, n, ch)
    for r, length in enumerate(lengths):
        want_y, want_h = _sequential(*(v[r].astype(np.float64) for v in (x, dt, b, c)), a, d, h0[r], length)
        np.testing.assert_allclose(np.asarray(y[r, :length]), want_y, atol=2e-5)
        np.testing.assert_allclose(np.asarray(h[r]), want_h, atol=2e-5)
    # a row with no real position keeps its state to the bit
    for r, length in enumerate(lengths):
        if length == 0:
            np.testing.assert_array_equal(np.asarray(h[r]), h0[r])


def test_a_padded_position_advances_nothing_whatever_it_holds():
    rng = np.random.default_rng(3)
    rows, t, ch, n = 2, 24, 128, 16
    args = [rng.standard_normal(s).astype(np.float32) for s in ((rows, t, ch), (rows, t, ch), (rows, t, n), (rows, t, n))]
    args[1] = np.abs(args[1]) * 0.05
    a = -np.ones((n, ch), np.float32)
    d, h0 = np.ones(ch, np.float32), np.zeros((rows, n, ch), np.float32)
    lengths = jnp.asarray([9, 20], jnp.int32)
    y1, h1 = jamba.selective_scan(*(jnp.asarray(v) for v in args), jnp.asarray(a), jnp.asarray(d), jnp.asarray(h0), lengths)
    noisy = [v.copy() for v in args]
    for v in noisy:
        v[0, 9:] = 1e3 * rng.standard_normal(v[0, 9:].shape)  # garbage past the first row's length
    noisy[1] = np.abs(noisy[1])
    y2, h2 = jamba.selective_scan(*(jnp.asarray(v) for v in noisy), jnp.asarray(a), jnp.asarray(d), jnp.asarray(h0), lengths)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(y1[0, :9]), np.asarray(y2[0, :9]))


# ---- the model: shapes, weights, the shared head -------------------------------

def test_layer_kinds_shapes_and_parameter_count_at_the_published_widths():
    real = jamba.JambaConfig(
        hidden=2560, heads=20, kv_heads=1, intermediate=8192, layers=28, vocab=65536,
        attn_period=14, attn_offset=7,
    )
    assert [l for l in range(28) if real.is_attention(l)] == [7, 21]
    assert real.head_dim == 128 and real.d_inner == 5120 and real.positions == 104
    mamba = sum(int(np.prod(s)) for s in jamba.layer_shapes(real, 0).values())
    attn = sum(int(np.prod(s)) for s in jamba.layer_shapes(real, 7).values())
    # ISSUE 37: 104.16M and 76.68M a layer (the norms' few thousand beside)
    assert mamba == pytest.approx(104.16e6, rel=1e-3) and attn == pytest.approx(76.68e6, rel=1e-3)
    assert jamba.param_count(real) == pytest.approx(3.029e9, rel=1e-3)
    state = jamba.state_bytes(real, 32)
    assert state["recurrent"] == 33 * 26 * (16 + 3) * 5120 * 4  # 10.1 MB a slot whatever its length
    assert state["kv"] == 33 * 2 * 2 * 104 * 128 * 2
    assert jamba.JambaConfig.from_extensions(
        {k: str(v) for k, v in real.to_extensions().items()}.get
    ) == real


def test_the_recurrence_is_initialised_as_published():
    t = jamba.init_tensors(CFG, 5, jnp.bfloat16)
    a_log, d, b_dt = (np.asarray(t[f"L0.{k}"]) for k in ("A_log", "D", "dt_bias"))
    assert a_log.dtype == d.dtype == b_dt.dtype == np.float32  # whatever the weights' dtype
    np.testing.assert_allclose(np.exp(a_log[:, 0]), np.arange(1, 17), rtol=1e-6)
    assert np.all(d == 1.0)
    dt = np.log1p(np.exp(b_dt))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1 and np.median(dt) == pytest.approx(1e-2, rel=0.5)
    assert t["L0.in_proj"].dtype == jnp.bfloat16 and np.all(np.asarray(t["L0.ln1"].astype(jnp.float32)) == 1.0)
    assert "L1.wq" in t and "L1.in_proj" not in t and "L0.wq" not in t
    again = jamba.init_tensors(CFG, 5, jnp.bfloat16)
    assert all(np.array_equal(np.asarray(t[k]), np.asarray(again[k])) for k in t)
    other = jamba.init_tensors(CFG, 6, jnp.bfloat16)
    assert not np.array_equal(np.asarray(t["L0.in_proj"]), np.asarray(other["L0.in_proj"]))


def test_one_head_serves_both_generating_encoders():
    rng = np.random.default_rng(1)
    view = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((5, 128)).astype(np.float32))
    top, arg, conf = catalog_head(z, view, 40)
    logits = np.asarray(z) @ np.asarray(view).T
    logits[:, 40:] = -np.inf
    np.testing.assert_array_equal(np.asarray(arg), logits.argmax(-1))
    np.testing.assert_allclose(np.asarray(top), logits.max(-1), rtol=1e-5)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(conf), (p / p.sum(-1, keepdims=True)).max(-1), rtol=1e-4)
    assert arg.dtype == jnp.int32
    # all four generating programs call it, each under its own scope, through
    # the one padding of the hidden state to the view's width
    import inspect

    from oryx_tpu.ops import decoder, joyai, trinity

    assert "catalog_head(" in inspect.getsource(decoder.view_head)
    assert "view_head(" in inspect.getsource(sdar.denoise_step.__wrapped__)
    for mod in (jamba, joyai, trinity):
        assert "view_head(" in inspect.getsource(mod.decode_step.__wrapped__)


# ---- prefill, then steps, against the full forward pass ---------------------------

@pytest.mark.parametrize("n", [2, 7, 12, 24])
def test_prefill_then_step_is_the_full_pass_at_the_last_position(n):
    """`prefill(L)` then a step equals the full pass over L + 1 tokens at its
    last position, across a Mamba layer (the state and the conv's last
    inputs) and an attention layer (keys and values): sessions shorter than
    the conv's width, at and past it, and the longest a slot holds."""
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    session = _sessions((n,), seed=n)[0]
    state = enc.init_state(enc.step_rows)
    state, hidden, _ = enc.prefill(params, state, *enc.pack([session], 24, [3], enc.step_rows))
    full = np.asarray(jamba.reference_forward(CFG, params, jnp.asarray(session)))
    z, _ = jamba._token_hidden(
        CFG, params, state, jnp.asarray([3]), jnp.asarray([n - 1]), jnp.asarray([True])
    )
    np.testing.assert_allclose(np.asarray(z[0]), full[-1], atol=F32_ATOL)
    # the conv's tail is the last three REAL inputs: zeros in front of a short session
    tail = np.asarray(state["conv"][0][3])
    assert (np.abs(tail).sum(-1) > 0).sum() == min(n - 1, CFG.d_conv - 1)
    assert state["h"][1] is None and state["k"][0] is None  # a layer keeps its own kind alone


@pytest.mark.parametrize(
    "dtype,atol", [(jnp.float32, F32_ATOL), (jnp.bfloat16, BF16_ATOL)], ids=["float32", "bfloat16"]
)
def test_cached_generation_against_the_references_full_forward(dtype, atol):
    params, e = _weights(dtype=dtype)
    enc = jamba.JambaEncoder(CFG, dtype)
    view = jnp.asarray(e, dtype)
    sessions = _sessions((13, 24, 2))
    out, _ = _generate(enc, params, view, sessions)
    for i, session in enumerate(sessions):
        np.testing.assert_array_equal(out["step"][i], np.arange(4))
        # the reference's ONE full pass over [session + the basket the system
        # chose] at the four positions: its logits, and that each item fed
        # back was its argmax
        tokens = np.concatenate([session, out["row"][i][:-1]]).astype(np.int32)
        full = np.asarray(jamba.reference_forward(CFG, params, jnp.asarray(tokens)))[-4:]
        logits = e[:N_ITEMS] @ full.T
        np.testing.assert_allclose(e[:N_ITEMS] @ out["z"][i].T, logits, atol=atol)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(out["row"][i], logits.argmax(0))


@pytest.mark.parametrize("how", ["full_dispatch", "other_bucket", "both"])
def test_an_answer_is_the_same_alone_in_a_full_dispatch_and_in_either_bucket(how):
    params, e = _weights()  # no tensor's shape depends on max_len
    assert jamba.JambaEncoder(CFG, jnp.float32).length_buckets == (24,)  # under 32: one bucket
    enc = jamba.JambaEncoder(CFG._replace(max_len=40), jnp.float32)
    assert enc.length_buckets == (32, 40)
    view = jnp.asarray(e)
    mine = _sessions((13,))
    alone, _ = _generate(enc, params, view, mine)
    fill, slots_of, bucket = (), None, None
    if how in ("full_dispatch", "both"):
        fill = _sessions([3 + (5 * j) % 30 for j in range(enc.step_rows - 1)], seed=5)
        slots_of = [enc.step_rows - 1] + list(range(enc.step_rows - 1))  # and another slot
    if how in ("other_bucket", "both"):
        bucket = 40
    shared, _ = _generate(enc, params, view, mine, slots_of=slots_of, fill=fill, bucket=bucket)
    np.testing.assert_array_equal(alone["row"][0], shared["row"][0])
    np.testing.assert_allclose(e @ alone["z"][0].T, e @ shared["z"][0].T, atol=F32_ATOL)


def test_a_slot_freed_and_taken_again_leaks_nothing():
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    view = jnp.asarray(e)
    first, second = _sessions((24, 4), seed=9)
    fresh, _ = _generate(enc, params, view, [second], slots_of=[5])
    _, used = _generate(enc, params, view, [first], slots_of=[5])
    assert float(jnp.abs(used["h"][0][5]).max()) > 0 and float(jnp.abs(used["k"][1][5]).max()) > 0
    again, after = _generate(enc, params, view, [second], slots_of=[5], state=used)
    np.testing.assert_array_equal(fresh["row"][0], again["row"][0])
    np.testing.assert_array_equal(fresh["z"][0], again["z"][0])
    # a session of ONE event prefills nothing: its slot starts from zero all the same
    one = _sessions((1,), seed=2)
    lone, _ = _generate(enc, params, view, one, slots_of=[5])
    reused, _ = _generate(enc, params, view, one, slots_of=[5], state=after)
    np.testing.assert_array_equal(lone["z"][0], reused["z"][0])
    ref = jamba.reference_generate(CFG, params, e[:N_ITEMS], one[0])
    np.testing.assert_array_equal(lone["row"][0], ref["row"])


def test_padding_rows_touch_only_the_scratch_slot():
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    state = enc.init_state(enc.step_rows)
    state, _, _ = enc.prefill(params, state, *enc.pack(_sessions((9,)), 24, [4], enc.step_rows))
    h = np.asarray(state["h"][0])
    assert np.abs(h[4]).max() > 0
    untouched = [s for s in range(enc.step_rows) if s != 4]
    assert np.abs(h[untouched]).max() == 0 and np.abs(np.asarray(state["k"][1])[untouched]).max() == 0


# ---- the one-token step: the slots' state updated where it lies --------------------

def _step_rows(n_live, seed=0, rows=32):
    """`n_live` live rows first, in shuffled slot order; the rest padding on
    the scratch slot."""
    rng = np.random.default_rng(seed)
    slots = np.full(rows, rows, np.int32)
    slots[:n_live] = rng.permutation(rows)[:n_live]
    live = np.arange(rows) < n_live
    return slots, live


@pytest.mark.parametrize("n_live", [1, 3, 32])
def test_one_step_of_the_update_against_the_mixer_at_one_position(n_live):
    """`_mamba_step` on the whole slot arrays against the prefill's mixer at
    T = 1 on gathered state, and from an empty slot against the reference."""
    params, _ = _weights()
    p = params["layers"][0]
    rng = np.random.default_rng(n_live)
    rows, c = 32, CFG.d_inner
    x = jnp.asarray(rng.standard_normal((rows, CFG.hidden)).astype(np.float32))
    conv = jnp.asarray(rng.standard_normal((rows + 1, CFG.d_conv - 1, c)).astype(np.float32))
    h = jnp.asarray(rng.standard_normal((rows + 1, CFG.d_state, c)).astype(np.float32))
    slots, live = _step_rows(n_live, seed=n_live)
    want, tail, state = jamba._mamba(
        CFG, p, x[:, None, :], conv[slots], h[slots], jnp.asarray(live.astype(np.int32))
    )
    got, new_conv, new_h = jamba._mamba_step(CFG, p, x, conv, h, jnp.asarray(slots), jnp.asarray(live))
    on = np.flatnonzero(live)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_h)[slots[on]], np.asarray(state)[on], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new_conv)[slots[on]], np.asarray(tail)[on])
    others = np.setdiff1d(np.arange(rows + 1), slots[on])
    np.testing.assert_array_equal(np.asarray(new_h)[others], np.asarray(h)[others])
    np.testing.assert_array_equal(np.asarray(new_conv)[others], np.asarray(conv)[others])
    # an empty slot: one position of the reference's recurrence
    zero_conv, zero_h = jnp.zeros_like(conv), jnp.zeros_like(h)
    got, _, _ = jamba._mamba_step(CFG, p, x, zero_conv, zero_h, jnp.asarray(slots), jnp.asarray(live))
    with jax.default_matmul_precision("highest"):
        for i in on[:3]:
            u = sdar.rms_norm(x[i:i + 1], p["ln1"], CFG.eps)
            ref = np.asarray(x[i] + jamba._reference_mamba(CFG, p, u)[0])
            np.testing.assert_allclose(np.asarray(got[i]), ref, atol=F32_ATOL)


def _random_state(enc, seed):
    """A slot cache in which every slot holds something, the scratch slot too."""
    rng = np.random.default_rng(seed)

    def fill(a):
        if a is None:
            return None
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(0, 100, a.shape).astype(np.int32))
        return jnp.asarray(rng.standard_normal(a.shape).astype(np.float32), a.dtype)

    return jax.tree.map(fill, enc.init_state(enc.step_rows), is_leaf=lambda a: a is None)


def _host(state):
    return jax.tree.map(lambda a: None if a is None else np.asarray(a), state, is_leaf=lambda a: a is None)


NAMED = (5, 17, 2)  # the live rows' slots


@pytest.fixture(scope="module")
def around_a_step():
    """(the state before, the state after) ONE step of three live rows over a
    cache whose every slot is in use."""
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    state = _random_state(enc, 11)
    before = _host(state)
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    slots[:3] = NAMED
    lengths = np.zeros(enc.step_rows, np.int32)
    lengths[:3] = (6, 20, 11)
    live = np.arange(enc.step_rows) < 3
    step = np.zeros(enc.step_rows, np.int32)
    step[:3] = (0, 3, 1)
    state, _ = enc.step(params, state, (jnp.asarray(e), N_ITEMS, None), slots, lengths, live, step)
    return before, _host(state)


@pytest.mark.parametrize("key", ["h", "conv", "k", "v", "x_in", "z", "row", "step"])
def test_a_step_leaves_every_slot_it_does_not_name_bit_identical(around_a_step, key):
    """The in-place write is the new way to corrupt a neighbour: every slot
    but the three named (and the scratch slot, the padding rows') is after
    the step what it was before it, to the bit."""
    before, after = around_a_step
    others = [s for s in range(33) if s not in NAMED + (32,)]
    layers = before[key] if isinstance(before[key], list) else [before[key]]
    after_layers = after[key] if isinstance(after[key], list) else [after[key]]
    seen = 0
    for was, now in zip(layers, after_layers):
        if was is None:
            continue
        np.testing.assert_array_equal(now[others], was[others])
        assert not np.array_equal(now[list(NAMED)], was[list(NAMED)])  # and the named ones moved
        seen += 1
    assert seen


@pytest.mark.parametrize("holds", ["zeros", "nan", "huge"])
def test_padding_rows_write_the_scratch_slot_alone_whatever_it_holds(holds):
    """29 padding rows on the scratch slot: the live rows' answers and every
    other slot are the same whatever the scratch slot holds."""
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    head = (jnp.asarray(e), N_ITEMS, None)
    scratch = enc.step_rows
    value = {"zeros": 0.0, "nan": np.nan, "huge": 3e38}[holds]

    def run(poison):
        state = _random_state(enc, 5)
        if poison:
            state = jax.tree.map(
                lambda a: None if a is None else (a if jnp.issubdtype(a.dtype, jnp.integer) else a.at[scratch].set(value)),
                state, is_leaf=lambda a: a is None,
            )
        slots, live = _step_rows(3, seed=4)
        lengths = np.where(live, 9, 0).astype(np.int32)
        state, out = enc.step(params, state, head, slots, lengths, live, np.zeros(enc.step_rows, np.int32))
        return slots, _host(state), {k: np.asarray(v) for k, v in out.items()}

    slots, clean, clean_out = run(False)
    _, dirty, dirty_out = run(True)
    for k in ("z", "row", "step"):
        np.testing.assert_array_equal(dirty_out[k][:3], clean_out[k][:3])
    kept = np.arange(scratch)
    for key in ("h", "conv", "k", "v", "x_in", "z"):
        pairs = zip(clean[key], dirty[key]) if isinstance(clean[key], list) else [(clean[key], dirty[key])]
        for was, now in pairs:
            if was is not None:
                np.testing.assert_array_equal(now[kept], was[kept])


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize(
    "kernel,key,aliased", [("jamba_step_scan", "h", (8, 1)), ("jamba_step_conv", "conv", (7, 1))]
)
def test_the_step_addresses_its_state_by_slot(kernel, key, aliased):
    """The state is addressed by slot, not carried: the step's jaxpr holds no
    gather and no scatter of a layer's state array, and the kernel that
    updates it aliases its state input to its output."""
    params, e = _weights()
    enc = jamba.JambaEncoder(CFG, jnp.float32)
    state = enc.init_state(enc.step_rows)
    rows = lambda dt: jnp.zeros(enc.step_rows, dt)  # noqa: E731
    jaxpr = jax.make_jaxpr(jamba.decode_step, static_argnums=(0,))(
        CFG, params, state, jnp.asarray(e), jnp.int32(N_ITEMS), rows(jnp.int32), rows(jnp.int32), rows(bool), rows(jnp.int32)
    )
    shape = state[key][0].shape
    swapped = (shape[1], shape[0], shape[2])  # the conv's inputs are handed over as they lie on the chip
    moved = [
        eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)
        if eqn.primitive.name.startswith(("gather", "scatter", "dynamic_slice", "dynamic_update_slice"))
        and tuple(eqn.invars[0].aval.shape) in (shape, swapped, (enc.step_rows,) + shape[1:])
    ]
    assert not moved
    calls = [
        eqn for eqn in _equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == kernel
    ]
    mamba_layers = sum(1 for l in range(CFG.layers) if not CFG.is_attention(l))
    assert len(calls) == mamba_layers
    for eqn in calls:
        assert tuple(eqn.params["input_output_aliases"]) == (aliased,)
        assert tuple(eqn.invars[aliased[0]].aval.shape) in (shape, swapped)


# ---- through the seam, the stepper and the app ------------------------------------

def _jamba_message(seed=7):
    from oryx_tpu.common.artifact import ModelArtifact

    tensors = {k: np.asarray(v) for k, v in jamba.init_tensors(CFG, seed, jnp.float32).items()}
    tensors["E_in"] = np.asarray(_weights(seed)[0]["E_in"])
    tensors["E"] = tensors["E_in"][:N_ITEMS]  # the tied embedding IS the catalog
    art = ModelArtifact("seq", tensors=tensors)
    for k, v in CFG.to_extensions().items():
        art.set_extension(k, v)
    art.set_extension("encoder", "jamba")
    art.set_extension("dtype", "float32")
    art.set_extension("ItemIDs", [f"i{j}" for j in range(N_ITEMS)])
    return art.to_string()


def test_jamba_artifact_answers_recommend_next_end_to_end():
    """MODEL message -> apply_seq_update -> ServingLayer -> GET
    /recommend-next: through the seam, the batched encoder step and
    TopKBatcher, against the plain reference's generation."""
    from oryx_tpu.apps.seq.serving import SeqServingModelManager
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://jamba-e2e"
    cfg = load_config(overlay={
        "oryx.id": "jamba-e2e",
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
    manager.consume_key_message("MODEL", _jamba_message())
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    try:
        base = f"http://127.0.0.1:{serving.port}"
        reg = get_registry()
        blocks0 = reg.counter("oryx_seq_blocks_total").value()
        session = [3, 141, 59, 26, 5, 258, 97]
        path = "/".join(f"i{j}" for j in session)

        def get(p):
            req = urllib.request.Request(f"{base}{p}", headers={"Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read())

        answer = get(f"/recommend-next/{path}?howMany=10")
        params, e = _weights()
        ref = jamba.reference_generate(CFG, params, e[:N_ITEMS], np.asarray(session, np.int32))
        assert len(answer) == CFG.basket
        for b, entry in enumerate(answer):
            assert entry["item"] == f"i{ref['row'][b]}" and entry["step"] == b
            logits = ref["logits"][b].copy()
            logits[session] = -np.inf
            want = np.argsort(-logits, kind="stable")[:10]
            assert [i for i, _ in entry["next"]] == [f"i{r}" for r in want]
            np.testing.assert_allclose([s for _, s in entry["next"]], logits[want], atol=F32_ATOL)
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
            'oryx_seq_slot_state_bytes{state="recurrent"}', 'oryx_seq_slot_state_bytes{state="kv"}',
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


def _compile_uncached(lowered):
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("rows,t", [(8, 32), (8, 100), (32, 1)], ids=["prefill_32", "prefill_100", "step"])
def test_the_scan_compiles_for_a_v5e(one_chip, rows, t, monkeypatch):
    """The kernels at the published widths (5,120 channels, 16 states) through
    the chip's own compiler: what Mosaic refuses, it refuses here. A prefill's
    is the scan over its positions; a step's are the two that update 32 rows'
    slots of 33 in place, compiled inside a step of one Mamba and one attention
    layer, whose text also shows that no slot array is copied, gathered or
    scattered around them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel's compiled form
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    ch, n = 5120, 16
    if t > 1:
        compiled = _compile_uncached(jax.jit(jamba.selective_scan).lower(
            sds((rows, t, ch)), sds((rows, t, ch)), sds((rows, t, n)), sds((rows, t, n)),
            sds((n, ch)), sds((ch,)), sds((rows, n, ch)), sds((rows,), jnp.int32),
        ))
        assert "jamba_scan" in compiled.as_text()
        return
    real = jamba.JambaConfig(
        hidden=2560, heads=20, kv_heads=1, intermediate=8192, layers=2, vocab=65536,
        attn_period=2, attn_offset=1,
    )
    assert real.d_inner == ch and real.d_state == n
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: jamba.init_params(real, 1)))
    state = on_chip(jax.eval_shape(lambda: jamba.init_state(real, rows)))
    assert state["h"][0].shape == (rows + 1, n, ch)
    text = _compile_uncached(jamba.decode_step.lower(
        real, params, state, sds((81920, 2560), jnp.bfloat16), sds((), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32), sds((rows,), jnp.bool_), sds((rows,), jnp.int32),
    )).as_text()
    assert "jamba_step_scan" in text and "jamba_step_conv" in text and "jamba_scan" not in text.replace("jamba_step_scan", "")
    # the slots' state is updated where it lies: no instruction but the two
    # kernels (and the program's own parameters and results) holds a whole
    # slot array, in either layout the chip keeps it in
    whole = ("f32[33,16,5120]", "f32[32,16,5120]", "f32[33,3,5120]", "f32[32,3,5120]", "f32[3,33,5120]")
    moving = [
        line.strip()[:200] for line in text.splitlines()
        if any(w in line.split("metadata=")[0] for w in whole)
        and any(f" {op}(" in line for op in ("copy", "copy-start", "gather", "scatter", "dynamic-slice", "dynamic-update-slice", "fusion", "slice-start"))
    ]
    assert not moving, moving
