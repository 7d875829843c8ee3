"""The hyper-connections' boundary kernel (ops/pallas_hc.py) against the
unfused composition it replaces (ops/xing.py `_write`, then `_maps`, h and
the Sinkhorn's tally), interpreted on the CPU at a small size: a prefill's
ragged rows (one of length 0, one ending inside a block, the positions not a
whole number of blocks) and a step's tokens with dead rows between live ones,
in each of the kernel's three forms (a program's first boundary opens alone,
the others close and open, the last closes and sums the streams). A block
with no live token is never touched: its streams keep what they held and its
h what the buffer it is written over held. The boundary's host count, its
scope on the chip's compiled programs and the wrappers' counts for the
stepper are here too; ops/xing.py's own tests hold the served answers to the
reference with the kernel in place."""

from __future__ import annotations

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import pallas_hc, xing

CFG = xing.XingConfig(
    hidden=128, heads=2, q_rank=32, kv_rank=32, nope=16, rope=8, v_dim=16, intermediate=96,
    experts=8, expert_width=32, experts_per_token=2, shared_experts=1, first_dense=1,
    layers=2, vocab=64, basket=4, max_len=20,
)
FNS = (xing._maps, xing._write, xing.sinkhorn_error, xing.UNCONVERGED)
# the kernel's products and sums against XLA's, both float32 and at highest precision:
# the order of accumulation alone
ATOL = 2e-5

# (rows, positions, the live tokens): a prefill's ragged rows, a step's tokens
CASES = {
    "prefill": (4, 20, lambda: np.arange(20)[None, :] < np.asarray([13, 0, 20, 3])[:, None]),
    "step": (1, 32, lambda: np.isin(np.arange(32), [0, 2, 3, 5, 17])[None, :]),
}


def _operands(rows, positions, seed=0):
    """Streams, a sublayer's output and the maps it closes with, and a layer
    whose phi spreads `a` as the published widths spread it."""
    n, hidden = CFG.hc_mult, CFG.hidden
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (n, rows, positions, hidden))
    y = jax.random.normal(keys[1], (rows, positions, hidden))
    p = xing.init_params(CFG, seed + 1, jnp.float32)["layers"][1]
    p = {k: v * math.sqrt(3584 / hidden) if k.endswith("_phi") else v for k, v in p.items()}
    _, post, m = xing._maps(CFG, p, "attn", jax.random.normal(keys[2], x.shape))
    return x, y, p, (post, m)


def _walked_tokens(live):
    """[R, T] booleans: the tokens of the blocks the kernel walks."""
    walked, skipped = pallas_hc.hc_tokens(live)
    r, t = live.shape
    nb = -(-t // pallas_hc.HC_BLOCK)
    padded = np.zeros((r, nb * pallas_hc.HC_BLOCK), bool)
    padded[:, :t] = live
    blocks = padded.reshape(r, nb, -1).any(-1)
    if not blocks.any():
        blocks[0, 0] = True
    tokens = np.repeat(blocks, pallas_hc.HC_BLOCK, axis=1)[:, :t]
    assert (walked, skipped) == (int(tokens.sum()), int((~tokens).sum()))
    return tokens


def _blocks(maps):
    """Maps [n, (n,) R, T] laid out as a boundary leaves them, a block at a
    time: [R x blocks, n, (n,) block]."""
    out = []
    for a in maps:
        a = np.asarray(a)
        *lead, r, t = a.shape
        nb = -(-t // pallas_hc.HC_BLOCK)
        a = np.pad(a, [(0, 0)] * (len(lead) + 1) + [(0, nb * pallas_hc.HC_BLOCK - t)])
        a = np.moveaxis(a.reshape(*lead, r * nb, pallas_hc.HC_BLOCK), -2, 0)
        out.append(jnp.asarray(a))
    return tuple(out)


def _blocked(maps, rows, positions):
    """The kernel's maps, laid out a block at a time, as [n, (n,) R, T]."""
    out = []
    for a in maps:
        a = np.asarray(a)                                               # [G, ..., block]
        g, block = a.shape[0], a.shape[-1]
        lead = a.shape[1:-1]
        a = np.moveaxis(a, 0, -2).reshape(*lead, rows, g // rows * block)
        out.append(a[..., :positions])
    return out


@pytest.mark.parametrize("form", ["open", "close_open", "close"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_boundary_is_the_unfused_composition_and_leaves_skipped_blocks_as_they_were(case, form):
    rows, positions, live_of = CASES[case]
    live = live_of()
    x, y, p, maps = _operands(rows, positions)
    walked = _walked_tokens(live)
    assert 0 < walked.sum() < walked.size
    hc = pallas_hc.plan(jnp.asarray(live))
    closing = form != "open"
    over = jax.random.normal(jax.random.PRNGKey(9), y.shape)
    got = pallas_hc.boundary(
        CFG, hc, x, y if closing else None, _blocks(maps) if closing else None, None if form == "close" else p,
        None if form == "close" else "ffn", h_over=None if closing else over, fns=FNS, interpret=True,
    )
    # the unfused composition, every token
    streams = xing._write(x, maps, y) if closing else x
    w = walked[None, :, :, None]
    if form == "close":
        np.testing.assert_allclose(np.where(w[0], got, 0), np.where(w[0], np.asarray(streams).sum(0), 0), atol=ATOL)
        np.testing.assert_array_equal(np.where(w[0], 0, got), np.where(w[0], 0, y))   # y where nothing walked
        return
    new_x, h, (post, m), (err, unconverged) = got
    pre, want_post, want_m = xing._maps(CFG, p, "ffn", streams)
    want_h = jnp.sum(pre[..., None] * streams, axis=0)
    np.testing.assert_allclose(np.where(w, new_x, 0), np.where(w, streams, 0), atol=ATOL)
    np.testing.assert_allclose(np.where(w[0], h, 0), np.where(w[0], want_h, 0), atol=ATOL)
    got_post, got_m = _blocked((post, m), rows, positions)
    np.testing.assert_allclose(got_post[:, walked], np.asarray(want_post)[:, walked], atol=ATOL)
    np.testing.assert_allclose(got_m[:, :, walked], np.asarray(want_m)[:, :, walked], atol=ATOL)
    error = np.asarray(xing.sinkhorn_error(want_m))
    assert float(err) == pytest.approx(float(error[live].max()), abs=1e-6)
    assert int(unconverged) == int((error[live] > xing.UNCONVERGED).sum())
    # a block nobody walked: its streams as they were, its h what h was written over
    np.testing.assert_array_equal(np.where(w, 0, new_x), np.where(w, 0, x))
    np.testing.assert_array_equal(np.where(w[0], 0, h), np.where(w[0], 0, y if closing else over))
    assert np.isfinite(np.asarray(new_x)).all() and np.isfinite(np.asarray(h)).all()


@pytest.mark.parametrize(
    "lengths,walked",
    [([13, 0, 20, 3], 16 + 20 + 8), ([0, 0, 0, 0], 8), ([20, 20, 20, 20], 80), ([1, 9, 16, 17], 8 + 16 + 16 + 20)],
    ids=["ragged", "empty", "full", "edges"],
)
def test_the_host_count_is_the_plans_walk(lengths, walked):
    """`hc_tokens` counts, on the host, the slots of the blocks `plan` has
    the kernel walk on the device: a block a row's live tokens reach, its
    slots inside the row (the last block of 20 positions holds 4); with no
    live token the first block walks."""
    live = np.arange(20)[None, :] < np.asarray(lengths)[:, None]
    assert pallas_hc.hc_tokens(live) == (walked, 80 - walked)
    count, order, tokens = (np.asarray(a) for a in pallas_hc.plan(jnp.asarray(live)))
    blocks = np.asarray([b for b in range(12) if tokens[b].any()] or [0])
    sizes = np.tile([8, 8, 4], 4)
    assert int(count) == len(blocks) and int(sizes[blocks].sum()) == walked
    np.testing.assert_array_equal(order[:count], blocks)  # the grid walks those blocks, in order
    assert tokens.shape == (12, 1, 8) and tokens.sum() == live.sum()


def test_the_wrappers_hand_the_stepper_each_dispatchs_boundary_counts():
    """A prefill's and a step's `hc_tokens`: the walked and skipped slots of
    one boundary times the sublayers, 2 x layers; what
    `oryx_seq_hc_tokens_total` publishes."""
    enc = xing.XingEncoder(CFG, jnp.float32)
    params = xing.init_params(CFG, 3, jnp.float32)
    state = enc.init_state(enc.step_rows)
    rng = np.random.default_rng(0)
    sessions = [rng.choice(CFG.vocab, size=k, replace=False).astype(np.int32) for k in (14, 3)]
    packed = enc.pack(sessions, 20, [0, 1], enc.step_rows)
    state, _, tallies = enc.prefill(params, state, *packed)
    sublayers = 2 * CFG.layers
    # rows of 13 and 2 positions: two blocks and one; two empty rows
    assert tallies["hc_tokens"] == (24 * sublayers, (80 - 24) * sublayers)
    view = jnp.asarray(rng.standard_normal((64, CFG.hidden)).astype(np.float32))
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    slots[:2] = (0, 1)
    lengths = np.zeros(enc.step_rows, np.int32)
    lengths[:2] = (13, 2)
    live = np.arange(enc.step_rows) < 2
    _, out = enc.step(params, state, (view, 64, jnp.arange(64, dtype=jnp.int32)), slots, lengths, live,
                      np.zeros(enc.step_rows, np.int32))
    assert out["hc_tokens"] == (8 * sublayers, 24 * sublayers)
    assert np.isfinite(np.asarray(out["z"])).all()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["prefill", "step"])
def test_every_boundary_is_one_kernel_under_the_hc_scope_on_the_chip(one_chip, program, monkeypatch):
    """Both programs compiled for a described v5e: 2 x layers + 1 boundary
    kernels, each a custom call whose op_name carries `xing.hc` (what the
    benchmark's `xing_hc_*` readers attribute), and no other instruction
    under the scope but the dispatch's one plan."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: xing.init_params(CFG, 1)))
    state = on_chip(jax.eval_shape(lambda: xing.init_state(CFG, 32)))
    rows = lambda n, dt=jnp.int32: sds((n,), dt)  # noqa: E731
    if program == "step":
        lowered = xing.decode_step.lower(
            CFG, params, state, sds((128, CFG.hidden), jnp.bfloat16), sds((), jnp.int32), rows(128),
            rows(32), rows(32), rows(32, jnp.bool_), rows(32),
        )
    else:
        lowered = xing.prefill.lower(CFG, params, state, sds((4, 20), jnp.int32), rows(4), rows(4), rows(4))
    text = lowered.compile().as_text()
    kernels = [ln for ln in text.splitlines() if re.search(r"=.*custom-call\(.*\"xing_hc\"|%xing_hc[.\d]* = ", ln)]
    assert len(kernels) == 2 * CFG.layers + 1
    assert all(re.search(r'op_name="jit\((prefill|decode_step)\)/xing\.hc/xing_hc', ln) for ln in kernels)
