"""The catalog head as one Pallas TPU kernel: the largest logit of each
query row over the served view's valid rows, its row, and the softmax sum
behind its probability, reduced block by block as the view
streams, so no logit is ever written to HBM and no block past the valid
rows is read.

ops/seq.py catalog_head is the entry point and says what it computes;
this module is how. The grid walks the view in blocks of HEAD_BLOCK_ROWS
rows (the whole view where it is shorter; a last block the view ends
inside is partial, its rows past the view past n_valid too). With
live = ceil(n_valid / block) (n_valid a scalar-prefetch operand, traced:
one program serves every catalog size a view holds), the view's index map
stays on the last live block behind it, so the pipeline, which copies a
block only when its index changes, starts no DMA past it and waits on
none; `pl.when(i < live)` runs no dot there. Inside a live block the rows
at or past n_valid score -inf before anything reads them.

The running values are per lane ([R, 128] scratch): a block's 128-row
chunks are reduced elementwise into each lane's maximum, the first chunk
that reaches it and sum(exp(logit - max)); a block replaces a
lane's maximum only where it is strictly greater, so on ties the earlier
row keeps it. The one cross-lane reduction runs at the last grid step: the
largest of the lanes, the smallest row among the lanes that reach it (the
first such row, as jnp.argmax), and the lanes' sums rescaled to it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128

# Rows of the view one grid step streams (the probe in ops/seq.py
# catalog_head's docstring chose it).
HEAD_BLOCK_ROWS = 1024


def head_rows(rows: int, n_valid: int) -> tuple[int, int]:
    """(rows walked, rows skipped) of one head pass over a view of `rows`
    rows whose first `n_valid` hold items: the live blocks' rows (the last
    one cut at the view's end) and the rest."""
    block = min(HEAD_BLOCK_ROWS, rows)
    walked = min(rows, -(-max(0, n_valid) // block) * block)
    return walked, rows - walked


def _head_kernel(nv_ref, z_ref, v_ref, top_ref, arg_ref, conf_ref, m_sc, a_sc, s_sc, *, block):
    i = pl.program_id(0)
    n_valid = nv_ref[0]
    live = (n_valid + block - 1) // block
    f32 = jnp.float32

    @pl.when(i == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, -jnp.inf, f32)
        a_sc[:] = jnp.zeros(a_sc.shape, jnp.int32)
        s_sc[:] = jnp.zeros(s_sc.shape, f32)

    @pl.when(i < live)
    def _walk():
        logits = jax.lax.dot_general(
            z_ref[:], v_ref[:], (((1,), (1,)), ((), ())), preferred_element_type=f32
        )
        lane = jax.lax.broadcasted_iota(jnp.int32, m_sc.shape, 1)
        base = i * block
        chunks = []
        for j in range(-(-block // _LANE)):
            c = logits[:, j * _LANE:(j + 1) * _LANE]
            if c.shape[1] < _LANE:  # a view's rows end inside this lane tile
                c = jnp.concatenate(
                    [c, jnp.full((c.shape[0], _LANE - c.shape[1]), -jnp.inf, f32)], axis=1
                )
            # a row at or past n_valid is never read as a logit
            chunks.append(jnp.where(base + j * _LANE + lane < n_valid, c, -jnp.inf))
        best = chunks[0]
        for c in chunks[1:]:
            best = jnp.maximum(best, c)
        first = jnp.full(best.shape, len(chunks), jnp.int32)
        for j in reversed(range(len(chunks))):
            first = jnp.where(chunks[j] == best, j, first)
        m_old = m_sc[:]
        m_new = jnp.maximum(m_old, best)
        # a lane with no valid row yet keeps a sum of 0 (exp(-inf) = 0)
        ref = jnp.where(m_new > -jnp.inf, m_new, 0.0)
        s = s_sc[:] * jnp.exp(m_old - ref)
        for c in chunks:
            s = s + jnp.exp(c - ref)
        s_sc[:] = s
        a_sc[:] = jnp.where(best > m_old, base + first * _LANE + lane, a_sc[:])
        m_sc[:] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        m = m_sc[:]
        top = jnp.max(m, axis=1, keepdims=True)
        big = jnp.iinfo(jnp.int32).max
        arg = jnp.min(jnp.where(m == top, a_sc[:], big), axis=1, keepdims=True)
        top_ref[:] = jnp.broadcast_to(top, top_ref.shape)
        arg_ref[:] = jnp.broadcast_to(arg, arg_ref.shape)
        ref = jnp.where(top > -jnp.inf, top, 0.0)
        total = jnp.sum(s_sc[:] * jnp.exp(m - ref), axis=1, keepdims=True)
        # exp(top - logsumexp) = exp(top - top - log(total)) = 1 / total
        conf_ref[:] = jnp.broadcast_to(1.0 / total, conf_ref.shape)


@partial(jax.jit, static_argnames=("interpret",))
def head_pallas(z, view, n_valid, *, interpret):
    """z [R, F] x view [rows, F] over the first `n_valid` rows -> (top f32
    [R], arg int32 [R], conf f32 [R]). A view that is not a whole
    number of blocks ends in a partial one, whose rows past the view are
    past n_valid too."""
    r, feat = z.shape
    rows = view.shape[0]
    block = min(HEAD_BLOCK_ROWS, rows)
    f32 = jnp.float32
    itemsize = jnp.dtype(view.dtype).itemsize
    # two view blocks in flight, the query twice, the block's logits, the
    # running values and the outputs, and room for the compiler's own
    vmem = (2 * block * feat * itemsize + 2 * r * feat * jnp.dtype(z.dtype).itemsize
            + 3 * r * max(block, _LANE) * 4 + 12 * r * _LANE * 4)
    out = pl.BlockSpec((r, _LANE), lambda i, nv: (0, 0))
    kinds = [f32, jnp.int32, f32]
    top, arg, conf = pl.pallas_call(
        partial(_head_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block),),
            in_specs=[
                pl.BlockSpec((r, feat), lambda i, nv: (0, 0)),
                # behind the last live block the index stays on it: no copy
                pl.BlockSpec(
                    (block, feat),
                    lambda i, nv: (jnp.minimum(i, jnp.maximum(nv[0] - 1, 0) // block), 0),
                ),
            ],
            out_specs=[out] * 3,
            # each lane's running maximum, its first row and its sum
            scratch_shapes=[pltpu.VMEM((r, _LANE), t) for t in kinds],
        ),
        out_shape=[jax.ShapeDtypeStruct((r, _LANE), t) for t in kinds],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20),
        ),
        interpret=interpret,
        name="catalog_head",
    )(jnp.minimum(jnp.reshape(n_valid, (1,)), rows).astype(jnp.int32), z, view)
    return top[:, 0], arg[:, 0], conf[:, 0]
