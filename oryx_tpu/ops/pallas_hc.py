"""The hyper-connections' sublayer boundary as one Pallas TPU kernel: each
token's streams read once and written once, and only the blocks that hold a
real token walked.

ops/xing.py says what a boundary computes; this module is how. Between two
sublayers the kernel closes the one behind (the write: X_i <- sum_j M_ij X_j
+ Hpost_i y) and, on the fresh streams while they are in VMEM, opens the one
ahead (the read: the streams' RMS, `a` = v phi at highest precision, the
maps, the Sinkhorn, h = sum_i Hpre_i X_i, and the tallies over the live
tokens). A program's first boundary only reads, its last only writes and
returns the streams' sum. The model's own functions do the arithmetic: the
caller hands `maps`, `write` and `error` (ops/xing.py `_maps`, `_write`,
`sinkhorn_error`) as it finds them when its program is traced, so whatever
replaces them there runs inside the kernel, and the error past which a
matrix counts unconverged.

The streams are [n, R, T, H] float32 (a prefill's rows and positions; a
step's tokens are one row), h and y [R, T, H]; a block is `block` positions
of a row. The grid is one step a block that holds a live token, its length
and the blocks traced (`plan`, once a dispatch; scalar prefetch), so one
program serves every pattern of live tokens and a block that holds none is
never visited, copied in or out. Its streams keep what they held (they are
updated in place, `input_output_aliases`), and its h keeps what the buffer
it is written over held (y, the sublayer's output there, or the embeddings
at the first boundary): finite, as a padded position has to be for the
attention's p @ v. A block that straddles the end of a row is partial; its
positions past the row are never stored.

Between boundaries a block's maps (Hpost [n, block], M [n, n, block]) wait in
HBM laid out as the maps come, tokens on the lanes. phi is read as [w, n H]:
its transpose, which is how the chip keeps a [n H, w] float32 array
(minor-to-major {0, 1}), so the transpose costs no copy and the product is
lane-dense.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions of a row one grid step walks: a prefill's rows end where its
# sessions end, a step's live rows come first
HC_BLOCK = 8


def hc_tokens(live, block: int = HC_BLOCK) -> tuple[int, int]:
    """(token slots walked, skipped) of ONE boundary over a dispatch whose
    live tokens are `live` [R, T] (host booleans): the walked blocks' slots
    inside the rows, and the rest."""
    live = np.asarray(live, dtype=bool).reshape(-1, np.shape(live)[-1])
    r, t = live.shape
    nb = -(-t // block)
    padded = np.zeros((r, nb * block), dtype=bool)
    padded[:, :t] = live
    walked = padded.reshape(r, nb, block).any(-1)
    if not walked.any():
        walked[0, 0] = True
    sizes = np.minimum(block, t - np.arange(nb) * block)
    n = int((walked * sizes[None, :]).sum())
    return n, r * t - n


def plan(live, block: int = HC_BLOCK) -> tuple:
    """live [R, T] bool -> what every boundary of the dispatch shares: the
    count of blocks that walk (of G = R x ceil(T / block), row by row; with
    no live token the first block walks) int32[], the blocks that walk first
    and in order int32 [G], and the live tokens a block int32 [G, 1, block]."""
    r, t = live.shape
    nb = pl.cdiv(t, block)
    tokens = jnp.pad(live, ((0, 0), (0, nb * block - t))).reshape(r * nb, block)
    walked = jnp.any(tokens, axis=1)
    walked = walked | ((jnp.arange(r * nb) == 0) & ~jnp.any(walked))
    count = jnp.sum(walked, dtype=jnp.int32)
    order = jnp.argsort(~walked, stable=True).astype(jnp.int32)
    return count, order, tokens.astype(jnp.int32)[:, None, :]


def _boundary_kernel(order_ref, *refs, fns, cfg, sub, write, read, names):
    maps_fn, write_fn, error_fn, limit = fns
    refs = list(refs)
    take = lambda k: [refs.pop(0) for _ in range(k)]  # noqa: E731
    live_ref, x_ref = take(2)
    y_ref, post_ref, m_ref = take(3) if write else (None, None, None)
    phi_ref, alpha_ref, bias_ref = take(3) if read else (None, None, None)
    if read and not write:
        take(1)  # the buffer h is written over: aliased, never read
    if read:
        x_out, h_out, post_out, m_out, err_out, unconverged_out = refs if write else [None, *refs]
    else:
        (s_out,) = refs
    x = x_ref[...]                                                       # [n, block, H]
    if write:
        x = write_fn(x, (post_ref[...], m_ref[...]), y_ref[...])
        if not read:
            s_out[...] = jnp.sum(x, axis=0)
            return
        x_out[...] = x

    @pl.when(pl.program_id(0) == 0)
    def _init():
        err_out[0] = jnp.float32(0.0)
        unconverged_out[0] = jnp.int32(0)

    p = {names[0]: phi_ref[...], names[1]: alpha_ref[...], names[2]: bias_ref[...]}
    pre, post, m = maps_fn(cfg, p, sub, x)
    h_out[...] = jnp.sum(pre[..., None] * x, axis=0)
    post_out[...] = post
    m_out[...] = m
    live = live_ref[...] > 0                                             # [1, block]
    err = error_fn(m)[None]
    err_out[0] = jnp.maximum(err_out[0], jnp.max(jnp.where(live, err, 0.0)))
    unconverged_out[0] += jnp.sum((live & (err > limit)).astype(jnp.int32))


def boundary(cfg, hc, x, y=None, maps=None, p=None, sub=None, *, fns, h_over=None, interpret: bool):
    """One boundary of a dispatch planned by `plan` (`hc`): closes the
    sublayer behind where `maps` (its (Hpost, M) as the last boundary left
    them) and its output `y` [R, T, H] are given, opens `sub` of layer `p`
    where `p` is given.

    -> opening: (x, h [R, T, H], maps, (largest Sinkhorn error, matrices
    unconverged) over the live tokens); h is written over `y`, or over
    `h_over` [R, T, H] where nothing closes. Closing alone: the streams' sum
    [R, T, H], written over `y`."""
    count, order, live = hc
    n, r, t, hidden = x.shape
    block = live.shape[-1]
    nb = pl.cdiv(t, block)
    write, read = maps is not None, p is not None
    f32 = jnp.float32

    def grouped(*zeros):
        return lambda k, order_: (order_[k], *zeros)

    stream = pl.BlockSpec((n, None, block, hidden), lambda k, o: (0, o[k] // nb, o[k] % nb, 0))
    row = pl.BlockSpec((None, block, hidden), lambda k, o: (o[k] // nb, o[k] % nb, 0))
    post_spec = pl.BlockSpec((None, n, block), grouped(0, 0))
    m_spec = pl.BlockSpec((None, n, n, block), grouped(0, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda k, o: (0,) * len(shape))  # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    operands = [live, x]
    in_specs = [pl.BlockSpec((None, 1, block), grouped(0, 0)), stream]
    if write:
        operands += [y.astype(f32), *maps]
        in_specs += [row, post_spec, m_spec]
    # the streams' blocks in and out twice over, y and h, and the kernel's
    # own values (v, the mixed streams) beside them; phi twice
    tile = block * hidden * 4
    vmem = 2 * (2 * n + 2) * tile + 4 * n * tile
    names = ()
    if read:
        names = tuple(f"hc_{sub}_{k}" for k in ("phi", "alpha", "bias"))
        phi_t = p[names[0]].astype(f32).T                                 # [w, n H]: the chip's own layout
        operands += [phi_t, p[names[1]].astype(f32), p[names[2]].astype(f32)]
        in_specs += [whole(phi_t.shape), whole((3,)), whole((cfg.maps_width,))]
        vmem += 2 * phi_t.size * 4
    sums = jax.ShapeDtypeStruct((r, t, hidden), f32)
    g = r * nb
    if read:
        out_shape = [sums, jax.ShapeDtypeStruct((g, n, block), f32), jax.ShapeDtypeStruct((g, n, n, block), f32),
                     jax.ShapeDtypeStruct((1,), f32), jax.ShapeDtypeStruct((1,), jnp.int32)]
        out_specs = [row, post_spec, m_spec, smem, smem]
        if write:
            out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, f32), *out_shape], [stream, *out_specs]
            # the streams in place, h over y
            aliases = {2: 0, 3: 1}
        else:
            operands.append(h_over.astype(f32))
            in_specs.append(row)
            aliases = {len(operands): 0}
    else:
        out_shape, out_specs = [sums], [row]
        aliases = {3: 0}
    out = pl.pallas_call(
        partial(_boundary_kernel, fns=fns, cfg=cfg, sub=sub, write=write, read=read, names=names),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(count,), in_specs=in_specs, out_specs=out_specs,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20),
        ),
        interpret=interpret,
        name="xing_hc",
    )(order, *operands)
    if not read:
        return out[0]
    if write:
        x, h, post, m, err, unconverged = out
    else:
        h, post, m, err, unconverged = out
    return x, h, (post, m), (err[0], unconverged[0])
