"""Sequence-parallel attention over the device mesh: ring and all-to-all.

The reference has no sequence models (SURVEY.md §5 "long-context ...
absent"), but long-context support is a first-class capability of this
framework. Two schedules, both sharding the sequence over the mesh "data"
axis:

- ``ring_attention``: each device keeps its Q shard resident and streams
  K/V shards around the ring with lax.ppermute (neighbor exchanges over
  ICI, never a full all-gather), folding blocks in with online-softmax
  (flash-attention) rescaling — the full [S, S] score matrix never
  exists and K/V memory per chip stays S/n. Best when S is the scarce
  resource and head count is small.
- ``ulysses_attention`` (DeepSpeed-Ulysses style): one all-to-all swaps
  the sharded axis from sequence to heads (each device then holds H/n
  full-sequence heads), attention runs locally and exactly, and a second
  all-to-all swaps back. Two collectives total instead of n ring steps —
  cheaper when H >= n and per-head attention fits on a chip.

Single-device ``attention`` is the exact reference implementation both
are tested against; all support causal masking (the ring variant masks by
global chunk position via where-masking so every device still executes
the same program).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oryx_tpu.parallel.mesh import DATA_AXIS

_NEG_INF = -1e30


def attention(q, k, v, *, causal: bool = False):
    """Exact softmax attention. q,k,v: [..., S, D] -> [..., S, D]."""
    d = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32)).astype(q.dtype)


def _block_fold(q, k, v, m_prev, l_prev, o_prev, bias):
    """Fold one K/V block into the running online-softmax state.
    q: [Sq, D], k/v: [Sk, D]; m/l: [Sq], o: [Sq, D]; bias: [Sq, Sk]."""
    d = q.shape[-1]
    s = (q @ k.T).astype(jnp.float32) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = s + bias
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    o_new = o_prev * scale[:, None] + p @ v.astype(jnp.float32)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, causal: bool, axis_name: str, n_shards: int):
    """Per-device body under shard_map. q,k,v: local [Sq, D] shards."""
    my = jax.lax.axis_index(axis_name)
    sq = q.shape[0]

    def step(i, carry):
        m, l, o, k_cur, v_cur = carry
        src = (my - i) % n_shards  # which global chunk this K/V block is
        if causal:
            # global causal mask between my Q chunk and the src K chunk:
            # src > my -> fully masked; src == my -> triangular; else open
            tri = jnp.tril(jnp.ones((sq, k_cur.shape[0]), dtype=bool))
            open_ = jnp.ones((sq, k_cur.shape[0]), dtype=bool)
            mask = jnp.where(src == my, tri, jnp.where(src < my, open_, ~open_))
            bias = jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)
        else:
            bias = jnp.zeros((sq, k_cur.shape[0]), dtype=jnp.float32)
        m, l, o = _block_fold(q, k_cur, v_cur, m, l, o, bias)
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m, l, o, k_nxt, v_nxt

    m0 = jnp.full((sq,), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((sq,), dtype=jnp.float32)
    o0 = jnp.zeros((sq, q.shape[1]), dtype=jnp.float32)
    m, l, o, _, _ = jax.lax.fori_loop(0, n_shards, step, (m0, l0, o0, k, v))
    return (o / jnp.maximum(l, 1e-30)[:, None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False):
    """Sequence-parallel attention: [..., S, D] arrays with S sharded over
    the mesh data axis. Leading dims (batch, heads) are vmapped on every
    device. Returns [..., S, D] with the same sharding as q."""
    n_shards = mesh.shape[DATA_AXIS]
    if q.shape[-2] % n_shards or k.shape[-2] % n_shards:
        raise ValueError(
            f"sequence length {q.shape[-2]} must be divisible by the {n_shards}-way "
            f"'{DATA_AXIS}' axis"
        )
    spec = P(*([None] * (q.ndim - 2)), DATA_AXIS, None)
    body = partial(
        _ring_attention_local, causal=causal, axis_name=DATA_AXIS, n_shards=n_shards
    )
    for _ in range(q.ndim - 2):
        body = jax.vmap(body)
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, spec)
    return fn(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )


def _ulysses_local(q, k, v, *, causal: bool, axis_name: str):
    """Per-device body under shard_map. q,k,v: [..., H, Sq_local, D]."""
    h_ax, s_ax = q.ndim - 3, q.ndim - 2
    # sequence-sharded -> head-sharded: [..., H, S/n, D] -> [..., H/n, S, D]
    swap = lambda x: jax.lax.all_to_all(
        x, axis_name, split_axis=h_ax, concat_axis=s_ax, tiled=True
    )
    o = attention(swap(q), swap(k), swap(v), causal=causal)
    # head-sharded -> sequence-sharded
    return jax.lax.all_to_all(
        o, axis_name, split_axis=s_ax, concat_axis=h_ax, tiled=True
    )


def ulysses_attention(q, k, v, mesh: Mesh, *, causal: bool = False):
    """All-to-all sequence-parallel attention: [..., H, S, D] arrays with S
    sharded over the mesh data axis and H divisible by the axis size. Two
    all-to-alls re-shard sequence->heads and back; attention itself runs
    locally and EXACTLY per head. Returns [..., H, S, D] sharded like q."""
    n_shards = mesh.shape[DATA_AXIS]
    if q.ndim < 3:
        raise ValueError("ulysses_attention needs [..., H, S, D] inputs")
    # validate q AND k (cross-attention may use a different S_k; GQA-style
    # mismatched head counts are not supported by the all-to-all re-shard)
    if k.shape[-3] != q.shape[-3]:
        raise ValueError(
            f"k head count {k.shape[-3]} must equal q's {q.shape[-3]}"
        )
    for name, arr in (("q", q), ("k", k)):
        h, s = arr.shape[-3], arr.shape[-2]
        if h % n_shards:
            raise ValueError(
                f"{name} head count {h} must be divisible by the {n_shards}-way "
                f"'{DATA_AXIS}' axis (use ring_attention when heads are scarce)"
            )
        if s % n_shards:
            raise ValueError(
                f"{name} sequence length {s} must be divisible by the {n_shards}-way "
                f"'{DATA_AXIS}' axis"
            )
    spec = P(*([None] * (q.ndim - 2)), DATA_AXIS, None)
    fn = jax.jit(
        jax.shard_map(
            partial(_ulysses_local, causal=causal, axis_name=DATA_AXIS),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, spec)
    return fn(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
