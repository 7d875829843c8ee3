"""Cross-shard top-k: per-shard fused partials, exactly merged.

Pod-scale serving (ROADMAP item 1) splits the item matrix by row across
shards (ops/transfer.ShardedMatrix, one device per shard when the host
has them): each shard runs the EXISTING fused score+top-k over its own
row slice — the gen-2 Pallas kernel on TPU, XLA elsewhere, quantized or
bf16 per shard — producing per-shard (values, global-index) top-k
partials. The cross-shard merge below is the gen-2 kernel's bitonic
split + merge (ops/pallas_topk) one level up: the same (value desc,
index asc) total order that makes the in-kernel merge agree with
jax.lax.top_k makes the cross-shard merge select exactly the INDICES
the unsharded dispatch selects — duplicate-score tie-breaks included —
which is what lets a CPU host_mesh(n) simulation prove the sharded path
correct before a pod ever runs it. Values agree to f32 rounding only:
a shard's matmul has a different shape from the whole matrix's, XLA may
accumulate it in a different order, and the last ulp can differ.

The merge runs as a host-side reduce (partials are fetched and merged on
the default device). At k <= 128 a partial is ~1 KB per shard per row —
three orders of magnitude below the per-shard HBM scan it concludes —
so the reduce is not worth a collective until shard counts reach the
hundreds; the merge tree itself is shard-count-agnostic either way.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops.pallas_topk import _bitonic_merge, _split_top

# index value carried by merge padding slots: loses every (value desc,
# index asc) comparison against any real candidate at equal value
_PAD_IDX = np.iinfo(np.int32).max

_MERGE_METRICS = None
_MERGE_METRICS_LOCK = threading.Lock()


def _merge_metrics():
    """(merge-seconds histogram,) — process-wide, lazily registered so
    importing this module never touches the registry."""
    global _MERGE_METRICS
    if _MERGE_METRICS is None:
        with _MERGE_METRICS_LOCK:
            if _MERGE_METRICS is None:
                from oryx_tpu.common.metrics import (
                    MICROBATCH_BUCKETS, get_registry,
                )

                _MERGE_METRICS = (
                    get_registry().histogram(
                        "oryx_shard_merge_seconds",
                        "wall-clock of one cross-shard top-k merge (the "
                        "host-side reduce over per-shard partials; the "
                        "per-shard scans it concludes ride "
                        "oryx_device_dispatch_seconds)",
                        buckets=MICROBATCH_BUCKETS,
                    ),
                )
    return _MERGE_METRICS


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _pad_tail(a, width: int, value):
    pad = width - a.shape[-1]
    if pad <= 0:
        return a
    return jnp.pad(
        a, [(0, 0)] * (a.ndim - 1) + [(0, pad)], constant_values=value
    )


def _merge_top(av, ai, bv, bi):
    """Exact top-L of two sorted-DESCENDING length-L lists: reverse one
    (plain XLA here, where a flip is free; the kernel never has to, its
    lists are produced in alternating directions), bitonic split, one
    log-merge. 1 + log2(L) stages."""
    v, i = _split_top(av, ai, jnp.flip(bv, axis=-1), jnp.flip(bi, axis=-1))
    return _bitonic_merge(v, i, descending=True)


def _merge_stacked(vals, idx, *, k: int):
    """Merge tree over stacked sorted-descending partials: vals/idx
    [S, B, L] (L pow2) -> exact top-k of the union per row, ordered by
    (value desc, index asc). Pairwise _merge_top halvings — the gen-2
    kernel's block merge applied across shards."""
    s = vals.shape[0]
    while s > 1:
        half = s // 2
        mv, mi = _merge_top(
            vals[:half], idx[:half], vals[half : 2 * half], idx[half : 2 * half]
        )
        if s % 2:
            vals = jnp.concatenate([mv, vals[-1:]], axis=0)
            idx = jnp.concatenate([mi, idx[-1:]], axis=0)
        else:
            vals, idx = mv, mi
        s = vals.shape[0]
    return vals[0, :, :k], idx[0, :, :k]


_merge_stacked_jit = jax.jit(_merge_stacked, static_argnames=("k",))


def merge_topk_partials(partials, k: int):
    """Exact top-k of the union of per-shard top-k partials.

    partials: [(vals [B, k_s], idx [B, k_s])] per shard, each row sorted
    descending with GLOBAL indices (ties already index-ascending — what
    lax.top_k and the fused kernel both emit after index rebasing).
    Returns ([B, k] f32, [B, k] int32) in the same total order the
    single-matrix kernel produces, tie-breaks included.
    Padding slots carry (-inf, int32 max) so they lose every comparison
    against real candidates.
    """
    if not partials:
        raise ValueError("merge_topk_partials needs at least one partial")
    width = _pow2_ceil(max(k, max(int(v.shape[-1]) for v, _ in partials)))
    vals = jnp.stack([
        _pad_tail(jnp.asarray(v, dtype=jnp.float32), width, -jnp.inf)
        for v, _ in partials
    ])
    idx = jnp.stack([
        _pad_tail(jnp.asarray(i, dtype=jnp.int32), width, _PAD_IDX)
        for _, i in partials
    ])
    return _merge_stacked_jit(vals, idx, k=k)


def topk_dot_batch_sharded(
    xs, sm, *, k: int, recall: float = 1.0, rows=None
):
    """Batched top-k over a ShardedMatrix: each shard scores its row
    slice with the normal kernel-selection path (ops.als.topk_dot_batch
    — fused Pallas on TPU, quantized/bf16 per the shard's dtype), with
    the query block placed on the shard's device, then the per-shard
    partials merge exactly with indices rebased to global rows. Every
    shard is given the same `rows` (ops.als.topk_dot_batch: the real
    leading rows of xs).

    Top-k is associative over row partitions, so the merge is exact;
    with recall < 1 each shard's partial reduce carries the same
    per-shard recall target (the chunked kernel's convention)."""
    from oryx_tpu.ops.als import topk_dot_batch

    total = sm.plan.total
    if k > total:
        # contract parity with the single-dispatch kernel (lax.top_k
        # raises there); padded merge slots would otherwise fabricate
        # (-inf, pad-index) results
        raise ValueError(f"k={k} exceeds total rows {total}")
    partials = []
    for s, shard in enumerate(sm.shards):
        n_s = int(shard.shape[0])
        if n_s == 0:
            continue  # an empty shard contributes no candidates
        dev = next(iter(shard.devices()), None)
        xs_s = xs if dev is None else jax.device_put(xs, dev)
        v, i = topk_dot_batch(
            xs_s, shard, k=min(k, n_s), recall=recall, rows=rows
        )
        partials.append((v, i + sm.plan.lo(s)))
    t0 = time.monotonic()
    # host-side reduce: partials come back to the default device and the
    # bitonic merge tree runs once over the stack
    merged = merge_topk_partials(
        [(np.asarray(v), np.asarray(i)) for v, i in partials], k
    )
    _merge_metrics()[0].observe(time.monotonic() - t0)
    return merged
