"""Fused streaming dot+top-k Pallas TPU kernel for the serving hot path.

`topk_dot_batch` in ops/als.py is the whole serving request path (the
reference's ALSServingModel.topN LSH fan-out, app/oryx-app-serving
.../als/model/ALSServingModel.java:264-279, collapsed into one matmul +
top-k). Its XLA form materializes the [B, I] score matrix in HBM — at
reference scale (B=1024 requests x I=20M items) that is an 80 GB write +
read per dispatch, dwarfing the matmul itself. This kernel streams item
blocks HBM->VMEM, scores each block on the MXU, and folds it into a
running per-row top-k held in VMEM scratch, so Y is read exactly once and
the score matrix never exists.

Second generation (PR 8), three changes over the first kernel:

- Selection: the first kernel ran k sequential argmax+mask sweeps over a
  [Bb, k+Ib] candidate buffer — O(k·Ib) VPU work per block that capped
  the fused path at k<=32. Now a 128-item chunk is BITONIC-sorted
  ascending (28 compare-exchange stages), split against the descending
  running top-128 and merged back (1 + 7 stages): 36 vectorized stages,
  independent of k, exact for any k <= 128 — the comparisons order by
  (value desc, index asc), the same total order as jax.lax.top_k, so
  duplicate scores tie-break identically. The chunks of a block are a
  fori_loop, not an unrolled merge tree: the unrolled form of PR 8 (a
  [Bb, block_i] score block, sorted whole) took 377 s to compile under
  Mosaic at block_i=4096 — longer than the batcher's cold-compile grace
  — against ~1 s for the loop, and needed `rev` and lane-splitting
  reshapes that Mosaic does not lower (PR 21).
- Streaming: the item matrix stays in HBM (`memory_space=ANY`) and the
  kernel issues its own double-buffered `pltpu.make_async_copy` DMAs
  into a 2-slot VMEM scratch, starting block i+1's copy before computing
  block i.
- Blocks: `(block_b, block_i)` are a pure function of the feature pad
  and the item matrix's itemsize (`tuned_blocks`): the largest block
  whose working set fits the VMEM budget. The shape a resident view is
  stored in (`view_shape`) follows from it, so nothing in the process
  can change it after an upload.

The threshold gate (PR 26). Until PR 26 EVERY chunk paid the 36 stages,
so the kernel was bound by the sort network, flat in k and features and
linear in query rows: 1,116 ms for a 512-row dispatch over a 6,291,456
x 256 bf16 view, whatever it held. But a chunk can change a row block's
result only if one of its scores is above that row's running k-th score
(`thr`: lane k - 1 of the descending running list). Strict `>` is the
kernel's own order: items stream in index order, so a chunk's indices
are above every index already held and an equal score loses its tie. An
item enters a row's top-k of n seen with probability k / n, so over a
large catalog almost no chunk qualifies; the zero rows the batcher pads
a dispatch with score 0.0 everywhere and qualify once. So the kernel
scores `_GATE_CHUNKS` chunks with one dot, tests the elementwise max of
their scores against `thr` (one compare, one reduce to a scalar, one
branch), and only inside a group that fires tests and folds its chunks
one by one. A skipped chunk holds nothing that precedes any row's k-th
element, so the output is bit for bit what folding every chunk gives;
lanes past k of the running list go stale and are never returned. The
kernel counts the chunks it folds and the chunks it walks (two int32
per row block; a third since PR 32 and a fourth since PR 35, below); the
wrapper returns them beside the results when asked (`counted=True`), and
the batcher puts them on the DispatchRecord and into
`oryx_topk_chunks_folded` / `oryx_topk_chunks`.

Measured on one v5e chip (PR 26; 512-row dispatch, k = 128, over a
6,291,456 x 256 bf16 view holding 5,000,000 rows of standard-normal
factors, all four row blocks walked as they were until PR 30; the
ungated kernel took 1,116 ms in every case, and every result below is
bit-identical to its):

    real rows of the 512     time       chunks folded of 196,608
    1                         23.5 ms        788
    16                        62.0 ms      6,791
    112                      159.2 ms     22,801
    512                      619.4 ms     97,391
    112, ascending *         255.8 ms     39,066
    512, ascending *         967.9 ms    156,252

    * the worst case: the items stored in ascending order of score for
      every row asked about, so that every chunk of real items folds. A
      chunk that folds costs 6.2 us against 5.67 us before the gate; the
      ungated kernel's time is not reached here only because the view's
      capacity-pad rows (score 0) still skip.

A gated group of 8 chunks costs 0.78 us (one gate per chunk: 0.33 us a
chunk, 69 ms for the one-row dispatch; one gate per 16 or 32 chunks is
no faster than per 8), so one pass of a row block over this view has a
floor of 4.2-4.8 ms against 3.93 ms of HBM time for its 3.22 GB. PR 21's
comparison (XLA matmul + top_k at 512 x 1.31M x 50f: 41 / 72 / 257 ms at
k 16 / 32 / 128, the ungated kernel 236 ms) has not been repeated with
the gate.

Dead row blocks (PR 30). The grid walks the whole view once per row
block, so until PR 30 a 512-row dispatch was FOUR passes whatever it
held, and the batcher's dispatches hold 1-10 real rows: three of the
four passes scored zeros (12.7 of 36.2 ms at 5 real rows, and 9.66 GB
of the 12.9 GB read from HBM). The kernel is now told how many leading
rows of the query block are real (`rows`: an int32 scalar prefetched
into SMEM, traced, so one compiled program serves every count) and a
row block that lies wholly past them is dead: it starts no DMA, waits
on none, runs no gate loop and counts no chunk; its grid steps are
empty (2,304 of them cost 0.12 ms) and its output blocks are written
once with (-inf, index 0, counts 0). A dynamic bound on the grid's row
dimension was measured against this and is no faster (23.31 against
23.43 ms at 5 rows, 11.75 against 10.14 at 1), and leaves the dead
blocks' outputs to be masked outside the kernel. Same view, 512-row
dispatch, k = 128, one v5e (PR 30; parent -> this kernel, the real rows
bit for bit the parent's):

    real rows     bf16 k 128        bf16 k 32         int8 k 128
    1             22.89 -> 10.14
    5             36.16 -> 23.43    23.29 -> 10.51    30.17 -> 22.47
    129          171.87 -> 163.43   (two blocks walked, two skipped)
    512          618.03 -> 618.13   (no block is dead)

What is left of a small dispatch is its one live block: its own pass
over the view and about 600 folds a real row at 6.07 us.

Live tiles (PR 32). A fold ran its 36 compare-exchange stages over the
whole [128, 128] row block, sixteen (8, 128) sublane tiles of values and
sixteen of indices, and the batcher's dispatches hold 1-10 real rows:
fifteen of the sixteen tiles it sorted held zero rows. The kernel knows
how many rows of a block are real (`rows`, above), so a fired chunk is
now folded at the narrowest of the widths 1, 2, 4, 8 tiles or the whole
block (`_FOLD_TILES`) that holds the block's live tiles: static,
sublane-aligned slices of the score block, the running lists and `thr`,
one `pl.when` a width, chosen from the count the kernel is given and
never from an option (all five compile in 3-4 s). A row's running list
changes only through that row's own scores and no row takes part in
another's sort network, so the real rows come back bit for bit as
before. A row at or past `rows` starts with `thr` = +inf, so it never
fires the gate (a zero row used to fire once), and is written out as
(-inf, index 0) whether its block is live or dead. The kernel counts the
tiles its folds sort beside the chunks (a third int32 per row block):
`oryx_topk_fold_tiles` over 16 x `oryx_topk_chunks_folded` is the share
of a whole-block fold's work still done.

The fold is a dependent chain, so its time is the chain's latency and
not the tiles' count: one tile costs 3.0 us (83 ns, about 78 cycles, a
stage) where sixteen cost 6.2 (170 cycles a stage), and the fired
group's store and chunk tests add 0.65 us a fold. Same view, 512-row
dispatch, one v5e (PR 32; parent -> this kernel, the real rows bit for
bit the parent's, every other row the filler; the folded chunks are the
parent's, the width is the tiles a fold sorts):

    real rows     bf16 k 128         chunks folded   width   us a fold
    1             10.16 ->   7.77          844          1      3.65
    5             23.42 ->  15.25        2,878          1      3.65
    8             32.12 ->  20.10        4,211          1      3.65
    9             34.57 ->  22.08        4,595          2      3.78
    17            51.63 ->  34.46        7,296          4      4.08
    25            65.62 ->  43.19        9,545          4      4.03
    33            77.34 ->  59.06       11,442          8      4.75
    64           111.26 ->  83.99       17,038          8      4.65
    127          153.40 -> 154.12       24,084         16      6.2
    129          163.59 -> 161.99       24,972     16 and 1
    512          618.32 -> 621.12       97,361         16      (+0.45 %)
    5, bf16 k 32  10.52 ->   7.93          908          1
    5, int8 k 128 22.47 ->  14.28        2,878          1
    5, int8 k 32   9.43 ->   6.86          903          1

Measured against it and not kept: folding the live tiles one by one in a
`fori_loop` (the same 7.80 and 15.30 ms at 1 and 5 rows, but 2, 3 and 4
tiles cost 6.7, 9.7 and 12.7 us a fold: nothing of one tile's chain
overlaps the next's), and a tile testing its own eight rows against
`thr` before it folds (0.6 us more a fold at one tile; 24.0 / 39.7 /
55.3 / 71.2 ms at 9 / 17 / 25 / 33 rows, behind the widths; 131.6 ms at
64 rows, 267.6 at 127 and 1,076.5 at 512, far behind the whole-block
fold: sixteen reduces to a scalar a chunk cost more than the tiles they
save). What is left of a one-row dispatch: the pass, 4.7 ms, and 844
folds at 3.65 us.

Single entrants (PR 35). What was left of a small dispatch after PR 32
was the folds: a fired chunk paid the 36 dependent stages (3.0 us on one
tile, 3.7 with the fired group's store and chunk tests) whatever it
brought, and most fired chunks bring ONE score above the row's running
k-th: an item enters a row's top-k of n seen with probability k / n, so
late in the catalog a chunk that fires holds one entrant and the other
127 scores are at or below `thr`. For those the sort of the chunk and
the split and merge compute what a compare and a lane shift give. So a
chunk's test now says HOW MANY of its scores are above each live row's
`thr` and the kernel acts on the largest count over the rows (one lane
sum and one reduce to a scalar, in place of the any-test's reduce, at
the live width): 0, the chunk is skipped as before; 1, every row has at
most one entrant and each is placed (`insert_rows`): the row's entrant
is a lane max of the scores above `thr`, the lanes holding a value at or
above it stay (the list is sorted descending over all 128 lanes, so they
are a prefix, and an equal value already held has the lower index and
stays ahead: the kernel's own strict `>`), the entrant takes the first
lane outside the prefix and the rest move up one lane (`pltpu.roll`),
lane 127 falling off; the new k-th is the smaller of the entrant and the
old (k - 1)-th; a row with no entrant has the entrant -inf, every lane
stays and `thr` is kept, so rows need no branch of their own. 2 or more
in some row: the fold, unchanged. It is exact: a chunk score not above
`thr` cannot be among a row's k best (an equal one loses its tie), so
lanes [:k] after the insert are the k best of the old k and the entrant,
bit for bit what the fold gives; for k < 128 a fold would also have
carried scores between the k-th and the 128-th into lanes past k, which
are stale by the kernel's own contract, never returned, and stay at or
below the k-th as every later fold needs. The choice is made from what
the kernel observes in the chunk it holds, at the same static widths as
the fold (`_FOLD_TILES`); no caller, option or shape selects it, and
the int8 kernel takes the same path. The kernel counts the chunks it
places (a fourth int32 per row block): `oryx_topk_chunks_inserted` over
`oryx_topk_chunks_folded` (which counts every fired chunk, placed or
folded, as before) is the share of the path that engages; the tiles
count only what the folds sorted.

Read first, on the chip, with a build that counts and still folds every
fired chunk: the share of fired chunks whose largest per-row count is 1
is 0.83 / 0.86 / 0.90 at 1 / 2 / 5 real rows at k 128 and 0.86 / 0.89 /
0.91 at k 32 (the issue expected two thirds), and that build is as fast
as the parent (the count costs what the any-test did). A placed chunk
costs 0.7-0.9 us with the fired group's share against 3.7 for a folded
one; at the whole block 0.6 against 6.3. Same view, 512-row dispatch,
one v5e (PR 35; parent -> this kernel, calls issued back to back as PR
32's table was taken, the real rows bit for bit the parent's, every
other row the filler; fired are the parent's folded chunks):

    real rows     bf16 k 128           fired    placed   share placed
    1              7.51 ->   5.60         775      645      0.83
    2              9.76 ->   6.27       1,376    1,186      0.86
    5             15.20 ->   7.63       2,860    2,568      0.90
    8             19.73 ->   8.69       4,130    3,748      0.91
    9             21.70 ->   9.28       4,516    4,102      0.91
    17            34.19 ->  11.78       7,240    6,614      0.91
    25            43.16 ->  13.43       9,556    8,781      0.92
    33            59.13 ->  15.71      11,479   10,595      0.92
    64            84.17 ->  18.96      17,099   15,892      0.93
    127          154.97 ->  29.17      24,197   22,446      0.93
    129          162.81 ->  34.54      25,089   23,197      0.92
    512          621.48 -> 114.63      97,397   90,427      0.93
    1, bf16 k 32   5.43 ->   4.80         246      211      0.86
    5, bf16 k 32   8.11 ->   5.55         949      865      0.91
    5, int8 k 128 14.16 ->   6.65       2,844    2,547      0.90
    5, int8 k 32   7.06 ->   4.48         957      874      0.91

So kernel(r) = 5.1 + 0.5 r ms where it was 5.9 + 1.9 r, and a dispatch
of 512 real rows is four passes (19 ms), 7,000 folds (44 ms) and 90,000
placements (52 ms). Measured against it and not kept: the entrant's
value and index reduced before the branch, beside the count (within
0.4 ms of it up to 64 rows, 5-6 % slower at 127-512: the reduces are
then paid by every tested chunk), and the new k-th read out of the new
list as a fold does (a fourth reduce, in the chain: within 0.25 ms at
1-17 rows, 1-7 % slower at 33-512). What is left of a one-row dispatch:
the pass, 4.7 ms, 130 folds (0.5 ms) and 645 placements (0.4 ms).

Valid item rows (PR 40). A serving view is stored with room to grow
(ops/transfer.py row_capacity: an eighth of headroom, rounded up a bucket
ladder), so the speed layer appends without a new device matrix or a new
compiled shape: 6,291,456 rows for 5,000,000 items. Until PR 40 the
kernel's item count was the operand's static row count, so a fifth of
every pass streamed, multiplied and gated rows of zeros that belong to
no item (they scored 0.0 and the serving model dropped them on the
host). The count of valid item rows now rides beside `rows` in ONE
scalar-prefetch operand (int32[2], `stage_counts`: one array for both,
traced, so every catalog size that fits a view shares its compiled
program). With live_blocks = ceil(n_valid / block_i): a grid step at or
past live_blocks starts no DMA, waits on none, runs no dot and no gate
and counts no chunk; block i + 1 is prefetched only where i + 1 <
live_blocks, so every copy started is waited on; the tail mask compares
against n_valid, so the rows between it and the end of the last live
block are never selected and never move a threshold; the output block
is revisited over the item axis, so what the last live step wrote is
what comes back; n_valid = 0 is a dead row block. On the int8 path the
scales' block index stays on the last live block behind it, so their
pipeline fetches nothing new. A view filled to its capacity runs the
walk it ran before. Same view, 512-row dispatch, one v5e (PR 40; parent
-> n_valid 5,000,000 | n_valid 6,291,456; calls issued back to back;
values and indices of the real rows bit for bit the parent's in every
case; chunks walked a row block 49,152 -> 39,104):

    real rows     bf16 k 128                  bf16 k 32
    1              5.615 ->   4.776 |   5.643    4.784 -> 3.933 | 4.802
    5              7.716 ->   6.877 |   7.736    5.519 -> 4.671 | 5.540
    129           34.107 ->  32.401 |  34.127
    512          114.312 -> 110.882 | 114.336
    5, int8 k 128  6.722 ->   6.124 |   6.719
    1, int8 k 32   3.637 ->   3.029 |   3.651

0.84-0.85 ms a pass in bf16 (20.4 % of a 4.15 ms pass), 0.60 in int8; the
guards cost a full view 0.02-0.03 ms. What is left of a one-row dispatch
at k 32: 3.93 ms against 3.13 ms of HBM time for the 2.56 GB the items
take at 256 lanes.

The kernel also scores QUANTIZED item matrices (int8 rows + per-row f32
scales, ops/transfer.py QuantizedMatrix): the int8 stream halves the
bf16 HBM traffic that dominates the scan, queries are per-row
int8-quantized on device (quantize_queries) and the dot runs
int8 x int8 -> int32 on the MXU — the 2x-rate mode the int8 MFU peak
tables describe. Item scales multiply back before selection; query
scales (order-invariant per row) multiply the returned values after the
kernel. The serving tier re-ranks surviving candidates in f32 either
way (apps/als/serving.py _rerank_exact).

Layout: grid (B-blocks, I-blocks) with the item dimension innermost, so
each row block is one pass over the item matrix's valid rows; the counts
of real query rows and of valid item rows ride ahead of the grid as one
scalar-prefetch operand. A live block's running top-k scratch is
(re)initialized at item-block 0 and written to the output block on every
live step (the last live step's write wins), its rows past the real ones
as filler; a dead block writes its filler at item-block 0 and nothing
after. k is padded to the 128-lane tile internally and sliced by the
wrapper.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # TPU lane tile; also the padded top-k slot width
_SUBLANE = 8  # rows of one float32 (8, 128) tile: the unit a fold works on

# Widths, in sublane tiles, of the fold below the whole row block: a fired
# chunk is folded at the narrowest width that holds the block's real rows.
_FOLD_TILES = (1, 2, 4, 8)

# 128-item chunks scored by one dot and tested by one gate (pow2). The
# gate's cost is the reduce to a scalar and the branch, which the chunks
# of a group share; a group that fires tests its chunks one by one.
_GATE_CHUNKS = 8

# Scoped-VMEM working-set budget the block rule sizes against (v5e
# exposes ~16 MB; leave headroom for the compiler's own temporaries).
_VMEM_BUDGET_BYTES = 12 << 20
# The scoped VMEM the compiler gives a kernel that asks for none (v5e). A
# view so wide that the smallest item block's working set passes it (3,584
# bf16 features: 17.6 MiB at block_i 1,024) asks for its working set and
# the budget's headroom beside it (`scoped_vmem_limit`).
_SCOPED_VMEM_DEFAULT = 16 << 20


# ---------------------------------------------------------------------------
# bitonic partial-sort selection (exact, index-carrying)
#
# Every array here is sorted along a 128-lane last axis. The only data
# movement is a static lane rotate (pltpu.roll -> the XLU) plus a select:
# Mosaic lowers no `rev`, and a reshape that splits the lane axis is a
# relayout. No list is ever reversed in the kernel: a chunk is sorted
# ASCENDING and the running top-k is kept DESCENDING, which is what the
# bitonic split needs.
# ---------------------------------------------------------------------------

def _lane(x):
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def _partner(x, d, is_lo):
    """Values at lane XOR d (d a power of two below the lane count):
    lanes with bit d clear (`is_lo`) read d to the right, the others d
    to the left — two lane rotates and a select."""
    n = x.shape[-1]
    ax = x.ndim - 1
    return jnp.where(
        is_lo,
        pltpu.roll(x, n - d, ax),  # out[j] = x[j + d]
        pltpu.roll(x, d, ax),      # out[j] = x[j - d]
    )


def _before(v, i, v_o, i_o):
    """The strict total order (value desc, index asc): equal values
    resolve exactly like jax.lax.top_k's stable lowest-index-first."""
    return (v > v_o) | ((v == v_o) & (i < i_o))


def _cmp_exchange(v, i, d, desc):
    """One compare-exchange stage at XOR distance d, carrying indices.
    desc: bool, or bool array over the lanes — True where the run
    containing the lane sorts descending."""
    is_lo = (_lane(v) & d) == 0
    v_o = _partner(v, d, is_lo)
    i_o = _partner(i, d, is_lo)
    take_self = _before(v, i, v_o, i_o) == (is_lo == desc)
    return jnp.where(take_self, v, v_o), jnp.where(take_self, i, i_o)


def _bitonic_sort(v, i, descending: bool):
    """Full sort of the (pow2-length) lane axis, carrying i."""
    l = v.shape[-1]
    lane = _lane(v)
    size = 2
    while size <= l:
        # runs of `size` lanes alternate direction until the last pass
        desc = ((lane & size) == 0) == descending if size < l else descending
        d = size // 2
        while d >= 1:
            v, i = _cmp_exchange(v, i, d, desc)
            d //= 2
        size *= 2
    return v, i


def _bitonic_merge(v, i, descending: bool):
    """Sort a bitonic (pow2-length) lane axis: log2(L) stages."""
    d = v.shape[-1] // 2
    while d >= 1:
        v, i = _cmp_exchange(v, i, d, descending)
        d //= 2
    return v, i


def _split_top(av, ai, bv, bi):
    """The bitonic split of a descending list against an ASCENDING one:
    the elementwise winner keeps the L largest of the union, as a
    bitonic sequence one _bitonic_merge then sorts."""
    first = _before(av, ai, bv, bi)
    return jnp.where(first, av, bv), jnp.where(first, ai, bi)


# ---------------------------------------------------------------------------
# the kernel: manual double-buffered Y stream + bitonic merge
# ---------------------------------------------------------------------------

def _topk_kernel(
    counts_in, *refs, block_i, k, quantized,
):
    if quantized:
        (xs_ref, y_hbm, scale_ref, vals_ref, idx_ref, counts_ref,
         run_vals, run_idx, thr, group_scores, y_buf, sem, counts) = refs
    else:
        (xs_ref, y_hbm, vals_ref, idx_ref, counts_ref,
         run_vals, run_idx, thr, group_scores, y_buf, sem, counts) = refs
        scale_ref = None
    i = pl.program_id(1)
    block_b = xs_ref.shape[0]
    n_tiles = block_b // _SUBLANE
    # the widths, in sublane tiles, a fold comes in: the whole block last
    fold_widths = [w for w in _FOLD_TILES if w < n_tiles] + [n_tiles]
    # counts_in[0] leading rows of the query block are real (scalar
    # prefetch): this row block holds live_rows of them, in its first
    # live_tiles (8, 128) sublane tiles; past them is the caller's padding
    live_rows = jnp.clip(counts_in[0] - pl.program_id(0) * block_b, 0, block_b)
    live_tiles = (live_rows + (_SUBLANE - 1)) // _SUBLANE
    # counts_in[1] leading rows of the item matrix are items (never more
    # than it holds): they lie in its first live_blocks item blocks, and
    # the blocks behind them are the view's capacity, which no step reads
    n_valid = jnp.clip(counts_in[1], 0, pl.num_programs(1) * block_i)
    live_blocks = (n_valid + (block_i - 1)) // block_i
    live = (live_rows > 0) & (live_blocks > 0)

    def is_real(n):
        """[n, 128] mask of the block's first n rows: which are real."""
        return jax.lax.broadcasted_iota(jnp.int32, (n, _LANE), 0) < live_rows

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _dead():
        # no DMA, no gate: the block's outputs are written once, here, and
        # keep the defined filler through the block's other (empty) steps
        vals_ref[:] = jnp.full_like(vals_ref, -jnp.inf)
        idx_ref[:] = jnp.zeros_like(idx_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)

    @pl.when(live & (i < live_blocks))
    def _live():
        slot = jax.lax.rem(i, 2)

        def dma(s, chunk):
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(chunk * block_i, block_i)], y_buf.at[s], sem.at[s]
            )

        @pl.when(i == 0)
        def _init():
            dma(0, 0).start()
            run_vals[:] = jnp.full_like(run_vals, -jnp.inf)
            run_idx[:] = jnp.zeros_like(run_idx)
            # a padding row never fires the gate: nothing is above +inf
            thr[:] = jnp.where(is_real(block_b), -jnp.inf, jnp.inf)
            counts[0] = 0
            counts[1] = 0
            counts[2] = 0
            counts[3] = 0

        # prefetch block i+1 while block i computes: the double buffer. Only
        # a block some step will wait on: a copy started and never waited
        # on would leave its semaphore signalled for the next row block
        @pl.when(i + 1 < live_blocks)
        def _prefetch():
            dma(jax.lax.rem(i + 1, 2), i + 1).start()

        dma(slot, i).wait()

        xs = xs_ref[:]
        n_gate = group_scores.shape[1] // _LANE  # chunks behind one gate
        lane_g = jax.lax.broadcasted_iota(jnp.int32, group_scores.shape, 1)
        # [Bb, K] x [n_gate * 128, K]^T on the MXU, contracting the feature
        # axis of both (no materialized transpose)
        contract = (((1,), (1,)), ((), ()))

        def beats_kth(scores):
            """Whether any score is above its row's running k-th. Strict `>`
            is the kernel's own order: items stream in index order, so every
            index still to come is above every index in the running list and
            an equal score loses its tie."""
            return jnp.max(jnp.where(scores > thr[:], 1.0, 0.0)) > 0.0

        def fold_rows(scores, col):
            """Sort one chunk's scores ascending (28 stages) and fold them
            into the descending running top-128 (1 + 7 stages), for the
            leading rows of the block that `scores` holds: a row's list
            changes only through that row's own scores, so the rows left
            out keep theirs."""
            n = scores.shape[0]
            rs = slice(0, n)
            lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANE), 1)
            cv, ci = _bitonic_sort(scores, col + lane, descending=False)
            nv, nidx = _bitonic_merge(
                *_split_top(run_vals[rs, :], run_idx[rs, :], cv, ci),
                descending=True,
            )
            run_vals[rs, :] = nv
            run_idx[rs, :] = nidx
            # every real row's new k-th value, across all lanes
            kth = jnp.max(
                jnp.where(lane == k - 1, nv, -jnp.inf), axis=1, keepdims=True
            )
            thr[rs, :] = jnp.where(
                is_real(n), jnp.broadcast_to(kth, nv.shape), jnp.inf
            )

        def insert_rows(scores, above, col):
            """Place the ONE score of each row that is above its `thr`
            (`above` marks it; a row with none keeps its list) into the
            descending running list: the values at or above the entrant
            stay, the entrant follows them (it came later, so an equal value
            already held has the lower index and stays ahead), the rest move
            one lane up and lane 127 falls off. The list is sorted, so "at or
            above the entrant" is a prefix of the lanes and the entrant's
            place needs no count: it is the first lane outside the prefix.
            Lanes [:k] come out as a fold's would; the new k-th is the
            smaller of the entrant and the old (k - 1)-th."""
            n = scores.shape[0]
            rs = slice(0, n)
            lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANE), 1)
            rv, ri, old = run_vals[rs, :], run_idx[rs, :], thr[rs, :]
            # the entrant's value and index, and the old (k - 1)-th: three lane
            # reduces that wait on nothing but the chunk's test
            v = jnp.max(jnp.where(above, scores, -jnp.inf), axis=1, keepdims=True)
            vi = jnp.max(jnp.where(above, col + lane, 0), axis=1, keepdims=True)
            if k > 1:
                kth = jnp.minimum(v, jnp.max(
                    jnp.where(lane == k - 2, rv, -jnp.inf), axis=1, keepdims=True
                ))
            else:
                kth = v
            stays = rv >= v  # a row with no entrant: v = -inf, every lane stays
            up_v, up_i = pltpu.roll(rv, 1, 1), pltpu.roll(ri, 1, 1)  # out[j] = x[j - 1]
            first_out = jnp.logical_not(stays) & ((up_v >= v) | (lane == 0))
            run_vals[rs, :] = jnp.where(stays, rv, jnp.where(first_out, v, up_v))
            run_idx[rs, :] = jnp.where(stays, ri, jnp.where(first_out, vi, up_i))
            # v > old only in a row with an entrant; a padding row keeps +inf
            thr[rs, :] = jnp.where(v > old, kth, old)

        def gate_chunk(w, at, col):
            """One chunk of a fired group, at the w sublane tiles that hold
            the block's real rows: count, per row, the scores above the
            row's `thr`, and act on the largest count (one reduce to a
            scalar). 0: nothing of the chunk precedes any row's k-th. 1:
            every row has at most one entrant, which is placed without a
            sort. More: the 36 stages. Those are a dependent chain, so a
            fold's time is the chain's latency plus a little for every tile
            that rides along; rows of the width past the real ones never
            fire, are sorted along and never returned."""
            n = w * _SUBLANE
            s_j = group_scores[:n, pl.ds(at, _LANE)]
            above = s_j > thr[:n, :]
            most = jnp.max(
                jnp.sum(jnp.where(above, 1.0, 0.0), axis=1, keepdims=True)
            )

            @pl.when(most == 1.0)
            def _insert():
                insert_rows(s_j, above, col)
                counts[0] = counts[0] + 1
                counts[3] = counts[3] + 1

            @pl.when(most > 1.0)
            def _fold():
                fold_rows(s_j, col)
                counts[0] = counts[0] + 1
                counts[2] = counts[2] + w

        def gate_group(g, carry):
            """Score n_gate 128-item chunks of the block with one dot and
            fold, in order, only those holding a score above some row's
            running k-th (`thr`). A chunk that does not holds nothing that
            precedes any row's k-th element, so folding it would leave lanes
            [:k] of the running list as they are: it costs a compare. Lanes
            past k go stale, and stay at or below the k-th, so a later merge
            still has the exact top-k in its first k lanes. Loops, not
            unrolled trees: the program stays one fold long whatever block_i
            is, and the [Bb, block_i] score block never exists."""
            c0 = g * n_gate
            off = pl.multiple_of(c0 * _LANE, n_gate * _LANE)
            y_g = y_buf[slot, pl.ds(off, n_gate * _LANE), :]
            if scale_ref is not None:
                # TRUE int8 path: queries arrive pre-quantized (wrapper,
                # per-row scales), so the dot runs int8 x int8 -> int32 on
                # the MXU — the 2x-rate mode the int8 MFU peak describes —
                # exactly. Item scales multiply back in before selection
                # (they reorder across rows); the QUERY scales do not:
                # scaling a row by a positive constant never changes that
                # row's top-k order, so the wrapper applies them to the
                # returned values after the kernel.
                scale_g = jnp.concatenate(
                    [scale_ref[pl.ds(c0 + j, 1), :] for j in range(n_gate)], axis=1
                )
                scores = jax.lax.dot_general(
                    xs, y_g, contract, preferred_element_type=jnp.int32
                ).astype(jnp.float32) * scale_g
            else:
                scores = jax.lax.dot_general(
                    xs, y_g, contract, preferred_element_type=jnp.float32
                )
            col0 = i * block_i + off
            # the last live block's rows past the items are never selected
            scores = jnp.where(col0 + lane_g < n_valid, scores, -jnp.inf)
            # the gate is mostly a reduce to a scalar and a branch: one for
            # the group, on the elementwise max of its chunks' scores, and
            # one per chunk only inside a group that fired
            best = scores[:, :_LANE]
            for j in range(1, n_gate):
                best = jnp.maximum(best, scores[:, j * _LANE:(j + 1) * _LANE])

            @pl.when(beats_kth(best))
            def _walk():
                group_scores[:] = scores

                def chunks_at(w):
                    def gate(j, carry):
                        at = pl.multiple_of(j * _LANE, _LANE)
                        gate_chunk(w, at, col0 + at)
                        return carry

                    jax.lax.fori_loop(0, n_gate, gate, 0)

                # the group's chunks are tested, placed and folded over the
                # live sublane tiles of the block and no others: the
                # narrowest of the widths that holds them
                below = 0
                for w in fold_widths:
                    pl.when((below < live_tiles) & (live_tiles <= w))(
                        partial(chunks_at, w)
                    )
                    below = w

            return carry

        jax.lax.fori_loop(0, block_i // (n_gate * _LANE), gate_group, 0)
        counts[1] = counts[1] + block_i // _LANE
        # a padding row of a live block returns what a dead block does
        real = is_real(block_b)
        vals_ref[:] = jnp.where(real, run_vals[:], -jnp.inf)
        idx_ref[:] = jnp.where(real, run_idx[:], 0)
        # lane 0: chunks fired, lane 1: chunks walked, lane 2: sublane tiles
        # the folds sorted, lane 3: chunks placed without a sort, by this
        # row block
        lane_c = jax.lax.broadcasted_iota(jnp.int32, counts_ref.shape, 1)
        out = jnp.zeros(counts_ref.shape, jnp.int32)
        for j in range(4):
            out = jnp.where(lane_c == j, counts[j], out)
        counts_ref[:] = out


def _pad_to(x, size, axis, value=0.0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _pow2_floor(n: int) -> int:
    return 1 << max(0, n.bit_length() - 1)


def lane_pad(n_feat: int) -> int:
    """The feature width the kernel DMAs: up to the 128-lane tile."""
    return max(_LANE, -(-n_feat // _LANE) * _LANE)


# ---------------------------------------------------------------------------
# block rule: (feature pad, itemsize) -> (block_b, block_i), from the VMEM
# budget alone; view_shape stores a resident view by it
# ---------------------------------------------------------------------------


def _working_set_bytes(
    block_b: int, block_i: int, feat_pad: int, y_itemsize: int, x_itemsize: int = 4
) -> int:
    """Scoped-VMEM estimate for one grid step: the 2-slot Y stream
    buffer, the (pipelined, so doubled) query, scale and output blocks
    (the fold count's (8, 128) tile among them), the running top-k with
    the gate's threshold block and one group's scores beside it, and one
    chunk's sort network temporaries. The score block is one group of
    128-item chunks at a time, so block_i enters only through the stream
    buffer and the scales. The block rule sizes the query block at float32
    whatever its dtype (`x_itemsize`): the widest a path stages."""
    return (
        2 * block_i * feat_pad * y_itemsize
        + 2 * block_b * feat_pad * x_itemsize
        + 2 * block_i * 4
        + 6 * block_b * _LANE * 8
        + block_b * (1 + _GATE_CHUNKS) * _LANE * 4
        + 2 * 8 * _LANE * 4
        + 8 * block_b * _LANE * 4
    )


def tuned_blocks(feat_pad: int, y_itemsize: int) -> tuple[int, int]:
    """(block_b, block_i) for a feature pad + item-matrix itemsize: the
    largest pow2 block_i whose working set fits the VMEM budget at
    block_b=128. A function of its arguments and nothing else: a resident
    view is stored by it (`view_shape`) and `_topk_pallas_jit` refuses an
    operand that is not. int8 matrices (itemsize 1) stream twice the rows
    of bf16 per byte, so the same block_i is half the bytes."""
    block_b = 128
    block_i = 8192
    # >= 1024: a quantized block's scales are a [block_i/128, 128] f32
    # tile, whose sublane count must reach the (8, 128) minimum
    while block_i > 1024 and _working_set_bytes(
        block_b, block_i, feat_pad, y_itemsize
    ) > _VMEM_BUDGET_BYTES:
        block_i //= 2
    return block_b, block_i


def scoped_vmem_limit(
    block_b: int, block_i: int, feat_pad: int, y_itemsize: int, x_itemsize: int
) -> int | None:
    """The scoped VMEM one dispatch's kernel asks the compiler for: None (the
    compiler's default) where its working set at these blocks, the query
    block in its own dtype, fits the default; else that working set and the
    block rule's headroom beside it. Every view up to 3,072 bf16 features
    asks for none."""
    need = _working_set_bytes(block_b, block_i, feat_pad, y_itemsize, x_itemsize)
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return need + (_SCOPED_VMEM_DEFAULT - _VMEM_BUDGET_BYTES)


def item_block(
    n_items: int, feat_pad: int, y_itemsize: int, block_i: int | None = None
) -> int:
    """The item block one dispatch over `n_items` rows streams: the tuned
    block (or the caller's), a multiple of the lane tile for the chunk
    loop and pow2 so the compiled-shape count stays small. Non-pow2
    requests round DOWN — a test or a probe shrinking the block (the
    only callers that pass one) must get at most what it asked for,
    never a silently larger block — and never past the next pow2 of the
    row count (no point padding the item axis beyond it)."""
    if block_i is None:
        block_i = tuned_blocks(feat_pad, y_itemsize)[1]
    return max(_LANE, min(_pow2_floor(block_i), _pow2_ceil(n_items)))


def row_block(
    n_queries: int, feat_pad: int, y_itemsize: int, block_b: int | None = None
) -> int:
    """The rows of one row block of a dispatch of `n_queries` query rows:
    the tuned block (or the caller's), never more than the dispatch
    holds, in whole 8-row sublane tiles (the unit the kernel folds)."""
    if block_b is None:
        block_b = tuned_blocks(feat_pad, y_itemsize)[0]
    return -(-max(1, min(block_b, n_queries)) // _SUBLANE) * _SUBLANE


def view_shape(n_rows: int, n_feat: int, dtype) -> tuple[int, int]:
    """The shape a resident [n_rows, n_feat] item matrix of `dtype` is
    stored in so the kernel reads it as it lies: features up to the lane
    tile (zeros there leave every dot product as it is), rows up to a
    multiple of the item block a dispatch over them streams. The one
    rule for every form of the serving view (ops/transfer.py pads inside
    the upload); an array stored otherwise is padded by
    topk_dot_batch_pallas on every call. Applying it to its own result
    changes nothing."""
    feat_pad = lane_pad(n_feat)
    block_i = item_block(n_rows, feat_pad, jnp.dtype(dtype).itemsize)
    return -(-n_rows // block_i) * block_i, feat_pad


def dispatch_grid(
    n_queries: int, y_shape, y_dtype, n_valid: int | None = None
) -> tuple[int, int, int]:
    """(row blocks, 128-item chunks each LIVE one of them walks, chunks of
    the view behind those) of one dispatch of `n_queries` query rows at
    the tuned blocks over an item matrix of `y_shape` and `y_dtype` whose
    first `n_valid` rows are items (None: all of them). A row block walks
    the item blocks that hold a valid row and no other, so the second is
    what the kernel's walked count is a multiple of (a caller can read the
    row blocks it walked out of it) and the third is the capacity each of
    them left alone."""
    feat_pad = lane_pad(y_shape[1])
    itemsize = jnp.dtype(y_dtype).itemsize
    block_b = row_block(n_queries, feat_pad, itemsize)
    block_i = item_block(y_shape[0], feat_pad, itemsize)
    view_rows = -(-y_shape[0] // block_i) * block_i  # view_shape's rows
    valid = view_rows if n_valid is None else max(0, min(n_valid, view_rows))
    walked = -(-valid // block_i) * (block_i // _LANE)
    return -(-n_queries // block_b), walked, view_rows // _LANE - walked


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def quantize_queries(xs):
    """Per-row symmetric int8 quantization of a query block (device-side
    twin of transfer.quantize_rows_int8): (q int8, scale f32 [B]). The
    quantized kernels run the score dot int8 x int8 -> int32 on the MXU,
    which is what earns the int8 MFU denominator."""
    ax = jnp.max(jnp.abs(xs.astype(jnp.float32)), axis=1)
    sx = jnp.where(ax > 0, ax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(xs.astype(jnp.float32) / sx[:, None]), -127, 127
    ).astype(jnp.int8)
    return q, sx


def stage_counts(rows, n_valid, n_queries: int, n_items: int):
    """The kernel's scalar-prefetch operand: int32[2], (real query rows,
    valid item rows), None meaning all `n_queries` and all `n_items`. Two
    host numbers make ONE host array that rides the jitted call (the
    second never past `n_items`, where a caller's unaligned matrix is
    padded); a count already on the device (or traced) is stacked there,
    and the kernel reads no further than its operand whatever it says. An
    int32[2] array given as `rows` was staged earlier and passes through."""
    if getattr(rows, "shape", None) == (2,):
        return rows
    rows = n_queries if rows is None else rows
    n_valid = n_items if n_valid is None else n_valid
    if isinstance(rows, jax.Array) or isinstance(n_valid, jax.Array):
        return jnp.stack([
            jnp.asarray(rows, dtype=jnp.int32).reshape(()),
            jnp.asarray(n_valid, dtype=jnp.int32).reshape(()),
        ])
    return np.array([rows, min(n_valid, n_items)], dtype=np.int32)


@partial(
    jax.jit,
    static_argnames=("k", "block_b", "block_i", "quantized", "interpret"),
)
def _topk_pallas_jit(
    xs, y, scales, counts, *, k, block_b, block_i, quantized, interpret
):
    """The kernel over an item matrix ALREADY in the shape it DMAs
    (view_shape): nothing the size of the catalog is copied here.
    `counts` is int32[2], traced, so one program serves whatever it
    holds (`stage_counts`): how many leading rows of `xs` are real, and
    how many leading rows of `y` are items. A row block past the real
    rows is not walked, and a fold sorts the live sublane tiles of its
    block alone; an item block past the valid rows is neither streamed
    nor scored, and the rows at or past the count inside the last live
    block are never selected. Third result: int32[4], (chunks fired,
    chunks walked, tiles the folds sorted, chunks placed without a
    sort)."""
    n_b = xs.shape[0]
    feat_pad = y.shape[1]
    if feat_pad % _LANE or y.shape[0] % block_i or xs.shape[1] > feat_pad:
        raise ValueError(
            f"item matrix {y.shape} is not in the kernel's shape for "
            f"{xs.shape[1]} features and item block {block_i} (view_shape)"
        )
    if quantized:
        # int8 queries into the int8 kernel; per-row query scales apply
        # to the returned VALUES only (row-positive scaling is top-k
        # order-invariant, so they never need to enter the kernel)
        xs, sx = quantize_queries(xs)
    # the query block alone is padded: features to the view's lanes
    # (zeros leave dot products unchanged), batch to the block size
    xs_p = _pad_to(_pad_to(xs, feat_pad, 1), -(-n_b // block_b) * block_b, 0)
    nb = xs_p.shape[0] // block_b
    ni = y.shape[0] // block_i

    n_gate = min(_GATE_CHUNKS, block_i // _LANE)
    kernel = partial(
        _topk_kernel, block_i=block_i, k=k, quantized=quantized,
    )
    # index maps take the prefetched counts after the grid indices
    in_specs = [
        pl.BlockSpec((block_b, feat_pad), lambda b, i, counts: (b, 0)),
        # the item matrix stays in HBM: the kernel streams its own
        # double-buffered DMA blocks out of it
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [xs_p, y]
    if quantized:
        # one row of scales per 128-item chunk, so the kernel picks a
        # chunk's scales with a sublane index. Behind the last live item
        # block the index stays on it: the pipeline fetches a block only
        # when its index changes, so the capacity's scales are not read
        def live_scales(b, i, counts):
            last = (jnp.clip(counts[1], 1, ni * block_i) - 1) // block_i
            return jnp.minimum(i, last), 0

        in_specs.append(pl.BlockSpec((block_i // _LANE, _LANE), live_scales))
        operands.append(
            jnp.asarray(scales, dtype=jnp.float32).reshape(-1, _LANE)
        )
    limit = scoped_vmem_limit(block_b, block_i, feat_pad, y.dtype.itemsize, xs_p.dtype.itemsize)
    asked = {} if limit is None else {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}
    vals, idx, tally = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, ni),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((block_b, _LANE), lambda b, i, counts: (b, 0)),
                pl.BlockSpec((block_b, _LANE), lambda b, i, counts: (b, 0)),
                # a row block's (chunks fired, chunks walked, tiles
                # sorted, chunks inserted) in lanes 0-3 of an (8, 128) tile
                pl.BlockSpec((8, _LANE), lambda b, i, counts: (b, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_b, _LANE), jnp.float32),
                pltpu.VMEM((block_b, _LANE), jnp.int32),
                pltpu.VMEM((block_b, _LANE), jnp.float32),
                pltpu.VMEM((block_b, n_gate * _LANE), jnp.float32),
                pltpu.VMEM((2, block_i, feat_pad), y.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((xs_p.shape[0], _LANE), jnp.float32),
            jax.ShapeDtypeStruct((xs_p.shape[0], _LANE), jnp.int32),
            jax.ShapeDtypeStruct((nb * 8, _LANE), jnp.int32),
        ],
        interpret=interpret,
        **asked,
    )(counts, *operands)
    vals, idx = vals[:n_b, :k], idx[:n_b, :k]
    if quantized:
        # scale the selected values back into score units (sx > 0, so
        # -inf padding slots stay -inf)
        vals = vals * sx[:n_b, None]
    return vals, idx, jnp.sum(tally[::8, :4], axis=0)


def topk_dot_batch_pallas(
    xs,
    y,
    *,
    k: int,
    scales=None,
    block_b: int | None = None,
    block_i: int | None = None,
    interpret: bool = False,
    counted: bool = False,
    rows=None,
    n_valid=None,
):
    """Top-k of xs @ y.T per row without materializing the score matrix.

    xs: [B, K] queries; y: [I, K] item factors; returns ([B, k] f32 scores,
    [B, k] int32 indices), identical ordering to jax.lax.top_k — including
    duplicate-score tie-breaks (lowest index first). k <= 128 (one lane
    tile of running top-k state). scales: per-row f32 dequantization
    scales for an int8 y (ops/transfer.py QuantizedMatrix) — scores become
    (xs @ y.T) * scale. interpret=True runs the kernel in the Pallas
    interpreter (CPU tests).

    The kernel sorts and merges a 128-item chunk only if one of its
    scores is above some row's running k-th score (the module docstring
    says why that is exact); every other chunk costs its share of one
    compare. Its time therefore follows the input: a few real rows over
    a large catalog fold a few percent of the chunks, every chunk folds
    only where the items are stored in ascending order of score for the
    rows asked about, and a chunk that folds costs about a tenth more
    than before the gate. A fired chunk that brings no row more than one
    such score is not sorted at all: each row's entrant is placed into
    its running list (a lane max, two lane rotates), with the same result
    bit for bit. counted=True appends an int32[4] array, (chunks fired =
    folded or placed, chunks walked = row blocks walked x item chunks,
    sublane tiles the folds sorted, chunks placed without a sort), so a
    caller can see which case it is in.

    rows: how many leading rows of xs are real (an int, or an int32
    scalar array; never a static argument, so every count shares one
    compiled program); None means all of them. The rows before `rows`
    come back bit for bit as without it; every row at or past `rows`
    comes back (-inf, index 0) in every slot, whichever block it is in. A
    block of block_b rows that lies wholly past them is not walked: it
    starts no DMA, runs no gate and counts no chunk. In the block the
    last real row falls in, the rows past it never fire the gate, and a
    fired chunk is placed or folded over the block's live 8-row sublane
    tiles alone (at the narrowest of a few widths that holds them), so a
    dispatch of a few requests in a 512-row block sorts 8 rows a fold,
    not 128.

    n_valid: how many leading rows of y are items (an int or an int32
    scalar array, traced like `rows`: every catalog size that fits one
    view shares one compiled program); None means all of them. A serving
    view is stored with room to grow (ops/transfer.py row_capacity), and
    the rows behind its last item belong to none. The result is bit for
    bit that of the kernel over y[:n_valid], whatever the rows behind
    hold: an item block that lies wholly past the count starts no DMA,
    runs no dot and no gate and counts no chunk, so a row block walks
    ceil(n_valid / block_i) item blocks, and the rows of the last of
    them at or past the count are masked out before the gate. n_valid = 0
    returns the filler in every row. `rows` may also be the int32[2]
    array (rows, n_valid) that ops.als.stage_topk_operands staged
    earlier; n_valid is then left None.

    block_b/block_i default to the block rule (`tuned_blocks`): the
    largest pow2 item block whose double-buffered stream + sort
    temporaries fit the scoped-VMEM budget. A kernel that fails to
    compile or run raises; ops.als.topk_path decides from shapes, before
    the call, whether this kernel is used at all.
    """
    if k > _LANE:
        raise ValueError(f"k must be <= {_LANE}, got {k}")
    n_items = y.shape[0]
    feat_pad = lane_pad(y.shape[1])
    itemsize = jnp.dtype(y.dtype).itemsize
    block_b = row_block(xs.shape[0], feat_pad, itemsize, block_b)
    block_i = item_block(n_items, feat_pad, itemsize, block_i)
    if y.shape[1] != feat_pad or n_items % block_i:
        # not a resident serving view (tests, tools, the trainer's
        # evaluation): pad here, outside the jitted call, once per call
        y_rows = -(-n_items // block_i) * block_i
        y = _pad_to(_pad_to(y, feat_pad, 1), y_rows, 0)
        if scales is not None:
            scales = _pad_to(jnp.asarray(scales, dtype=jnp.float32), y_rows, 0)
    vals, idx, chunks = _topk_pallas_jit(
        xs, y, scales, stage_counts(rows, n_valid, xs.shape[0], n_items),
        k=k, block_b=block_b, block_i=block_i,
        quantized=scales is not None, interpret=interpret,
    )
    return (vals, idx, chunks) if counted else (vals, idx)
