"""ALS collaborative filtering — pjit-sharded trainer + incremental fold-in.

TPU-native re-design of the reference's ALS compute path:

- Batch training replaces org.apache.spark.mllib.recommendation.ALS (invoked
  at app/oryx-app-mllib .../als/ALSUpdate.java:140-151) with alternating
  normal-equation solves: interactions become *padded per-entity lists*
  (static shapes for XLA), each half-iteration is one big batched
  gather -> einsum -> Cholesky-solve on the MXU, with the user/item axes
  sharded over the mesh "data" axis. The Gram matrix Y^T.Y is a sharded
  einsum (XLA inserts the psum the reference hand-rolled as a partition
  sum). Implicit feedback follows Hu-Koren-Volinsky confidence weighting
  (c = 1 + alpha.r), explicit uses ALS-WR lambda.n_u regularization to
  match MLlib behavior.

- Input preprocessing mirrors ALSUpdate semantics (…/als/ALSUpdate.java:
  348-422): per-day exponential decay of old interactions, zero-threshold
  drop, NaN-as-delete aggregation for implicit (NaN-propagating sum),
  last-wins for explicit, optional log1p(r/epsilon) strength transform.

- The speed/serving incremental fold-in mirrors ALSUtils.computeTargetQui/
  computeUpdatedXu (app/oryx-app-common .../als/ALSUtils.java:37-106):
  interpolate the predicted strength toward 1/0 by the interaction
  strength, then solve (Y^T.Y) dXu = dQui.Yi against the cached Cholesky
  factor — here jitted and vmappable over a whole micro-batch.
"""

from __future__ import annotations

import logging
import math
import weakref
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

from oryx_tpu.common.rng import RandomManager
from oryx_tpu.ops.vector import gram


# ---------------------------------------------------------------------------
# host-side input preparation
# ---------------------------------------------------------------------------

@dataclass
class InteractionData:
    """Aggregated COO interactions with contiguous int ids."""

    user_ids: list[str]
    item_ids: list[str]
    users: np.ndarray  # [nnz] int32 indices into user_ids
    items: np.ndarray  # [nnz] int32 indices into item_ids
    values: np.ndarray  # [nnz] float32

    @property
    def n_users(self) -> int:
        return len(self.user_ids)

    @property
    def n_items(self) -> int:
        return len(self.item_ids)


def aggregate_interactions(
    users: np.ndarray,
    items: np.ndarray,
    values: np.ndarray,
    timestamps: np.ndarray | None = None,
    *,
    implicit: bool = True,
    decay_factor: float = 1.0,
    zero_threshold: float = 0.0,
    now_ms: int | None = None,
    log_strength: bool = False,
    epsilon: float = 1.0,
) -> InteractionData:
    """String-keyed raw events -> deduplicated COO with contiguous ids.

    Semantics parity with ALSUpdate: decay by factor^(days old), implicit
    NaN-propagating sum (NaN value = delete the pair), explicit last-wins by
    timestamp, drop aggregates <= zero-threshold (implicit), log-strength
    transform after aggregation. ID maps are sorted for determinism, like
    the reference's sorted zipWithIndex maps (ALSUpdate.java:180-189).
    """
    users = np.asarray(users)
    items = np.asarray(items)
    values = np.asarray(values, dtype=np.float64)
    n = len(values)
    ts = (
        np.asarray(timestamps, dtype=np.int64)
        if timestamps is not None
        else np.zeros(n, dtype=np.int64)
    )

    if decay_factor < 1.0 and now_ms is not None:
        # calendar-day ages (now's day-of-epoch minus the event's), not a
        # rolling 24h difference: an event's decay bucket is then a pure
        # function of ITS timestamp, so the incremental AggregateState can
        # store raw per-day sums and apply decay at view time — at any
        # later generation — and still match this from-scratch path
        # exactly. (The reference decays by whole days too.)
        days_old = np.maximum(0, now_ms // _DAY_MS - ts // _DAY_MS)
        values = values * np.power(decay_factor, days_old)

    uid_sorted, ui = _factorize_string_ids(users)
    iid_sorted, ii = _factorize_string_ids(items)
    pair = ui * len(iid_sorted) + ii

    if implicit:
        # NaN-propagating sum per pair: any NaN (delete marker) kills the pair
        uniq, inv = np.unique(pair, return_inverse=True)
        sums = np.zeros(len(uniq))
        np.add.at(sums, inv, values)  # NaN propagates into the bucket sum
        keep = ~np.isnan(sums) & (np.abs(sums) > zero_threshold) & (sums > 0)
        agg_pair, agg_val = uniq[keep], sums[keep]
    else:
        # last (by timestamp) wins; NaN final value = delete
        order = np.lexsort((ts, pair))
        pair_s, val_s = pair[order], values[order]
        last = np.r_[pair_s[1:] != pair_s[:-1], True]
        agg_pair, agg_val = pair_s[last], val_s[last]
        keep = ~np.isnan(agg_val)
        agg_pair, agg_val = agg_pair[keep], agg_val[keep]

    if log_strength:
        agg_val = np.log1p(np.maximum(agg_val, 0.0) / epsilon)

    au = (agg_pair // len(iid_sorted)).astype(np.int32)
    ai = (agg_pair % len(iid_sorted)).astype(np.int32)
    return InteractionData(uid_sorted, iid_sorted, au, ai, agg_val.astype(np.float32))


_DAY_MS = 86_400_000

_POW10 = 10 ** np.arange(1, 19, dtype=np.int64)


def _factorize_string_ids(arr: np.ndarray) -> tuple[list[str], np.ndarray]:
    """(lexicographically sorted distinct ids, index-per-row) — the
    vectorized form of the reference's sorted-distinct ID maps
    (ALSUpdate.java:180-189). np.unique on tens of millions of strings is
    a minutes-scale host bottleneck, so ids that are canonical decimal
    integers (the common case: MovieLens et al.) take an O(n) bincount
    factorization instead; anything else falls back to np.unique."""
    arr = np.asarray(arr)
    if arr.size == 0:
        return [], np.zeros(0, dtype=np.int64)
    if arr.dtype.kind in "iu":
        # already integer ids (e.g. from the native data loader, which only
        # accepts canonical decimal tokens) — no string checks needed
        nums = arr.astype(np.int64)
        canonical = True
    else:
        if arr.dtype.kind != "U":
            arr = arr.astype(str)
        try:
            nums = arr.astype(np.int64)
        except (ValueError, OverflowError):
            nums = None
        canonical = False
        if nums is not None and np.abs(nums).max() < 10**17:
            # canonical form check by exact digit count: rejects "07", "+7",
            # " 7", "-0" — strings astype(int) accepts but str() won't emit
            a = np.abs(nums)
            canon_len = np.searchsorted(_POW10, a, side="right") + 1 + (nums < 0)
            canonical = bool((np.char.str_len(arr) == canon_len).all())
    if nums is not None and canonical:
        lo = int(nums.min())
        span = int(nums.max()) - lo + 1
        if span <= max(4 * len(nums), 1 << 28):
            present = np.zeros(span, dtype=bool)
            present[nums - lo] = True
            uniq = np.nonzero(present)[0] + lo
            rank = np.cumsum(present) - 1
            inv = rank[nums - lo]
        else:
            uniq, inv = np.unique(nums, return_inverse=True)
        # remap numeric order -> lexicographic, for parity with the
        # reference's sorted string ids (only the small unique array
        # pays the string sort)
        uniq_strs = uniq.astype(str)
        lex = np.argsort(uniq_strs)
        perm = np.empty_like(lex)
        perm[lex] = np.arange(len(lex))
        return uniq_strs[lex].tolist(), perm[inv.astype(np.int64)]
    ids, inv = np.unique(arr, return_inverse=True)
    return ids.tolist(), inv.astype(np.int64)


# ---------------------------------------------------------------------------
# incremental aggregate state: aggregate_interactions, made mergeable
# ---------------------------------------------------------------------------

AGG_STATE_SCHEMA = 1


def _group_sum(u, i, d, v, presorted: bool = False):
    """Group (user, item[, day]) keys and NaN-propagating-sum their
    values: the ONE grouping kernel behind AggregateState's from_window,
    merge, and materialize paths — the stable lexsort keeps earlier
    entries (history order) first within a group, so partial sums add in
    the order the equivalence property test pins. d=None groups by
    (user, item) only. Returns (u_sorted, i_sorted, d_sorted, first_idx,
    sums) with one sums entry per group, first_idx naming each group's
    first sorted row."""
    if d is None:
        d = np.zeros(len(u), dtype=np.int64)
    if not presorted:
        order = np.lexsort((d, i, u))
        u, i, d, v = u[order], i[order], d[order], v[order]
    new = np.r_[
        True, (u[1:] != u[:-1]) | (i[1:] != i[:-1]) | (d[1:] != d[:-1])
    ]
    grp = np.cumsum(new) - 1
    sums = np.zeros(int(grp[-1]) + 1)
    np.add.at(sums, grp, v)  # NaN (delete marker) propagates into its group
    return u, i, d, np.nonzero(new)[0], sums


def agg_state_fingerprint(*, implicit: bool, with_days: bool) -> str:
    """Schema fingerprint a persisted snapshot must match to be loadable.
    zero-threshold / log-strength / the decay FACTOR are view-time
    parameters (materialize()) and deliberately absent: changing them must
    not force a full history re-read. Turning decay on/off changes the
    stored granularity (day buckets) and does."""
    return f"agg-v{AGG_STATE_SCHEMA}:implicit={implicit}:days={with_days}"


@dataclass
class AggregateState:
    """Persistent, mergeable form of ``aggregate_interactions``.

    Invariant: ``merge`` over any windowing of a history, then
    ``materialize``, equals ``aggregate_interactions`` over the
    concatenated history (bit-identical under exact float arithmetic;
    within rounding otherwise — the merge reorders sums only).

    - implicit: one entry per (user, item, day bucket) holding the raw
      NaN-propagating strength sum of that bucket. NaN (the delete
      marker) is KEPT in the state: any later strength added to a dead
      pair stays NaN, exactly like the full-history NaN-propagating sum.
      Decay is day-of-epoch (see aggregate_interactions), so a bucket's
      weight at any generation is ``sum * decay^(now_day - day)`` — decay
      never re-ages the stored sums. With decay off the day axis
      collapses to one bucket.
    - explicit: one entry per (user, item) holding (last_ts, raw last
      value); merges keep the newer timestamp, ties going to the newer
      window — the same winner the from-scratch stable lexsort picks.
      NaN value = delete, kept for the same resurrection-proofing.

    zero-threshold / positivity / log-strength are applied by
    ``materialize`` only: a pair below threshold this generation can come
    back above it later, exactly as a from-scratch re-aggregation would
    see it. Entries stay sorted by (user, item, day).
    """

    implicit: bool
    with_days: bool
    user_ids: np.ndarray  # [U] unicode, lexicographically sorted
    item_ids: np.ndarray  # [I] unicode, lexicographically sorted
    users: np.ndarray     # [M] int64 index into user_ids
    items: np.ndarray     # [M] int64 index into item_ids
    days: np.ndarray      # [M] int64 day-of-epoch bucket (0 when unused)
    vals: np.ndarray      # [M] float64 sums (implicit) / last value (explicit)
    last_ts: np.ndarray   # [M] int64 (explicit last-wins key; 0 when implicit)

    @property
    def entries(self) -> int:
        return len(self.vals)

    @property
    def fingerprint(self) -> str:
        return agg_state_fingerprint(
            implicit=self.implicit, with_days=self.with_days
        )

    @staticmethod
    def empty(*, implicit: bool, with_days: bool) -> "AggregateState":
        z = np.zeros(0, dtype=np.int64)
        return AggregateState(
            implicit, with_days,
            np.zeros(0, dtype="<U1"), np.zeros(0, dtype="<U1"),
            z.copy(), z.copy(), z.copy(), np.zeros(0, dtype=np.float64),
            z.copy(),
        )

    # -- construction --------------------------------------------------

    @staticmethod
    def from_window(
        users: np.ndarray,
        items: np.ndarray,
        values: np.ndarray,
        timestamps: np.ndarray | None = None,
        *,
        implicit: bool = True,
        with_days: bool = False,
    ) -> "AggregateState":
        """Aggregate ONE window of raw events into state form (the same
        id factorization and within-window combine rules as
        aggregate_interactions, minus the view-time transforms)."""
        users = np.asarray(users)
        items = np.asarray(items)
        values = np.asarray(values, dtype=np.float64)
        n = len(values)
        ts = (
            np.asarray(timestamps, dtype=np.int64)
            if timestamps is not None
            else np.zeros(n, dtype=np.int64)
        )
        if n == 0:
            return AggregateState.empty(implicit=implicit, with_days=with_days)
        uid_sorted, ui = _factorize_string_ids(users)
        iid_sorted, ii = _factorize_string_ids(items)
        uid_arr = np.asarray(uid_sorted, dtype=str)
        iid_arr = np.asarray(iid_sorted, dtype=str)
        ui = ui.astype(np.int64)
        ii = ii.astype(np.int64)
        day = (ts // _DAY_MS) if (implicit and with_days) else np.zeros(n, np.int64)
        if implicit:
            u_s, i_s, d_s, first, sums = _group_sum(ui, ii, day, values)
            return AggregateState(
                implicit, with_days, uid_arr, iid_arr,
                u_s[first], i_s[first], d_s[first], sums,
                np.zeros(len(first), dtype=np.int64),
            )
        # explicit: last (by timestamp) wins; stable sort breaks ties by
        # position in the window, like the from-scratch lexsort
        order = np.lexsort((ts, ii, ui))
        u_s, i_s, t_s, v_s = ui[order], ii[order], ts[order], values[order]
        last = np.r_[(u_s[1:] != u_s[:-1]) | (i_s[1:] != i_s[:-1]), True]
        keep = np.nonzero(last)[0]
        return AggregateState(
            implicit, with_days, uid_arr, iid_arr,
            u_s[keep], i_s[keep], np.zeros(len(keep), dtype=np.int64),
            v_s[keep], t_s[keep],
        )

    # -- merge -----------------------------------------------------------

    def merge(self, window: "AggregateState") -> "AggregateState":
        """Fold a newer window's state into this one: O(state + window),
        never O(history). ``window`` must be the NEWER side (explicit
        timestamp ties resolve toward it)."""
        if (self.implicit, self.with_days) != (window.implicit, window.with_days):
            raise ValueError("aggregate state schema mismatch")
        if window.entries == 0 and len(window.user_ids) == 0:
            return self
        if self.entries == 0 and len(self.user_ids) == 0:
            return window
        uids = np.union1d(self.user_ids, window.user_ids)
        iids = np.union1d(self.item_ids, window.item_ids)
        su = np.searchsorted(uids, self.user_ids)[self.users]
        si = np.searchsorted(iids, self.item_ids)[self.items]
        wu = np.searchsorted(uids, window.user_ids)[window.users]
        wi = np.searchsorted(iids, window.item_ids)[window.items]
        u = np.concatenate([su, wu])
        i = np.concatenate([si, wi])
        d = np.concatenate([self.days, window.days])
        v = np.concatenate([self.vals, window.vals])
        t = np.concatenate([self.last_ts, window.last_ts])
        if self.implicit:
            u_s, i_s, d_s, first, sums = _group_sum(u, i, d, v)
            return AggregateState(
                self.implicit, self.with_days, uids, iids,
                u_s[first], i_s[first], d_s[first], sums,
                np.zeros(len(first), dtype=np.int64),
            )
        # explicit: newest timestamp per pair wins; stable sort puts the
        # window's entry after the state's on equal ts, so ties go to it
        order = np.lexsort((t, i, u))
        u, i, v, t = u[order], i[order], v[order], t[order]
        last = np.r_[(u[1:] != u[:-1]) | (i[1:] != i[:-1]), True]
        keep = np.nonzero(last)[0]
        return AggregateState(
            self.implicit, self.with_days, uids, iids,
            u[keep], i[keep], np.zeros(len(keep), dtype=np.int64),
            v[keep], t[keep],
        )

    # -- view ------------------------------------------------------------

    def materialize(
        self,
        *,
        decay_factor: float = 1.0,
        zero_threshold: float = 0.0,
        now_ms: int | None = None,
        log_strength: bool = False,
        epsilon: float = 1.0,
    ) -> InteractionData:
        """The view-time half of aggregate_interactions: decay, delete/
        threshold filters and the log transform, over the merged state."""
        uid_list = self.user_ids.tolist()
        iid_list = self.item_ids.tolist()
        if self.implicit:
            w = self.vals
            if self.with_days and decay_factor < 1.0 and now_ms is not None:
                ages = np.maximum(0, now_ms // _DAY_MS - self.days)
                w = w * np.power(decay_factor, ages)
            if self.entries:
                # entries are already (user, item, day)-sorted: collapsing
                # the day axis groups by (user, item) in place
                u_s, i_s, _, first, sums = _group_sum(
                    self.users, self.items, None, w, presorted=True
                )
                pu, pi = u_s[first], i_s[first]
            else:
                sums = np.zeros(0)
                pu = pi = np.zeros(0, dtype=np.int64)
            keep = ~np.isnan(sums) & (np.abs(sums) > zero_threshold) & (sums > 0)
            agg_val = sums[keep]
            pu, pi = pu[keep], pi[keep]
        else:
            vals = self.vals
            if decay_factor < 1.0 and now_ms is not None:
                ages = np.maximum(0, now_ms // _DAY_MS - self.last_ts // _DAY_MS)
                vals = vals * np.power(decay_factor, ages)
            keep = ~np.isnan(vals)
            agg_val = vals[keep]
            pu, pi = self.users[keep], self.items[keep]
        if log_strength:
            agg_val = np.log1p(np.maximum(agg_val, 0.0) / epsilon)
        return InteractionData(
            uid_list, iid_list,
            pu.astype(np.int32), pi.astype(np.int32),
            agg_val.astype(np.float32),
        )

    # -- (de)serialization -------------------------------------------------

    def to_arrays(self) -> dict[str, np.ndarray]:
        """Compact columnar form for npz persistence (datastore snapshot)."""
        return {
            "user_ids": self.user_ids if self.user_ids.size else np.zeros(0, "<U1"),
            "item_ids": self.item_ids if self.item_ids.size else np.zeros(0, "<U1"),
            "users": self.users.astype(np.int64),
            "items": self.items.astype(np.int64),
            "days": self.days.astype(np.int64),
            "vals": self.vals.astype(np.float64),
            "last_ts": self.last_ts.astype(np.int64),
            "flags": np.asarray([int(self.implicit), int(self.with_days)], np.int64),
        }

    @staticmethod
    def from_arrays(arrays) -> "AggregateState":
        flags = np.asarray(arrays["flags"]).astype(np.int64)
        return AggregateState(
            bool(flags[0]), bool(flags[1]),
            np.asarray(arrays["user_ids"], dtype=str),
            np.asarray(arrays["item_ids"], dtype=str),
            np.asarray(arrays["users"], dtype=np.int64),
            np.asarray(arrays["items"], dtype=np.int64),
            np.asarray(arrays["days"], dtype=np.int64),
            np.asarray(arrays["vals"], dtype=np.float64),
            np.asarray(arrays["last_ts"], dtype=np.int64),
        )


def align_factors(
    prev_ids, prev_mat: np.ndarray | None, new_ids, features: int,
    seed_key=None,
) -> np.ndarray | None:
    """Map a previous generation's factor rows onto a new id table: ids
    retained across generations keep their learned rows, new ids get the
    cold random init (same scale as the trainers'). Returns None when
    there is nothing usable to resume from (no previous factors, or the
    feature width changed — a hyperparameter move cold-starts)."""
    if prev_mat is None or len(np.shape(prev_mat)) != 2:
        return None
    prev_mat = np.asarray(prev_mat, dtype=np.float32)
    if prev_mat.shape[1] != features or prev_mat.shape[0] == 0:
        return None
    prev_ids = np.asarray(prev_ids, dtype=str)
    new_ids = np.asarray(new_ids, dtype=str)
    order = np.argsort(prev_ids, kind="stable")
    prev_sorted, prev_rows = prev_ids[order], prev_mat[order]
    key = seed_key if seed_key is not None else RandomManager.get_key()
    # np.array (not asarray): jax hands back a read-only host view
    out = np.array(
        jax.random.normal(key, (len(new_ids), features), dtype=jnp.float32)
        * 0.1
        + 1.0 / math.sqrt(features)
    )
    pos = np.searchsorted(prev_sorted, new_ids)
    pos_c = np.clip(pos, 0, len(prev_sorted) - 1)
    hit = prev_sorted[pos_c] == new_ids
    out[hit] = prev_rows[pos_c[hit]]
    return out


def build_padded_lists(
    entity: np.ndarray,
    other: np.ndarray,
    values: np.ndarray,
    n_entities: int,
    cap: int = 1024,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Group COO by `entity` into static-shape padded lists.

    Returns (idx [N,P] int32, val [N,P] f32, mask [N,P] f32) with
    P = min(max row length, cap), power-of-2-padded for stable XLA tiling.
    Rows longer than P keep their largest-|value| interactions (the most
    informative ones) — the static-shape answer to Spark's ragged rows.
    """
    order = np.lexsort((-np.abs(values), entity))
    e, o, v = entity[order], other[order], values[order]
    counts = np.bincount(e, minlength=n_entities)
    max_c = int(counts.max()) if counts.size else 1
    p = 1 << max(0, (min(max_c, cap) - 1)).bit_length()
    p = max(p, 1)
    rank = np.arange(len(e)) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts
    )
    keep = rank < p
    e, o, v, rank = e[keep], o[keep], v[keep], rank[keep]
    idx = np.zeros((n_entities, p), dtype=np.int32)
    val = np.zeros((n_entities, p), dtype=np.float32)
    mask = np.zeros((n_entities, p), dtype=np.float32)
    idx[e, rank] = o
    val[e, rank] = v
    mask[e, rank] = 1.0
    return idx, val, mask


# ---------------------------------------------------------------------------
# the jitted trainer
# ---------------------------------------------------------------------------

def _half_step(
    factors, gram_f, idx, val, mask, lam, alpha, implicit: bool, block: int,
    compute_dtype=jnp.float32,
):
    """One ALS half-iteration: solve every row's normal equations.

    factors: [M,K] fixed side; idx/val/mask: [N,P] padded lists over the
    solving side. Processes rows in `block`-sized chunks via lax.map so the
    [B,P,K] gather never materializes for the whole axis at once.

    compute_dtype=bfloat16 feeds the dominant einsum bf16 inputs with f32
    accumulation (MXU-native single pass instead of multi-pass f32); the
    [K,K] systems and the Cholesky solves stay f32 either way.
    """
    n, p = idx.shape
    k = factors.shape[1]
    eye = jnp.eye(k, dtype=jnp.float32)
    nb = n // block
    # bf16 inputs accumulate exactly in f32 on the MXU; f32 inputs keep
    # the multi-pass HIGHEST path (plain f32 einsum on TPU rounds inputs
    # to bf16 anyway, which would silently degrade the default)
    prec = (
        jax.lax.Precision.DEFAULT
        if compute_dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST
    )

    def one_block(args):
        bidx, bval, bmask = args
        yu = factors[bidx].astype(compute_dtype)  # [B,P,K] gather
        if implicit:
            # Hu et al.: A = Y'Y + Yu' diag(alpha.r) Yu + lam.I
            #            b = Yu' ((1 + alpha.r) . p),  p = 1 for observed
            w = alpha * bval * bmask
            a = (
                gram_f[None]
                + jnp.einsum("bpk,bp,bpl->bkl", yu, w.astype(compute_dtype), yu,
                             precision=prec,
                             preferred_element_type=jnp.float32)
                + lam * eye[None]
            )
            pref = (bval > 0).astype(jnp.float32) * bmask
            b = jnp.einsum("bpk,bp->bk", yu,
                           ((1.0 + w) * pref).astype(compute_dtype),
                           precision=prec,
                           preferred_element_type=jnp.float32)
        else:
            # ALS-WR: A = Yu'Yu + lam.n_u.I ; b = Yu' r
            a = jnp.einsum("bpk,bp,bpl->bkl", yu, bmask.astype(compute_dtype), yu,
                           precision=prec,
                           preferred_element_type=jnp.float32)
            n_u = bmask.sum(axis=1)
            a = a + (lam * jnp.maximum(n_u, 1.0))[:, None, None] * eye[None]
            b = jnp.einsum("bpk,bp->bk", yu, (bval * bmask).astype(compute_dtype),
                           precision=prec,
                           preferred_element_type=jnp.float32)
        chol = jnp.linalg.cholesky(a)
        # bf16-assembled normal equations can round a marginal system
        # indefinite (the MXU rounds einsum INPUTS to bf16; observed at
        # ML-25M scale: one failed factorization NaN-poisons gram() and
        # with it the whole next half-sweep). Retry non-finite rows with
        # trace-scaled jitter — the ALS analogue of the reference solver's
        # singularity guard (ops/solver.py; Solver.java ill-conditioned
        # check) — and zero whatever still fails: a zero row re-enters the
        # next half-sweep cleanly and is re-solved from scratch.
        ok = jnp.isfinite(chol).all(axis=(-2, -1), keepdims=True)
        jitter = (
            0.02 * jnp.trace(a, axis1=-2, axis2=-1) / k + 1e-6
        )[:, None, None]
        chol = jnp.where(
            ok, chol, jnp.linalg.cholesky(a + jitter * eye[None])
        )
        y = jax.scipy.linalg.solve_triangular(chol, b[..., None], lower=True)
        x = jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(chol, -1, -2), y, lower=False
        )[..., 0]
        x = jnp.where(jnp.isfinite(x).all(axis=-1, keepdims=True), x, 0.0)
        # rows with no interactions (all-pad) solve to ~0 already (b = 0)
        return x

    blocks = jax.lax.map(
        one_block,
        (
            idx.reshape(nb, block, p),
            val.reshape(nb, block, p),
            mask.reshape(nb, block, p),
        ),
    )
    return blocks.reshape(n, k)


@partial(
    jax.jit,
    static_argnames=("implicit", "iterations", "block", "compute_dtype"),
)
def als_train_jit(
    u_idx, u_val, u_mask, i_idx, i_val, i_mask, y0, lam, alpha,
    *, implicit: bool, iterations: int, block: int,
    compute_dtype: str = "float32",
):
    """Full ALS training loop as one compiled program (lax.scan over
    iterations). All shapes static; shard u_* over users and i_* over items
    on the mesh "data" axis and XLA threads the collectives through."""
    cdt = jnp.dtype(compute_dtype)

    def body(carry, _):
        _, y = carry
        x = _half_step(
            y, gram(y), u_idx, u_val, u_mask, lam, alpha, implicit, block,
            compute_dtype=cdt,
        )
        y_new = _half_step(
            x, gram(x), i_idx, i_val, i_mask, lam, alpha, implicit, block,
            compute_dtype=cdt,
        )
        # x rides in the carry, NOT a per-step scan output: stacking it
        # would multiply peak factor memory by the iteration count
        return (x, y_new), None

    x0 = jnp.zeros((u_idx.shape[0], y0.shape[1]), dtype=jnp.float32)
    (x_fin, y_fin), _ = jax.lax.scan(body, (x0, y0), None, length=iterations)
    return x_fin, y_fin


@dataclass
class ALSModelArrays:
    x: np.ndarray  # [n_users, K]
    y: np.ndarray  # [n_items, K]
    user_ids: list[str]
    item_ids: list[str]


def _finish_model(x, y, n_u: int, n_i: int, data) -> ALSModelArrays:
    """Trim padding and surface solver-guard diagnostics. An all-zero
    factor row is almost always the _half_step singularity guard zeroing an
    unsolvable system in the final sweep (explicit rows whose aggregated
    ratings are all exactly zero also land here) — worth a warning, never
    worth a NaN."""
    x = np.asarray(x)[:n_u]
    y = np.asarray(y)[:n_i]
    zeroed = int((~x.any(axis=1)).sum() + (~y.any(axis=1)).sum())
    if zeroed:
        log.warning(
            "ALS: %d all-zero factor rows (singularity guard, or all-zero "
            "explicit ratings) of %d users + %d items", zeroed, n_u, n_i,
        )
    return ALSModelArrays(x, y, data.user_ids, data.item_ids)


def _record_train_dispatch(
    args, train_flops, train_s, n_u, n_i, n_u_pad, n_i_pad, features,
    compute_dtype,
) -> None:
    """Report one train-scan execution's cost (FLOPs, approximate bytes
    uploaded + factor tables back, wall-clock, row-padding occupancy) to
    the runtime perf accounting — the train-side twin of the serving
    batcher's per-dispatch records. Never lets accounting break training."""
    try:
        from oryx_tpu.common.perfstats import get_perfstats
        from oryx_tpu.ops.flops import device_peak_flops

        dtype = (
            "bfloat16" if str(compute_dtype).startswith("bf") else "float32"
        )
        ps = get_perfstats()
        # the backend is live here (the scan just ran), so resolving the
        # chip peak is safe — ensure_peak caches the one resolution
        ps.ensure_peak("train", lambda: device_peak_flops(dtype))
        bytes_moved = float(
            sum(
                getattr(a, "nbytes", 0)
                for bucket in args[0] + args[1]
                for a in bucket
            )
            + getattr(args[2], "nbytes", 0)
            + (n_u_pad + n_i_pad) * features * 4
        )
        ps.record_dispatch(
            "train",
            flops=train_flops, bytes_moved=bytes_moved, wall_s=train_s,
            rows=n_u + n_i, padded_rows=n_u_pad + n_i_pad,
            valid_rows=n_u + n_i, capacity_rows=n_u_pad + n_i_pad,
        )
    except Exception:  # pragma: no cover - accounting must not break builds
        pass


def train_als(
    data: InteractionData,
    features: int = 10,
    lam: float = 0.001,
    alpha: float = 1.0,
    iterations: int = 10,
    implicit: bool = True,
    mesh=None,
    cap: int = 1024,
    block: int = 1024,
    seed_key=None,
    compute_dtype: str = "float32",
    resume_y: np.ndarray | None = None,
    timings: dict | None = None,
    donate_y0: bool = False,
    shard_mesh=None,
) -> ALSModelArrays:
    """Train ALS factor matrices. If a mesh is given, the padded lists and
    factor tables are sharded over its "data" axis and the whole scan runs
    SPMD; a mesh with a non-trivial "model" axis dispatches to the
    tensor-parallel trainer (X sharded by user, Y by item — see
    train_als_tp); single-device otherwise. compute_dtype="bfloat16" feeds
    the normal-equation einsums bf16 inputs with f32 accumulation (the
    MXU-native fast path; solves stay f32). resume_y replaces the random
    item-factor init with a [n_items, features] matrix (mid-build
    checkpoint resume: the per-sweep carry is fully determined by Y).

    shard_mesh (mutually exclusive with mesh): run the BUCKETED scan —
    the trainer incremental generations and warm starts use — under pjit
    with the item-factor table sharded by row over the mesh's "model"
    axis (parallel/mesh.model_mesh) and the bucketed lists replicated;
    XLA inserts the gather/scatter collectives. This is the pod-scale
    path for factor tables larger than one chip's HBM that still wants
    the bucketed-width work savings and the donated Y carry, and it
    composes with the warm-start early stop unchanged (train_als_warm
    threads it through).

    timings (single-device path only): pass a dict to receive a
    {"lists_s", "compile_s", "train_s"} breakdown — the XLA compile is
    separated from compute via AOT lower/compile, so benchmarks report
    one-time compilation apart from the per-build cost it amortizes into.
    """
    if mesh is not None and shard_mesh is not None:
        # loud, not silent: a caller combining the two would get
        # mesh-only training with the shard layout dropped — exactly the
        # capability loss sharding exists to prevent (oryxlint's
        # device-placement rule flags such call sites before runtime)
        raise ValueError("train_als: mesh and shard_mesh are mutually exclusive")
    if mesh is not None:
        from oryx_tpu.parallel.mesh import MODEL_AXIS

        if MODEL_AXIS in mesh.shape and mesh.shape[MODEL_AXIS] > 1:
            return train_als_tp(
                data, mesh, features=features, lam=lam, alpha=alpha,
                iterations=iterations, implicit=implicit, cap=cap,
                block=block, seed_key=seed_key, compute_dtype=compute_dtype,
                resume_y=resume_y,
            )
    n_u, n_i = data.n_users, data.n_items
    if n_u == 0 or n_i == 0 or len(data.values) == 0:
        # covers both no-input and everything-deleted-by-NaN-markers
        raise ValueError("empty interaction data")

    if mesh is None:
        import time as _time

        t_mark = _time.perf_counter()
        # single-device: bucketed lists — work scales with real row
        # lengths instead of the heaviest row's power-of-two padding.
        # Row counts round to a 1024 unit so retrains on slowly growing
        # data keep hitting the jit cache.
        unit = 1024
        shard_n = 1
        if shard_mesh is not None:
            from oryx_tpu.parallel.mesh import MODEL_AXIS as _M

            shard_n = int(shard_mesh.shape[_M])
            if shard_n > 1 and unit % shard_n:
                # the sharded row axis must divide evenly across the
                # model axis; non-pow2 shard counts grow the rounding
                # unit instead of failing the device_put
                unit *= shard_n
        u_buckets, blocks_u = _cached_lists(
            "u_buckets", data, (cap, block, unit),
            lambda: build_bucketed_lists(
                data.users, data.items, data.values, n_u, cap,
                block=block, unit=unit,
            ),
        )
        i_buckets, blocks_i = _cached_lists(
            "i_buckets", data, (cap, block, unit),
            lambda: build_bucketed_lists(
                data.items, data.users, data.values, n_i, cap,
                block=block, unit=unit,
            ),
        )
        n_u_pad = -(-n_u // unit) * unit
        n_i_pad = -(-n_i // unit) * unit
        if resume_y is not None:
            y0 = jnp.asarray(_row_pad(np.asarray(resume_y, dtype=np.float32), n_i_pad))
        else:
            key = seed_key if seed_key is not None else RandomManager.get_key()
            # padding rows must be ZERO or phantom items inflate gram(Y)
            # in the first half-iteration
            y0 = (
                jax.random.normal(key, (n_i_pad, features), dtype=jnp.float32) * 0.1
                + 1.0 / math.sqrt(features)
            )
            y0 = y0 * (jnp.arange(n_i_pad) < n_i)[:, None]
        put = jnp.asarray
        if shard_n > 1:
            # pjit-sharded bucketed scan: the item-factor table (the Y
            # carry, donated on warm restarts) lives row-sharded over the
            # mesh's "model" axis; the bucketed lists replicate, and XLA
            # threads the gather/solve/scatter collectives through the
            # SAME compiled scan the single-device path runs
            from oryx_tpu.parallel.mesh import model_sharding, replicated

            rep = replicated(shard_mesh)
            put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
            y0 = jax.device_put(y0, model_sharding(shard_mesh, 2))
        args = (
            tuple(tuple(put(a) for a in b) for b in u_buckets),
            tuple(tuple(put(a) for a in b) for b in i_buckets),
            y0, jnp.float32(lam), jnp.float32(alpha),
        )
        kwargs = dict(
            implicit=implicit, iterations=iterations,
            blocks_u=tuple(blocks_u), blocks_i=tuple(blocks_i), n_u=n_u_pad,
            compute_dtype=compute_dtype,
        )
        # analytic FLOPs of the whole build (dominant einsum terms only —
        # ops/flops.py): benchmarks divide by train_s and the chip peak
        # for an honest MFU figure, and the runtime perf accounting
        # (common/perfstats.py) records the same number per scan call
        from oryx_tpu.ops.flops import als_halfstep_flops

        flops_half_u = sum(
            als_halfstep_flops(b[1].shape[0], b[1].shape[1], features, 0)
            for b in u_buckets
        ) + 2.0 * n_i_pad * features * features
        flops_half_i = sum(
            als_halfstep_flops(b[1].shape[0], b[1].shape[1], features, 0)
            for b in i_buckets
        ) + 2.0 * n_u_pad * features * features
        train_flops = iterations * (flops_half_u + flops_half_i)
        if timings is None:
            # donation is a no-op (with a warning) on CPU; only take the
            # donated program where buffer reuse actually exists
            fn = (
                als_train_bucketed_jit_donated
                if donate_y0 and jax.default_backend() != "cpu"
                else als_train_bucketed_jit
            )
            t_exec = _time.perf_counter()
            x, y = jax.block_until_ready(fn(*args, **kwargs))
            train_s = _time.perf_counter() - t_exec
        else:
            # AOT lower/compile so the one-time XLA compile is measured
            # apart from the compute it amortizes into
            timings["lists_s"] = _time.perf_counter() - t_mark
            timings["train_flops"] = train_flops
            t_mark = _time.perf_counter()
            compiled = als_train_bucketed_jit.lower(*args, **kwargs).compile()
            timings["compile_s"] = _time.perf_counter() - t_mark
            t_mark = _time.perf_counter()
            x, y = jax.block_until_ready(compiled(*args))
            train_s = timings["train_s"] = _time.perf_counter() - t_mark
        _record_train_dispatch(
            args, train_flops, train_s, n_u, n_i, n_u_pad, n_i_pad,
            features, compute_dtype,
        )
        return _finish_model(x, y, n_u, n_i, data)

    # mesh path: one global width, rows padded to a common multiple of the
    # chunk block and the mesh "data" axis so lax.map reshapes and shard
    # layouts both divide evenly
    from oryx_tpu.parallel.mesh import DATA_AXIS, shard_array

    u_lists = _cached_lists(
        "u_lists", data, (cap,),
        lambda: build_padded_lists(data.users, data.items, data.values, n_u, cap),
    )
    i_lists = _cached_lists(
        "i_lists", data, (cap,),
        lambda: build_padded_lists(data.items, data.users, data.values, n_i, cap),
    )

    mesh_n = mesh.shape[DATA_AXIS]
    blk = min(block, 1 << max(0, max(n_u, n_i) - 1).bit_length())
    unit = max(blk, mesh_n) if blk % mesh_n == 0 or mesh_n % blk == 0 else blk * mesh_n
    n_u_pad = -(-n_u // unit) * unit
    n_i_pad = -(-n_i // unit) * unit
    u_idx, u_val, u_mask = (_row_pad(a, n_u_pad) for a in u_lists)
    i_idx, i_val, i_mask = (_row_pad(a, n_i_pad) for a in i_lists)

    if resume_y is not None:
        y0 = jnp.asarray(_row_pad(np.asarray(resume_y, dtype=np.float32), n_i_pad))
    else:
        key = seed_key if seed_key is not None else RandomManager.get_key()
        # small random factors around 1/sqrt(K), the usual ALS init scale;
        # padding rows must be ZERO or phantom items inflate gram(Y) in
        # the first half-iteration
        y0 = (
            jax.random.normal(key, (n_i_pad, features), dtype=jnp.float32) * 0.1
            + 1.0 / math.sqrt(features)
        )
        y0 = y0 * (jnp.arange(n_i_pad) < n_i)[:, None]

    args = [
        shard_array(np.asarray(a), mesh)
        for a in (u_idx, u_val, u_mask, i_idx, i_val, i_mask, y0)
    ]

    x, y = als_train_jit(
        *args,
        jnp.float32(lam),
        jnp.float32(alpha),
        implicit=implicit,
        iterations=iterations,
        block=blk,
        compute_dtype=compute_dtype,
    )
    return _finish_model(x, y, n_u, n_i, data)


def train_als_checkpointed(
    data: InteractionData,
    checkpoint_dir,
    checkpoint_every: int,
    features: int = 10,
    lam: float = 0.001,
    alpha: float = 1.0,
    iterations: int = 10,
    implicit: bool = True,
    mesh=None,
    cap: int = 1024,
    block: int = 1024,
    seed_key=None,
    compute_dtype: str = "float32",
    shard_mesh=None,
) -> ALSModelArrays:
    """train_als with mid-build checkpoints every `checkpoint_every`
    sweeps: a preempted/killed build resumes from the last checkpoint
    instead of restarting, and the resumed run equals the uninterrupted
    one exactly (the per-sweep carry is fully determined by Y, which is
    what gets saved). The spirit of the reference's ALS
    checkpointInterval(5) (ALSUpdate.java:144 breaks RDD lineage every 5
    iterations), re-aimed at the failure mode long TPU builds actually
    have. Checkpoints are atomic (tmp + rename), fingerprinted against
    the exact training configuration, and removed on success.
    """
    import json as _json
    import os
    from pathlib import Path

    if iterations < 1:
        raise ValueError("iterations must be >= 1")
    ck_dir = Path(checkpoint_dir)
    ck_dir.mkdir(parents=True, exist_ok=True)
    ck = ck_dir / "als-train.ckpt.npz"
    import zlib

    # sampled content hash: time-decayed re-aggregation after a crash can
    # produce the same SHAPES with different values; a stale checkpoint
    # must not be accepted against different data
    sample = slice(None, None, max(1, len(data.values) // 262_144))
    data_crc = zlib.crc32(np.ascontiguousarray(data.values[sample]).tobytes())
    data_crc = zlib.crc32(np.ascontiguousarray(data.users[sample]).tobytes(), data_crc)
    data_crc = zlib.crc32(np.ascontiguousarray(data.items[sample]).tobytes(), data_crc)
    fingerprint = _json.dumps(
        {
            "n_users": data.n_users,
            "n_items": data.n_items,
            "nnz": int(len(data.values)),
            "data_crc": data_crc,
            "features": features,
            "lam": float(lam),
            "alpha": float(alpha),
            "implicit": implicit,
            "compute_dtype": compute_dtype,
            "iterations": iterations,
        },
        sort_keys=True,
    )

    done = 0
    resume_y = None
    if ck.exists():
        try:
            with np.load(ck, allow_pickle=False) as z:
                if str(z["fingerprint"]) == fingerprint:
                    done = int(z["done"])
                    resume_y = z["y"]
                    log.info("resuming ALS build from checkpoint: %d/%d sweeps done",
                             done, iterations)
        except Exception:  # noqa: BLE001 - a torn checkpoint means restart
            log.warning("ignoring unreadable ALS checkpoint %s", ck)

    kwargs = dict(
        features=features, lam=lam, alpha=alpha, implicit=implicit,
        mesh=mesh, cap=cap, block=block, compute_dtype=compute_dtype,
        shard_mesh=shard_mesh,
    )
    # checkpoints are only written mid-build (done < iterations) and the
    # fingerprint pins `iterations`, so done < iterations always holds
    # here; clamp defensively anyway — X is derived from Y, so at least
    # one sweep must run
    done = min(done, iterations - 1)
    model = None
    while done < iterations:
        chunk = min(max(1, checkpoint_every), iterations - done)
        model = train_als(
            data, iterations=chunk, seed_key=seed_key,
            resume_y=resume_y, **kwargs,
        )
        done += chunk
        resume_y = model.y
        if done < iterations:
            tmp = str(ck) + ".tmp"
            np.savez(tmp, y=model.y, done=done, fingerprint=fingerprint)
            # np.savez appends .npz to names without it
            os.replace(tmp if os.path.exists(tmp) else tmp + ".npz", ck)
    if ck.exists():
        ck.unlink()
    return model


def train_als_warm(
    data: InteractionData,
    features: int = 10,
    lam: float = 0.001,
    alpha: float = 1.0,
    iterations: int = 10,
    implicit: bool = True,
    mesh=None,
    cap: int = 1024,
    block: int = 1024,
    seed_key=None,
    compute_dtype: str = "float32",
    resume_y: np.ndarray | None = None,
    tol: float = 0.0,
    min_iterations: int = 1,
    check_every: int = 2,
    shard_mesh=None,
) -> tuple[ALSModelArrays, int]:
    """train_als with a convergence-based early stop for warm starts.

    Runs `check_every`-sweep chunks (each re-enters the SAME compiled
    program — the chunk size, not the total, is the jit-cache key, so
    steady-state generations never recompile) and stops once the model's
    PREDICTIONS stop moving: the relative change of x_u·y_i over a fixed
    deterministic sample of observed interactions drops below `tol`.
    Predictions, not factor norms — an ALS factor pair keeps drifting
    along near-degenerate directions (scale/rotation trades between X
    and Y) long after the scores it produces have settled, so a
    Frobenius-on-Y test either never fires or needs a uselessly loose
    threshold. Respects the `min_iterations` floor. A warm resume_y from
    the previous generation typically converges in a fraction of the
    cold iteration count; the per-chunk Y carry is donated to the
    trainer so the chunked loop holds one factor table, not two.
    Returns (model, sweeps actually run).

    tol <= 0 disables the early stop (one full-length train_als call).
    """
    if tol <= 0 or iterations <= max(1, check_every):
        m = train_als(
            data, features=features, lam=lam, alpha=alpha,
            iterations=iterations, implicit=implicit, mesh=mesh, cap=cap,
            block=block, seed_key=seed_key, compute_dtype=compute_dtype,
            resume_y=resume_y, shard_mesh=shard_mesh,
        )
        return m, iterations
    check_every = max(1, check_every)
    # deterministic stride sample of observed pairs (same idiom as the
    # checkpoint fingerprint): cheap, stable across chunks, and scored
    # where the model is actually used
    nnz = len(data.values)
    samp = slice(None, None, max(1, nnz // 4096))
    su, si = data.users[samp], data.items[samp]
    done = 0
    prev_y = resume_y
    prev_pred = None
    model = None
    while done < iterations:
        chunk = min(check_every, iterations - done)
        model = train_als(
            data, features=features, lam=lam, alpha=alpha,
            iterations=chunk, implicit=implicit, mesh=mesh, cap=cap,
            block=block, seed_key=seed_key, compute_dtype=compute_dtype,
            resume_y=prev_y, donate_y0=prev_y is not None,
            shard_mesh=shard_mesh,
        )
        done += chunk
        pred = (model.x[su] * model.y[si]).sum(axis=1)
        if prev_pred is not None:
            denom = float(np.linalg.norm(prev_pred)) or 1.0
            rel = float(np.linalg.norm(pred - prev_pred)) / denom
            if done >= min_iterations and rel < tol:
                log.info(
                    "ALS early stop at sweep %d/%d (relative prediction "
                    "change %.2e < tol %.2e)", done, iterations, rel, tol,
                )
                break
        prev_y, prev_pred = model.y, pred
    return model, done


def _row_pad(a: np.ndarray, n: int) -> np.ndarray:
    if a.shape[0] == n:
        return a
    return np.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


# ---------------------------------------------------------------------------
# bucketed lists: rows grouped by interaction count so light rows don't pay
# the heaviest row's padding
# ---------------------------------------------------------------------------

_prepared_lists_cache: dict = {}

# Distinct (data object, list kind, params) entries kept at once. Eviction
# normally rides weakref.finalize when the data object dies; the cap is
# the backstop for non-weakrefable data objects (finalize refuses those)
# and for long-lived processes cycling many live datasets — without it a
# hyperparameter sweep over fresh InteractionData objects grows the cache
# (and the multi-GB padded lists inside it) without bound.
_PREPARED_LISTS_CAP = 16


def _cached_lists(tag: str, data, params: tuple, build):
    """Memoize padded/bucketed list construction per InteractionData object
    (and scalar build parameters). The checkpointed trainer re-enters
    train_als once per chunk with the SAME data object; rebuilding the
    lists each chunk would repeat minutes of host work on large builds.
    Entries die with the data object via weakref.finalize, or with the
    oldest-entry cap for objects finalize can't track."""
    key = (id(data), tag, params)
    hit = _prepared_lists_cache.get(key)
    if hit is not None:
        return hit[0]
    out = build()
    try:
        weakref.ref(data)
        # weakref-able: one finalizer per data object purges all its
        # entries the moment it is collected
        if not any(k[0] == id(data) for k in _prepared_lists_cache):
            weakref.finalize(data, _purge_prepared, id(data))
        pin = None
    except TypeError:
        # data isn't weakref-able (e.g. a slotted/plain-tuple stand-in in
        # tests): cache anyway — but PIN the object in EVERY entry.
        # Untracked, id(data) could be reused by a new object at the same
        # address after this one dies, silently serving another dataset's
        # lists; per-entry pins survive cap eviction of a sibling entry,
        # and the cap bounds what the pins can keep alive.
        pin = data
    while len(_prepared_lists_cache) >= _PREPARED_LISTS_CAP:
        _prepared_lists_cache.pop(next(iter(_prepared_lists_cache)))
    _prepared_lists_cache[key] = (out, pin)
    return out


def _purge_prepared(obj_id: int) -> None:
    for k in [k for k in _prepared_lists_cache if k[0] == obj_id]:
        _prepared_lists_cache.pop(k, None)


def build_bucketed_lists(
    entity: np.ndarray,
    other: np.ndarray,
    values: np.ndarray,
    n_entities: int,
    cap: int = 1024,
    edges: tuple[int, ...] = (128, 512, 1024),
    min_rows: int = 4096,
    block: int = 1024,
    unit: int = 1024,
) -> tuple[list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]], list[int]]:
    """Like build_padded_lists, but rows are grouped into width buckets.

    One global P pads every row to the heaviest row's next power of two —
    at MovieLens-25M shape the mean row is ~150 interactions against
    P=1024, so >6x of the gather traffic and normal-equation FLOPs are
    padding. Here each row lands in the smallest bucket width that holds
    it (capped like before; largest-|value| kept on truncation), so the
    einsum work is proportional to the data, not to the tail.

    Returns (buckets, blocks): per bucket (rows [S] int32 into the entity
    axis, idx [S,P], val [S,P], mask [S,P]) with S padded to a multiple of
    its lax.map block AND of `unit` (so the jit cache keys on rounded
    sizes, not exact row counts; padding rows carry id n_entities —
    scattered with mode='drop'); blocks holds the per-bucket block size,
    capped at the caller's `block` working-set bound. Buckets with fewer
    than min_rows rows merge upward to bound compile variants, and each
    bucket's width clips to its own max row length so merged-up small
    datasets never pad past their data.
    """
    edges_arr = [e for e in edges if e < cap] + [cap]
    counts = np.bincount(entity, minlength=n_entities)
    cape = np.minimum(counts, cap)
    b_of = np.searchsorted(edges_arr, cape)  # smallest edge >= cape
    sizes = np.bincount(b_of, minlength=len(edges_arr))
    for j in range(len(edges_arr) - 1):  # merge small buckets upward
        if 0 < sizes[j] < min_rows:
            sizes[j + 1] += sizes[j]
            sizes[j] = 0
            b_of[b_of == j] = j + 1

    # rank interactions within each row, largest |value| first (truncation
    # keeps the most informative entries — same policy as the flat builder)
    order = np.lexsort((-np.abs(values), entity))
    e, o, v = entity[order], other[order], np.asarray(values)[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(e)) - np.repeat(starts, counts)
    pe = np.asarray(edges_arr)[b_of]
    keep = rank < pe[e]
    e, o, v, rank = e[keep], o[keep], v[keep], rank[keep]

    buckets = []
    blocks = []
    for j, p_edge in enumerate(edges_arr):
        rows = np.nonzero(b_of == j)[0]
        if rows.size == 0:
            continue
        # clip the width to this bucket's real max row length: an upward
        # merge of a small dataset must not pad everyone to the cap edge
        p_need = int(cape[rows].max()) if rows.size else 1
        p = 1 << max(0, min(int(p_edge), max(p_need, 1)) - 1).bit_length()
        blk = min(block, max(64, (1 << 20) // p))
        blk = 1 << (blk.bit_length() - 1)  # pow2 so it divides the unit
        u = max(blk, unit)  # pow2 >= blk -> multiples of u divide by blk
        s = -(-rows.size // u) * u
        blk = min(blk, s)
        pos_of = np.full(n_entities, -1, dtype=np.int64)
        pos_of[rows] = np.arange(rows.size)
        m = b_of[e] == j
        idx = np.zeros((s, p), dtype=np.int32)
        val = np.zeros((s, p), dtype=np.float32)
        mask = np.zeros((s, p), dtype=np.float32)
        idx[pos_of[e[m]], rank[m]] = o[m]
        val[pos_of[e[m]], rank[m]] = v[m]
        mask[pos_of[e[m]], rank[m]] = 1.0
        rows_padded = np.full(s, n_entities, dtype=np.int32)
        rows_padded[: rows.size] = rows
        buckets.append((rows_padded, idx, val, mask))
        blocks.append(blk)
    return buckets, blocks


def _half_step_buckets(
    factors, gram_f, buckets, lam, alpha, implicit: bool, blocks, n_out: int,
    compute_dtype=jnp.float32,
):
    """Bucketed half-iteration: solve each width class with its own padded
    shape, scatter results into the [n_out, K] factor table."""
    k = factors.shape[1]
    x = jnp.zeros((n_out, k), dtype=jnp.float32)
    for (rows, idx, val, mask), blk in zip(buckets, blocks):
        sol = _half_step(
            factors, gram_f, idx, val, mask, lam, alpha, implicit, blk,
            compute_dtype=compute_dtype,
        )
        x = x.at[rows].set(sol, mode="drop")  # padding rows carry id n_out
    return x


def _als_train_bucketed(
    u_buckets, i_buckets, y0, lam, alpha,
    *, implicit: bool, iterations: int, blocks_u, blocks_i, n_u: int,
    compute_dtype: str = "float32",
):
    """Bucketed ALS training loop (single-device / data-replicated). Same
    math as als_train_jit — the buckets partition exactly the same padded
    lists — with work proportional to real row lengths."""
    cdt = jnp.dtype(compute_dtype)

    def body(carry, _):
        _x_prev, y = carry
        x = _half_step_buckets(
            y, gram(y), u_buckets, lam, alpha, implicit, blocks_u, n_u,
            compute_dtype=cdt,
        )
        y_new = _half_step_buckets(
            x, gram(x), i_buckets, lam, alpha, implicit, blocks_i, y.shape[0],
            compute_dtype=cdt,
        )
        return (x, y_new), None

    x0 = jnp.zeros((n_u, y0.shape[1]), dtype=jnp.float32)
    (x_fin, y_fin), _ = jax.lax.scan(body, (x0, y0), None, length=iterations)
    return x_fin, y_fin


_BUCKETED_STATICS = (
    "implicit", "iterations", "blocks_u", "blocks_i", "n_u", "compute_dtype"
)

als_train_bucketed_jit = partial(jax.jit, static_argnames=_BUCKETED_STATICS)(
    _als_train_bucketed
)

# warm-start variant: the incoming Y carry is DONATED so XLA reuses its
# HBM buffer for the outgoing factors — the early-stop loop re-enters
# this program once per convergence check, and without donation every
# chunk would briefly hold two full item-factor tables
als_train_bucketed_jit_donated = partial(
    jax.jit, static_argnames=_BUCKETED_STATICS, donate_argnums=(2,)
)(_als_train_bucketed)


# ---------------------------------------------------------------------------
# tensor-parallel trainer: factor tables sharded over the mesh
# ---------------------------------------------------------------------------
#
# The data-parallel trainer above replicates both factor tables on every
# device; factor tables bigger than one chip's HBM need true model sharding.
# Design (the TPU-native scaling of the reference's partition-summed Gram,
# PartitionedFeatureVectors.java:209-213):
#
#   X rows sharded over "data" (dp), Y rows sharded over "model" (tp).
#   User half-step: each (d, m) device computes the partial normal-equation
#   terms A_u, b_u for ITS user rows from ITS resident Y block only (masked
#   local gather — items outside the block contribute zero), then A/b are
#   psum'd over "model". Every model replica solves the same [K,K] systems
#   (redundant solves, negligible next to the einsum), so X stays sharded
#   over "data" and replicated over "model" with no extra collective.
#   Item half-step is symmetric with the axes swapped (partials psum'd over
#   "data"). Y is NEVER materialized whole on any device, and the einsum
#   FLOPs split tp ways (user step) / dp ways (item step).

def _half_step_tp(
    factors_local, gram_full, base, idx, val, mask, lam, alpha,
    implicit: bool, block: int, other_axis: str, compute_dtype=jnp.float32,
):
    """One TP half-iteration inside shard_map.

    factors_local: [M_local, K] this device's block of the fixed side.
    base: global row index of factors_local[0].
    idx/val/mask: [B_local, P] padded lists for this device's solving rows,
    with GLOBAL indices into the fixed side.
    """
    n, p = idx.shape
    m_local, k = factors_local.shape
    eye = jnp.eye(k, dtype=jnp.float32)
    nb = n // block
    prec = (
        jax.lax.Precision.DEFAULT
        if compute_dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST
    )

    def one_block(args):
        bidx, bval, bmask = args
        rel = bidx - base
        inblk = ((rel >= 0) & (rel < m_local)).astype(jnp.float32) * bmask
        yu = factors_local[jnp.clip(rel, 0, m_local - 1)].astype(compute_dtype)
        if implicit:
            w = alpha * bval * inblk
            a_part = jnp.einsum(
                "bpk,bp,bpl->bkl", yu, w.astype(compute_dtype), yu,
                precision=prec, preferred_element_type=jnp.float32,
            )
            pref = (bval > 0).astype(jnp.float32) * inblk
            b_part = jnp.einsum(
                "bpk,bp->bk", yu, ((1.0 + w) * pref).astype(compute_dtype),
                precision=prec, preferred_element_type=jnp.float32,
            )
        else:
            a_part = jnp.einsum(
                "bpk,bp,bpl->bkl", yu, inblk.astype(compute_dtype), yu,
                precision=prec, preferred_element_type=jnp.float32,
            )
            b_part = jnp.einsum(
                "bpk,bp->bk", yu, (bval * inblk).astype(compute_dtype),
                precision=prec, preferred_element_type=jnp.float32,
            )
        # combine partial normal equations across the fixed side's shards
        a_part = jax.lax.psum(a_part, other_axis)
        b_part = jax.lax.psum(b_part, other_axis)
        if implicit:
            a = gram_full[None] + a_part + lam * eye[None]
        else:
            # n_u from the FULL list (replicated across other_axis), so the
            # ALS-WR regularization matches the unsharded trainer exactly
            n_u = bmask.sum(axis=1)
            a = a_part + (lam * jnp.maximum(n_u, 1.0))[:, None, None] * eye[None]
        chol = jnp.linalg.cholesky(a)
        yb = jax.scipy.linalg.solve_triangular(chol, b_part[..., None], lower=True)
        return jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(chol, -1, -2), yb, lower=False
        )[..., 0]

    blocks = jax.lax.map(
        one_block,
        (
            idx.reshape(nb, block, p),
            val.reshape(nb, block, p),
            mask.reshape(nb, block, p),
        ),
    )
    return blocks.reshape(n, k)


@lru_cache(maxsize=16)
def als_train_tp_jit(
    mesh, *, implicit: bool, iterations: int, block: int,
    compute_dtype: str = "float32",
):
    """Build the jitted tensor-parallel training step over `mesh` (cached
    per (mesh, statics) — the batch layer retrains every generation and
    must hit the jit cache, not recompile).

    Inputs (global shapes): u_* [N_u, P] with N_u % (dp*block) == 0,
    i_* [N_i, P] with N_i % (tp*block) == 0, y0 [N_i, K]. Returns (x, y)
    with x sharded over "data" rows and y over "model" rows.
    """
    from jax.sharding import PartitionSpec as P
    from oryx_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    cdt = jnp.dtype(compute_dtype)

    def body(u_idx, u_val, u_mask, i_idx, i_val, i_mask, y0, lam, alpha):
        m_i_local = y0.shape[0]  # N_i / tp
        n_u_local = u_idx.shape[0]  # N_u / dp
        y_base = jax.lax.axis_index(MODEL_AXIS) * m_i_local
        x_base = jax.lax.axis_index(DATA_AXIS) * n_u_local

        def one_iter(carry, _):
            _, y_local = carry
            gram_y = jax.lax.psum(gram(y_local), MODEL_AXIS)
            x_local = _half_step_tp(
                y_local, gram_y, y_base, u_idx, u_val, u_mask,
                lam, alpha, implicit, block, MODEL_AXIS, compute_dtype=cdt,
            )
            gram_x = jax.lax.psum(gram(x_local), DATA_AXIS)
            y_local = _half_step_tp(
                x_local, gram_x, x_base, i_idx, i_val, i_mask,
                lam, alpha, implicit, block, DATA_AXIS, compute_dtype=cdt,
            )
            return (x_local, y_local), None

        x0 = jnp.zeros((n_u_local, y0.shape[1]), dtype=jnp.float32)
        # mark the zero-filled carry as device-varying over "data" so its
        # type matches the per-shard x the loop produces (shard_map VMA)
        x0 = jax.lax.pcast(x0, (DATA_AXIS,), to="varying")
        (x_fin, y_fin), _ = jax.lax.scan(
            one_iter, (x0, y0), None, length=iterations
        )
        return x_fin, y_fin

    row_d = P(DATA_AXIS, None)
    row_m = P(MODEL_AXIS, None)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(row_d, row_d, row_d, row_m, row_m, row_m, row_m, P(), P()),
            out_specs=(row_d, row_m),
            check_vma=False,
        )
    )


def train_als_tp(
    data: InteractionData,
    mesh,
    features: int = 10,
    lam: float = 0.001,
    alpha: float = 1.0,
    iterations: int = 10,
    implicit: bool = True,
    cap: int = 1024,
    block: int = 1024,
    seed_key=None,
    compute_dtype: str = "float32",
    resume_y: np.ndarray | None = None,
) -> ALSModelArrays:
    """Tensor-parallel train_als: X sharded by user over "data", Y by item
    over "model"; neither factor table is ever whole on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from oryx_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    n_u, n_i = data.n_users, data.n_items
    if n_u == 0 or n_i == 0 or len(data.values) == 0:
        raise ValueError("empty interaction data")
    dp, tp = mesh.shape[DATA_AXIS], mesh.shape[MODEL_AXIS]

    u_lists = _cached_lists(
        "u_lists", data, (cap,),
        lambda: build_padded_lists(data.users, data.items, data.values, n_u, cap),
    )
    i_lists = _cached_lists(
        "i_lists", data, (cap,),
        lambda: build_padded_lists(data.items, data.users, data.values, n_i, cap),
    )

    # local row counts must divide the lax.map block: shrink the block to
    # the local shard size when shards are small
    blk_u = min(block, 1 << max(0, (max(1, n_u // dp)) - 1).bit_length())
    blk_i = min(block, 1 << max(0, (max(1, n_i // tp)) - 1).bit_length())
    blk = min(blk_u, blk_i)
    n_u_pad = -(-n_u // (dp * blk)) * (dp * blk)
    n_i_pad = -(-n_i // (tp * blk)) * (tp * blk)
    u_idx, u_val, u_mask = (_row_pad(a, n_u_pad) for a in u_lists)
    i_idx, i_val, i_mask = (_row_pad(a, n_i_pad) for a in i_lists)

    if resume_y is not None:
        y0 = jnp.asarray(_row_pad(np.asarray(resume_y, dtype=np.float32), n_i_pad))
    else:
        key = seed_key if seed_key is not None else RandomManager.get_key()
        if jax.process_count() > 1 and seed_key is None:
            from oryx_tpu.parallel.submesh import current_candidate_mesh

            if current_candidate_mesh() is None:
                # every host must init the SAME y0: its sharding replicates
                # along the cross-host data axis, and per-process urandom-
                # seeded keys would stitch divergent replicas into a
                # silently corrupt model
                from jax.experimental import multihost_utils

                key = jax.random.wrap_key_data(
                    multihost_utils.broadcast_one_to_all(jax.random.key_data(key))
                )
            # else: partitioned pod candidate search — the mesh spans only
            # THIS group's processes, so the pod-wide broadcast would
            # block on groups busy training other candidates. Group-wide
            # key agreement comes from the per-candidate deterministic
            # seed MLUpdate installs before every pod build.
        y0 = (
            jax.random.normal(key, (n_i_pad, features), dtype=jnp.float32) * 0.1
            + 1.0 / math.sqrt(features)
        )
        y0 = y0 * (jnp.arange(n_i_pad) < n_i)[:, None]

    row_d = NamedSharding(mesh, P(DATA_AXIS, None))
    row_m = NamedSharding(mesh, P(MODEL_AXIS, None))
    # spanning-THIS-mesh, not process_count: during a partitioned pod
    # candidate search the mesh covers only this group's processes, and a
    # fully-local sub-mesh must not enter pod-WIDE collectives — two groups'
    # process_allgathers would pair up and stitch different candidates'
    # factors into one corrupt model
    multihost = len({d.process_index for d in mesh.devices.ravel()}) > 1

    def put(a, s):
        # single-process: plain device_put. Multi-host: every process holds
        # the same full host array (the bus delivers the same generation to
        # each), so each process hands jax just its addressable shards.
        if not multihost:
            return jax.device_put(jnp.asarray(a), s)
        a = np.asarray(a)
        return jax.make_array_from_callback(a.shape, s, lambda idx: a[idx])

    step = als_train_tp_jit(
        mesh, implicit=implicit, iterations=iterations, block=blk,
        compute_dtype=compute_dtype,
    )
    x, y = step(
        put(u_idx, row_d), put(u_val, row_d), put(u_mask, row_d),
        put(i_idx, row_m), put(i_val, row_m), put(i_mask, row_m),
        put(y0, row_m), jnp.float32(lam), jnp.float32(alpha),
    )
    if multihost:
        # factor tables come back to every host (each publishes/serves the
        # whole model, like every reference layer holds the full model).
        # Gather WITHIN the mesh — an XLA all-gather over exactly the
        # mesh's devices — never a pod-wide process_allgather: during a
        # partitioned candidate search other process groups are busy
        # training different candidates, and a global collective would
        # pair up across groups and interleave their models
        from jax.sharding import NamedSharding

        rep = NamedSharding(mesh, P(None, None))
        x, y = jax.jit(lambda a, b: (a, b), out_shardings=(rep, rep))(x, y)
        x = np.asarray(x.addressable_data(0))
        y = np.asarray(y.addressable_data(0))
    return _finish_model(
        x, y, n_u, n_i, data
    )


# ---------------------------------------------------------------------------
# incremental fold-in (speed layer + anonymous serving estimates)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("implicit",))
def compute_target_qui(value, current, *, implicit: bool):
    """Target predicted-strength after an interaction of `value`.

    Implicit: interpolate from the current prediction toward 1 (positive
    value) or 0 (negative), fraction value/(1+value); NaN means "no change
    needed" (already out of range). Explicit: the value itself.
    Parity: ALSUtils.computeTargetQui (…/als/ALSUtils.java:37-60).
    """
    if not implicit:
        return value
    pos = (value > 0.0) & (current < 1.0)
    neg = (value < 0.0) & (current > 0.0)
    up = current + (value / (1.0 + value)) * (1.0 - jnp.maximum(0.0, current))
    dn = current + (value / (value - 1.0)) * (-jnp.minimum(1.0, current))
    return jnp.where(pos, up, jnp.where(neg, dn, jnp.nan))


@partial(jax.jit, static_argnames=("implicit",))
def compute_updated_xu(chol, value, xu, yi, *, implicit: bool):
    """Fold one interaction into a user vector: solve (Y'Y) dXu = dQui.Yi
    against the cached Cholesky factor of Y'Y and add the delta.

    xu may be a zero vector with had_xu=False semantics folded in by the
    caller passing current=0.5 sentinel: here, a NaN target yields xu
    unchanged (and callers treat all-zero xu as "new user").
    Parity: ALSUtils.computeUpdatedXu (…/als/ALSUtils.java:74-106).
    vmap over leading dims for micro-batch fold-in.
    """
    had_xu = jnp.any(xu != 0.0)
    qui = jnp.where(had_xu, jnp.vdot(xu, yi), 0.0)
    current = jnp.where(had_xu, qui, 0.5)
    target = compute_target_qui(value, current, implicit=implicit)
    dqui = jnp.where(jnp.isnan(target), 0.0, target - qui)
    rhs = (dqui * yi)[:, None]
    y = jax.scipy.linalg.solve_triangular(chol, rhs, lower=True)
    dxu = jax.scipy.linalg.solve_triangular(chol.T, y, lower=False)[:, 0]
    return xu + dxu


fold_in_batch = jax.vmap(
    lambda chol, value, xu, yi: compute_updated_xu(chol, value, xu, yi, implicit=True),
    in_axes=(None, 0, 0, 0),
)

fold_in_batch_explicit = jax.vmap(
    lambda chol, value, xu, yi: compute_updated_xu(chol, value, xu, yi, implicit=False),
    in_axes=(None, 0, 0, 0),
)


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def topk_dot(xu, y, *, k: int, exclude_mask=None):
    """Scores = Y.xu ; top-k with optional exclusion mask. One matmul +
    lax.top_k on device — this is the whole serving hot path that the
    reference needed LSH partitions and thread fan-out for
    (ALSServingModel.topN, …/als/model/ALSServingModel.java:264-279)."""
    scores = y.astype(jnp.float32) @ xu.astype(jnp.float32)
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


@partial(jax.jit, static_argnames=("k",))
def topk_dot_batch_xla(xs, y, *, k: int):
    """Batched variant: [B,K] users at once -> one [B,I] matmul. The XLA
    form materializes the [B,I] score matrix in HBM; at serving scale the
    fused Pallas kernel (ops/pallas_topk.py) avoids that round-trip."""
    scores = xs.astype(jnp.float32) @ y.astype(jnp.float32).T
    return jax.lax.top_k(scores, k)


@partial(jax.jit, static_argnames=("k", "recall"))
def topk_dot_batch_approx(xs, y, *, k: int, recall: float):
    """Batched APPROXIMATE top-k via the TPU-native partial-reduce
    (jax.lax.approx_max_k, measured 9.5x the exact fused kernel at
    4096 x 1M x 50). The on-device replacement for the reference's LSH
    candidate subsampling: recall is a compiler-verified target instead
    of an emergent property of hash partitions, and the serving tier's
    exact f32 re-rank runs on whatever comes back either way. On
    non-TPU backends approx_max_k computes exactly."""
    scores = jnp.dot(
        xs, y.T, preferred_element_type=jnp.float32
    )
    return jax.lax.approx_max_k(scores, k, recall_target=recall)


@partial(jax.jit, static_argnames=("k", "recall"))
def topk_dot_batch_quant_xla(xs, q, scale, *, k: int, recall: float = 1.0):
    """Batched top-k over an int8-quantized item matrix (q [I,F] int8,
    scale [I] f32). Queries quantize per-row exactly like the Pallas
    int8 kernel (ops/pallas_topk.py quantize_queries), and the dot runs
    over the quantized values in f32 — int8 x int8 products summed over
    a lane tile stay < 2^24, so the f32 accumulation is EXACT and this
    is a bit-faithful reference for the kernel's int32 MXU path. Scales
    multiply back in the same order the kernel applies them. The XLA
    reference the Pallas quantized kernel is tested against, and the CPU
    path for score-mode=quantized; the serving tier's exact f32 re-rank
    of the returned candidates corrects in-candidate ordering either
    way."""
    from oryx_tpu.ops.pallas_topk import quantize_queries

    xq, sx = quantize_queries(xs)
    scores = jnp.dot(
        xq.astype(jnp.float32), q.T.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale[None, :] * sx[:, None]
    if recall < 1.0:
        return jax.lax.approx_max_k(scores, k, recall_target=recall)
    return jax.lax.top_k(scores, k)


# Largest k dispatched to the fused Pallas kernel. The serving
# micro-batcher derives a k bucket from this so default /recommend
# overfetch (k=18) stays on the fused path — keep them coupled. The
# gen-2 bitonic kernel maintains a full 128-lane running top-k whatever
# the k, so the bound is the lane tile itself (the gen-1 argmax-round
# kernel capped out at 32, pushing the 128 bucket to the XLA fallback).
PALLAS_TOPK_MAX_K = 128

# Smallest catalog dispatched to the fused kernel: below this the [B,I]
# score matrix XLA materializes is small and streaming buys nothing.
PALLAS_TOPK_MIN_ITEMS = 32768


def topk_dot_batch_chunked(
    xs, y_chunks, *, k: int, recall: float = 1.0, rows=None
):
    """Exact batched top-k over an item matrix supplied as row CHUNKS:
    per-chunk top-k with the normal kernel (every equal-shaped chunk hits
    the SAME compiled program), then one merge over the C*k candidates
    with indices rebased to global rows. Every chunk is given the same
    `rows` (topk_dot_batch).

    Why: a single (20M, 250) bf16 dispatch is a 12 GB operand of 16 GB
    of HBM; bounded chunk shapes keep every compiled program and its
    temporaries small and reusable. Top-k is
    associative over row partitions, so the merge is exact; with
    recall < 1 each chunk's partial reduce carries the same per-chunk
    recall target."""
    total = sum(int(y.shape[0]) for y in y_chunks)
    if k > total:
        # contract parity with the single-dispatch kernel (lax.top_k
        # raises there); padded merge slots would otherwise fabricate
        # (-inf, aliased-index) results
        raise ValueError(f"k={k} exceeds total rows {total}")
    vals, idxs = [], []
    base = 0
    for y in y_chunks:
        v, i = topk_dot_batch(
            xs, y, k=min(k, y.shape[0]), recall=recall, rows=rows
        )
        pad = k - v.shape[1]
        if pad > 0:  # a chunk smaller than k still merges cleanly
            v = jnp.pad(v, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, pad)))
        vals.append(v)
        idxs.append(i + base)
        base += y.shape[0]
    cat_v = jnp.concatenate(vals, axis=1)
    cat_i = jnp.concatenate(idxs, axis=1)
    best_v, pos = jax.lax.top_k(cat_v, min(k, cat_v.shape[1]))
    return best_v, jnp.take_along_axis(cat_i, pos, axis=1)


def _on_tpu(a) -> bool:
    """Whether scoring against `a` runs on a TPU: a device array answers
    for itself; a tracer (the caller is jitting the selection) or a host
    array runs wherever the default backend is."""
    if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
        return all(d.platform == "tpu" for d in a.devices())
    return jax.default_backend() == "tpu"


def topk_path(y, k: int, recall: float = 1.0) -> str:
    """Which scoring path topk_dot_batch takes for this matrix, k and
    recall — decided from the matrix's type, shape and device before any
    call, never from a failed attempt: "sharded" | "chunked" (each shard
    or chunk re-enters this selection), "pallas-int8" | "xla-int8" for a
    QuantizedMatrix, "approx" for recall < 1, else "pallas" | "xla".
    The fused Pallas kernel serves exact requests on a TPU when k fits
    its 128-lane running top-k and the catalog is large enough to be
    worth streaming; everything else is plain XLA. A kernel that then
    fails to compile or run raises — nothing falls back."""
    from oryx_tpu.ops.transfer import (
        ChunkedMatrix, QuantizedMatrix, ShardedMatrix,
    )

    if isinstance(y, ShardedMatrix):
        return "sharded"
    if isinstance(y, ChunkedMatrix):
        return "chunked"
    quantized = isinstance(y, QuantizedMatrix)
    fused = (
        recall >= 1.0
        and k <= PALLAS_TOPK_MAX_K
        and y.shape[0] >= PALLAS_TOPK_MIN_ITEMS
        and _on_tpu(y.q if quantized else y)
    )
    if quantized:
        return "pallas-int8" if fused else "xla-int8"
    if recall < 1.0:
        return "approx"
    return "pallas" if fused else "xla"


# the paths whose jitted call takes the queries in the matrix's dtype
_SCORED_IN_VIEW_DTYPE = ("pallas", "approx", "xla")


def query_dtype(y, k: int, recall: float = 1.0):
    """The dtype topk_dot_batch's jitted call takes its queries in: the
    matrix's on the paths that score in it (the bf16 serving view;
    accumulation is f32 either way), float32 on the int8 paths (their
    call quantises the queries itself) and for a sharded or chunked
    matrix (each shard and chunk re-enters with a dtype of its own). A
    caller that forms its query block on the host forms it in this."""
    if topk_path(y, k, recall) in _SCORED_IN_VIEW_DTYPE:
        return np.dtype(y.dtype)
    return np.dtype(np.float32)


def stage_topk_operands(
    xs, y, *, k: int, recall: float = 1.0, rows=None, n_valid=None
):
    """What topk_dot_batch does to its operands before its jitted call:
    (xs, rows) as that call takes them. Nothing is uploaded or computed on
    the device for operands that are on the host: they stay numpy arrays
    and ride the jitted call's own argument path (serving/batcher.py forms
    its block at the view's width and in query_dtype already, so all that
    is left to do for it is the counts' array). Queries at the published
    width are zero-padded to a lane-padded view's and, where the path
    scores in the matrix's dtype, cast to it (a host block on the host,
    round-to-nearest-even as XLA's convert; a device block on the device);
    the fused kernel's two counts, the real query rows and the view's
    valid item rows, become the ONE int32[2] array it prefetches
    (returned in `rows`' place, and topk_dot_batch takes it there). Every
    other path ignores both counts and gets `rows` back as it came. A host
    block for a sharded or chunked matrix is uploaded here, once, so that
    every shard and chunk re-enters with a device array. topk_dot_batch
    does the queries' part to whatever it is handed (the kernel's wrapper
    stages the counts) and finds nothing left to do on operands that are
    staged already, so a caller that times the staging apart from the call
    (serving/batcher.py) stages first."""
    on_host = not isinstance(xs, jax.Array)
    if on_host:
        xs = np.asarray(xs)
    if xs.shape[1] < y.shape[1]:
        lanes = ((0, 0), (0, y.shape[1] - xs.shape[1]))
        xs = np.pad(xs, lanes) if on_host else jnp.pad(xs, lanes)
    path = topk_path(y, k, recall)
    if path in _SCORED_IN_VIEW_DTYPE and xs.dtype != y.dtype:
        xs = xs.astype(y.dtype) if on_host else jnp.asarray(xs, dtype=y.dtype)
    if on_host and path in ("sharded", "chunked"):
        xs = jnp.asarray(xs)
    if path in ("pallas", "pallas-int8") and (
        rows is not None or n_valid is not None
    ):
        from oryx_tpu.ops.pallas_topk import stage_counts

        rows = stage_counts(rows, n_valid, xs.shape[0], y.shape[0])
    return xs, rows


def topk_dot_batch(
    xs, y, *, k: int, recall: float = 1.0, counted: bool = False, rows=None,
    n_valid=None,
):
    """Batched top-k scoring; topk_path names the kernel selection.
    recall < 1 takes the approximate partial-reduce; exact requests take
    the fused streaming Pallas kernel on TPU (gen-2 bitonic-merge kernel,
    ops/pallas_topk.py — exact index agreement with lax.top_k up to
    k=128, never materializes the [B,I] scores), plain XLA elsewhere. A
    QuantizedMatrix (int8 rows + per-row scales, score-mode=quantized)
    dispatches the quantized kernel on TPU and the dequantize-and-dot XLA
    form elsewhere; a ChunkedMatrix (oversized model, ops/transfer.py)
    routes through the chunk-and-merge form; a ShardedMatrix (pod-scale
    row shards, one device per shard) scores per shard — each shard
    re-entering this selection with its own dtype — and merges the
    partials with the cross-shard bitonic merge (ops/shard_topk.py),
    selecting exactly the indices of the unsharded dispatch.

    counted=True appends a third result: the fused kernel's int32[4]
    device array (item chunks that fired, item chunks it walked — its
    threshold gate — the sublane tiles its folds sorted, and the fired
    chunks it placed without a sort, ops/pallas_topk.py), or None on
    every other path.

    rows: how many leading rows of xs are real, None for all. The fused
    kernel does not walk a row block that lies past them, folds only the
    sublane tiles that hold them, and returns filler for every row at or
    past them (ops/pallas_topk.py); every shard and chunk is given the
    same count, and the XLA, approximate and host paths ignore it: rows
    past `rows` are the caller's padding on every path, and only the
    rows before them are the same on all.

    n_valid: how many leading rows of y are items, None for all: a
    serving view is stored with room to grow, and the rows behind its
    last item belong to none. The fused kernel neither streams nor
    scores the item blocks that lie past them and never selects a row at
    or past them (ops/pallas_topk.py), so its candidates all lie below
    `n_valid`. Every other path ignores it, as it ignores `rows`, and
    scores the view's whole capacity: there a caller still drops the
    indices at or past its own count (apps/als/serving.py _post_pairs).
    A shard or a chunk is given none: its valid range is its own. `rows`
    may be both counts as stage_topk_operands staged them.

    A resident serving view is lane-padded in features (ops/transfer.py
    kernel_view_put); queries at the published width are zero-padded to
    it (stage_topk_operands), once for every path — zeros change no dot
    product."""
    # `rows` goes on as it was given: each shard and chunk re-enters here
    # with the same count, and the fused kernel's wrapper stages the counts
    xs, _ = stage_topk_operands(xs, y, k=k, recall=recall)
    path = topk_path(y, k, recall)
    if path in ("pallas", "pallas-int8"):
        from oryx_tpu.ops.pallas_topk import topk_dot_batch_pallas

        if path == "pallas-int8":
            return topk_dot_batch_pallas(
                xs, y.q, scales=y.scale, k=k, counted=counted, rows=rows,
                n_valid=n_valid,
            )
        return topk_dot_batch_pallas(
            xs, y, k=k, counted=counted, rows=rows, n_valid=n_valid
        )
    if path == "sharded":
        from oryx_tpu.ops.shard_topk import topk_dot_batch_sharded

        out = topk_dot_batch_sharded(xs, y, k=k, recall=recall, rows=rows)
    elif path == "chunked":
        out = topk_dot_batch_chunked(
            xs, y.chunks, k=k, recall=recall, rows=rows
        )
    elif path == "xla-int8":
        out = topk_dot_batch_quant_xla(
            xs, y.q, y.scale, k=k, recall=float(recall) if recall < 1.0 else 1.0
        )
    else:
        if path == "approx":
            out = topk_dot_batch_approx(xs, y, k=k, recall=float(recall))
        else:
            out = topk_dot_batch_xla(xs, y, k=k)
    return (*out, None) if counted else out
