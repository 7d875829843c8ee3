"""Request-coalescing micro-batcher for device top-k scoring.

The reference serves each /recommend request by fanning one thread pool
over LSH partitions (ALSServingModel.java:264-279; LoadBenchmark.java
measures ~1-2 concurrent requests saturating a 32-core host). On TPU the
equivalent hot loop is a single [B,K]x[K,I] matmul + top_k — but one
device dispatch per HTTP request wastes the MXU (B=1) and, worse, a
data-dependent k (how_many + len(exclude)) makes every distinct request
shape a fresh XLA compile.

This batcher fixes both:

- Concurrent requests are coalesced into ONE topk_dot_batch dispatch.
  Coalescing is *natural backpressure*, not a timer: while two dispatches
  are unresolved (one on the device, one queued behind it), new arrivals
  queue up and become the next dispatch. A server with fewer in flight
  dispatches a request as it arrives — no added latency floor.
- Shapes are bucketed: the row count pads up to a power of two (zero
  rows) and k rounds up to a fixed bucket, then results are trimmed
  host-side — so the jit cache holds a few dozen entries total instead of
  one per distinct (concurrency, exclusion-set-size) pair.

One process-wide dispatcher is shared across model swaps (serving managers
replace their model object on every MODEL update); requests are grouped by
the identity of the device matrix they score against, so a swap mid-window
simply splits one dispatch into two.

Device-hang failover: a device call can fail by never returning — not an
error, a silent hang inside a C call that cannot be cancelled in-process
(a lost device, a runtime deadlock). A watchdog thread detects a dispatch
stuck past ``device_timeout``, fails every parked and queued request over
to host-side numpy scoring (callers pass the row-aligned host matrix the
serving model already keeps for exact re-ranking), and serves degraded
while probing for device recovery in disposable threads. The hung
dispatcher thread is abandoned and superseded by a fresh one on recovery
(generation check in ``_run``). Every failover and every host-scored
request is counted (``device_failovers``, ``host_fallbacks``,
``oryx_topk_device_down``): chip_smoke.py asserts they stay zero, so
degraded service can never pass for a working chip.
"""

from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
from concurrent.futures import Future

from oryx_tpu.common import faults
from oryx_tpu.common.perfattr import (
    classify_idle_gap,
    current_ledger,
    get_perfattr,
)
from oryx_tpu.common.perfstats import get_perfstats
from oryx_tpu.common.tracing import (
    current_span,
    get_tracer,
    name_thread,
    thread_region_seconds,
)
from oryx_tpu.serving.futureutil import try_set_exception, try_set_result

import numpy as np

log = logging.getLogger(__name__)

# process-singleton tracer, bound once: the disabled-tracing submit cost
# is a single attribute read (common/tracing.py)
_TRACER = get_tracer()

# process-singleton dispatch-cost accounting (common/perfstats.py): every
# resolved device group records FLOPs/bytes/wall/occupancy, every host
# fallback zeroes the live MFU window
_PERF = get_perfstats()

# process-singleton latency attribution (common/perfattr.py): per-request
# phase stamps (queue_wait/batch_wait/pad/device/host_fallback), device
# idle-gap classification, and XLA compile telemetry
_PA = get_perfattr()


def _dispatch_bytes(padded: int, features: int, y, kb: int) -> float:
    """Approximate bytes one coalesced dispatch moves: the query upload,
    one read of the item matrix out of HBM (by far the largest term) and
    the result fetch, at the PUBLISHED `features`: the lanes a resident
    view is padded with (ops/transfer.py kernel_view_put) are the
    implementation's and are taken off the matrix's bytes. A count of
    bytes, not a model of the kernel's time: the fused scan reaches 9 %
    of its roofline at 5M x 250 (ledger, PR 26) and its time follows the
    query rows, so it is selection-bound and not bandwidth-bound in Y."""
    try:
        y_bytes = float(getattr(y, "nbytes", 0) or 0)
        if y_bytes:
            y_bytes -= (
                float(y.shape[0]) * (y.shape[1] - features)
                * np.dtype(y.dtype).itemsize
            )
    except Exception:  # non-jax stub matrices in tests
        y_bytes = 0.0
    return float(padded * features * 4 + y_bytes + padded * kb * 8)

from oryx_tpu.ops.als import PALLAS_TOPK_MAX_K

# k rounds up to the smallest of these (then min'd with the item count);
# larger requests fall back to next_pow2(k). A few buckets cover every
# realistic how_many + exclusion overfetch without recompiles. Every
# bucket up to PALLAS_TOPK_MAX_K (the full 128 lane tile since the gen-2
# bitonic kernel) rides the fused Pallas path — a default
# /recommend?howMany=10 overfetches to k=18 and lands in the 32 bucket,
# which bounds the result fetch and host trim below the 128 bucket's.
K_BUCKETS = (16, 32, PALLAS_TOPK_MAX_K, 1024)

# rows per device dispatch: the ladder's top rung. Not a measured knee
# (none has been measured): the fused kernel's time is linear in the row
# blocks it walks.
MAX_BATCH = 4096

# Queue-depth bound before the batcher sheds load (503 + Retry-After via
# serving/app.ShedLoad) instead of queueing without limit. At the default
# the backlog is ~2 full dispatches deep — past that, every queued request
# only adds latency for everyone behind it, and an honest refusal lets
# the client retry against a replica that has capacity.
MAX_QUEUE = 8192

# A dispatch stuck this long is a hung device, not a slow kernel —
# EXCEPT while a never-before-dispatched shape may be cold-compiling:
# first dispatches get COMPILE_TIMEOUT grace (a cold compile can run for
# minutes — PR 8's unrolled top-k kernel took 377 s under Mosaic — and
# misreading one as a hang fails the device path over to host scoring).
# Probes re-test a downed device at PROBE_INTERVAL.
DEVICE_TIMEOUT = 75.0
COMPILE_TIMEOUT = 240.0
PROBE_INTERVAL = 20.0

# On an accelerator batch shapes pad to just TWO buckets, so the pow2
# compile ramp (a dozen cold compiles) collapses to at most two per
# k-bucket. The ladder was chosen on the belief that the scan is
# HBM-bandwidth-bound in Y and so nearly flat in rows; PR 21's chip run
# measured the fused kernel LINEAR in rows instead (236 ms at 512 rows,
# 1876 ms at 4096, 1.31M x 50f). Since PR 30 the kernel is told the
# group's real row count and walks only the 128-row blocks that hold
# one, so the padding costs the query upload and the result fetch, not
# kernel time: a 513-request group pays for 5 row blocks, not 32, though
# it still compiles and fetches the 4096-row shape. ROADMAP S2
# re-chooses the ladder from the measured cost curve. On CPU the sgemm
# is compute-bound per row: fine-grained pow2 padding keeps wasted rows
# under 2x.
BATCH_BUCKETS_ACCEL = (512, MAX_BATCH)

# Dispatches launched and not yet retired (results fetched and handed to
# their requests) at which the dispatcher stops launching: one runs on the
# device and one is queued behind it, so the device finds the next scan
# waiting when one ends and no request waits for the host to fetch the
# one before. A third would only wait in the device's queue instead of the
# host's, and would split the same arrivals into more, smaller dispatches.
MAX_UNRESOLVED = 2


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _pad_rows(b: int, on_accel: bool) -> int:
    if on_accel:
        for s in BATCH_BUCKETS_ACCEL:
            if b <= s:
                return s
        # a batcher constructed with max_batch beyond the bucket ladder
        # dispatches the group unpadded — padding must never shrink a batch
        return b
    return _next_pow2(b)


def k_bucket(k: int) -> int:
    for b in K_BUCKETS:
        if k <= b:
            return b
    return _next_pow2(k)


def cosine_scale(scores: np.ndarray, norms: np.ndarray) -> np.ndarray:
    """Dot scores -> cosine scores with the shared zero-norm clamp."""
    return scores / np.maximum(norms, 1e-12)


def select_topk(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Top-k (values, indices) of a score vector, ranked descending:
    argpartition then an exact sort of the k survivors. The ONE host
    selection implementation — the batcher fallback and the LSH partition
    path both rank through it, so tie-breaking/NaN semantics can't drift."""
    k = min(k, scores.shape[0])
    top = np.argpartition(-scores, k - 1)[:k]
    top = top[np.argsort(-scores[top])]
    return scores[top], top


def host_topk(
    vec: np.ndarray,
    k: int,
    host_mat: np.ndarray,
    cosine: bool = False,
    norms: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Score one query on the host: f32 matmul + argpartition. The degraded
    path when the accelerator is unavailable — exact, just slower. Pass
    ``norms`` (cached per matrix snapshot) to skip the O(N.K) row-norm pass
    on cosine queries."""
    scores = host_mat @ np.asarray(vec, dtype=np.float32)
    if cosine:
        if norms is None:
            norms = np.linalg.norm(host_mat, axis=1)
        scores = cosine_scale(scores, norms)
    return select_topk(scores, k)


class _Pending:
    __slots__ = (
        "vec", "k", "y", "future", "host_mat", "cosine", "host_norms",
        "recall", "valid_rows", "score_mode", "t_enq", "trace_parent",
        "dev_span", "ledger",
    )

    def __init__(self, vec, k, y, future, host_mat=None, cosine=False,
                 host_norms=None, recall=1.0, valid_rows=None,
                 score_mode="exact"):
        self.vec = vec
        self.k = k
        self.y = y
        self.future = future
        self.host_mat = host_mat
        self.cosine = cosine
        self.host_norms = host_norms
        self.recall = recall
        # rows of y that hold real data: a capacity-padded serving view
        # (apps/als/serving.py) scatter-reserves rows past this for
        # speed-layer growth; the fused kernel is told not to walk them
        # and FLOP accounting must not count them
        self.valid_rows = valid_rows
        # which serving score mode produced this request (exact |
        # quantized | approx) — labels the dispatch's perfstats record so
        # per-mode throughput/latency are separable on /metrics
        self.score_mode = score_mode
        # enqueue time: always stamped at submit — the queue_wait phase
        # stamp needs it regardless of tracing. trace_parent/dev_span are
        # only populated while tracing is enabled (the submitting
        # request's span as parent, and a one-element box holding the
        # in-flight device span); ledger is the submitting request's
        # PhaseLedger (common/perfattr.py), or None off the request path
        self.t_enq = 0.0
        self.trace_parent = None
        self.dev_span = None
        self.ledger = None

    def take_dev_span(self):
        """Claim the in-flight device span, exactly once: the dispatcher's
        resolve and the watchdog's host-drain may race to finish it, and
        list.pop is a single GIL-atomic call so only one caller wins (a
        double finish would record the span into two ring slots and
        duplicate its subtree in /debug/traces)."""
        box = self.dev_span
        if not box:
            return None
        try:
            return box.pop()
        except IndexError:
            return None

    def resolve_on_host(self, reason: Exception | None = None) -> bool:
        """Host-score this request. Returns True if a result was delivered,
        False if it could only be failed (no host matrix) — callers count
        host fallbacks from the return value, so errored requests don't
        inflate the degraded-traffic metric."""
        if self.future.done():
            return False
        span = self.take_dev_span()
        if span is not None:
            # the wedged device span ends where host scoring takes over
            _TRACER.finish(span, failover="host")
        if self.host_mat is None:
            try_set_exception(
                self.future,
                reason or RuntimeError("device unavailable, no host fallback"),
            )
            return False
        try:
            tr = _TRACER
            t0 = time.monotonic()
            result = host_topk(
                self.vec, self.k, self.host_mat, self.cosine,
                self.host_norms,
            )
            if self.ledger is not None:
                self.ledger.add(
                    "host_fallback", time.monotonic() - t0, start=t0
                )
            if tr.enabled:
                tr.record_interval(
                    "batcher.host_score", t0, parent=self.trace_parent,
                    k=self.k,
                )
            # a lost try_set race means the wedged dispatcher unwedged
            # mid-drain and delivered its device result first — that
            # request succeeded, just not here
            return try_set_result(self.future, result)
        except Exception as e:  # pragma: no cover - defensive
            try_set_exception(self.future, e)
            return False


class TopKBatcher:
    """Coalesces top-k scoring requests into batched device dispatches."""

    _shared: "TopKBatcher | None" = None
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls) -> "TopKBatcher":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = TopKBatcher()
        return cls._shared

    def configure(self, config) -> None:
        """Adopt the serving config's shed knobs (ServingLayer.start);
        0 / negative max-queue disables shedding."""
        self.max_queue = config.get_int(
            "oryx.serving.api.shed.max-queue", MAX_QUEUE
        )
        self.retry_after_sec = config.get_int(
            "oryx.serving.api.shed.retry-after-sec", 1
        )

    def __init__(
        self,
        max_batch: int = MAX_BATCH,
        device_timeout: float = DEVICE_TIMEOUT,
        probe_interval: float = PROBE_INTERVAL,
        compile_timeout: float = COMPILE_TIMEOUT,
        max_queue: int = MAX_QUEUE,
        retry_after_sec: int = 1,
    ):
        self.max_batch = max_batch
        self.device_timeout = device_timeout
        self.probe_interval = probe_interval
        self.compile_timeout = compile_timeout
        self.max_queue = max_queue
        self.retry_after_sec = retry_after_sec
        self._lock = threading.Lock()
        # two waiters on one lock: the dispatcher waits on _cond (a submit,
        # a retire), the fetch thread on _fetch_cond (a launch)
        self._cond = threading.Condition(self._lock)
        self._fetch_cond = threading.Condition(self._lock)
        # dispatch shapes that have completed at least once: their XLA
        # compiles are done, so the wedge watchdog needs no compile grace
        self._compiled_shapes: set[tuple] = set()  # guarded-by: _lock
        # shape_key -> grace deadline for NEVER-COMPILED shapes currently
        # in flight: entries are added at dispatch, removed when the
        # dispatch resolves, and cleared on failover — so grace exists
        # exactly while a cold compile may legitimately be running, and a
        # wedge on an already-compiled shape still trips at device_timeout
        self._compiling: dict[tuple, float] = {}  # guarded-by: _lock
        self._on_accel = False
        # numbers the (matrix, k-bucket) groups as they start forming; the
        # number rides the group's handle to _resolve, names its batcher.*
        # regions and lands on its DispatchRecord (next() is GIL-atomic)
        self._dispatch_seq = itertools.count()
        self._queue: list[_Pending] = []  # guarded-by: _lock
        self._thread: threading.Thread | None = None  # guarded-by: _lock
        # the dispatcher's fetch thread: it resolves _unresolved in order
        self._fetcher: threading.Thread | None = None  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        # the launched group handles not yet retired, in dispatch order;
        # the dispatcher appends, the fetch thread resolves the head and
        # pops it
        self._unresolved: collections.deque = collections.deque()  # guarded-by: _lock
        # watchdog state: _picking marks the pick the dispatcher is
        # launching now (_busy_since reads it behind the oldest unresolved
        # launch); _inflight holds every request the (possibly wedged)
        # threads own so the watchdog can fail them over
        self._picking: float | None = None  # guarded-by: _lock
        self._inflight: dict[int, _Pending] = {}  # guarded-by: _lock
        self._device_down = threading.Event()
        self._watchdog: threading.Thread | None = None  # guarded-by: _lock
        self._probe_at = 0.0  # guarded-by: _lock
        self._probing = False  # guarded-by: _lock
        self._probe_started = 0.0  # guarded-by: _lock
        self._last_y = None  # guarded-by: _lock
        # idle-gap attribution (common/perfattr.py): _gap_mark is when the
        # device was last known busy (dispatch issued / results fetched).
        # What filled the idle time since is read off the dispatcher's own
        # regions at the next issue (_launch): the wall `batcher.idle`
        # (empty queue) and `batcher.full` (host serialize: the wait for
        # the fetch thread to retire a dispatch) gained since the issue
        # before, _gap_seen being their readings then and the thread they
        # are of. Only the down-window backoff has no
        # region (the probe's thread adds it) and keeps its accumulator.
        self._gap_mark = time.monotonic()  # guarded-by: _lock
        self._gap_seen = (0, 0.0, 0.0)  # guarded-by: _lock
        self._gap_down = 0.0  # guarded-by: _lock
        self._down_since = 0.0  # guarded-by: _lock
        # observability: dispatch count + coalesced-request count let a
        # /metrics scrape compute the achieved mean batch size;
        # host_fallbacks counts requests actually scored on the host.
        # Counters are writes-guarded: scrape-path reads of a monotonic
        # int are advisory by design, but concurrent unlocked increments
        # (a superseded dispatcher racing its replacement) lose updates.
        self.dispatches = 0  # guarded-by: _lock (writes)
        self.coalesced = 0  # guarded-by: _lock (writes)
        # dispatches launched while an earlier one was still unresolved:
        # over `dispatches`, the share that did not wait for a fetch
        self.launched_behind = 0  # guarded-by: _lock (writes)
        # item chunks the fused top-k kernel folded / walked, summed over
        # its dispatches: their ratio is how often its threshold gate let
        # a chunk through to the sort network (ops/pallas_topk.py)
        self.chunks_folded = 0  # guarded-by: _lock (writes)
        self.chunks_total = 0  # guarded-by: _lock (writes)
        # row blocks of the fused kernel's dispatches, and those of them it
        # did not walk because they lie past the dispatch's real rows
        self.row_blocks = 0  # guarded-by: _lock (writes)
        self.row_blocks_skipped = 0  # guarded-by: _lock (writes)
        # 128-item chunks of the views' capacity (the rows stored behind a
        # view's last item) that the walked row blocks did not walk
        self.item_chunks_skipped = 0  # guarded-by: _lock (writes)
        # (8, 128) sublane tiles the kernel's folds sorted: 16 a fold of a
        # whole 128-row block, 1 where the block holds one to eight requests
        self.fold_tiles = 0  # guarded-by: _lock (writes)
        # fired chunks the kernel placed without a sort: none brought a row
        # more than one score above its running k-th
        self.chunks_inserted = 0  # guarded-by: _lock (writes)
        self.host_fallbacks = 0  # guarded-by: _lock (writes)
        self.device_failovers = 0  # guarded-by: _lock (writes)
        # analytic FLOPs dispatched to the device (2·B·I·F per group,
        # ops/flops.py): rate(oryx_topk_flops_total) / oryx_device_peak_flops
        # is the serving MFU over any scrape interval
        self.flops_scored = 0.0  # guarded-by: _lock (writes)
        self._peak_flops = ...  # Ellipsis = not yet resolved (see _note_device)
        # tpu device_kind captured once at first dispatch; per-dtype peak
        # cache so a quantized (int8) dispatch divides by the int8 peak,
        # not the bf16 one (ops/flops.py per-dtype tables)
        self._device_kind: str | None = None
        self._peak_by_dtype: dict[str, float | None] = {}

    def register_gauges(self) -> None:
        """Expose the batcher's counters as callback gauges on the global
        metrics registry (the serving layer calls this once at startup;
        scrapes then read live values with no per-scrape mutation)."""
        from oryx_tpu.common.metrics import get_registry

        reg = get_registry()
        for name, help_text, fn in (
            ("oryx_topk_dispatches",
             "device top-k dispatches issued by the micro-batcher",
             lambda: float(self.dispatches)),
            ("oryx_topk_coalesced",
             "requests coalesced into device dispatches",
             lambda: float(self.coalesced)),
            ("oryx_topk_launched_behind_total",
             "device top-k dispatches launched while an earlier dispatch "
             "was unresolved (its results not yet fetched and handed out); "
             "over oryx_topk_dispatches, the share that did not wait for "
             "the fetch before them",
             lambda: float(self.launched_behind)),
            ("oryx_topk_chunks_folded",
             "128-item chunks that fired in the fused top-k kernel: those "
             "holding a score above a row block's running k-th, folded "
             "(sorted and merged) or placed without a sort",
             lambda: float(self.chunks_folded)),
            ("oryx_topk_chunks",
             "128-item chunks the fused top-k kernel walked (row blocks "
             "walked x the chunks of the view's valid item blocks)",
             lambda: float(self.chunks_total)),
            ("oryx_topk_row_blocks",
             "row blocks of the fused top-k kernel's dispatches (padded "
             "rows / the kernel's row block)",
             lambda: float(self.row_blocks)),
            ("oryx_topk_row_blocks_skipped",
             "row blocks the fused top-k kernel did not walk: those past "
             "the real rows of their dispatch",
             lambda: float(self.row_blocks_skipped)),
            ("oryx_topk_item_chunks_skipped",
             "128-item chunks of a view's capacity the fused top-k kernel "
             "did not walk: those behind the view's last valid item block, "
             "once for every row block it walked",
             lambda: float(self.item_chunks_skipped)),
            ("oryx_topk_fold_tiles",
             "(8, 128) sublane tiles the fused top-k kernel's folds sorted: "
             "over 16 x (oryx_topk_chunks_folded - oryx_topk_chunks_inserted), "
             "the share of a whole-block fold's work still done",
             lambda: float(self.fold_tiles)),
            ("oryx_topk_chunks_inserted",
             "fired 128-item chunks the fused top-k kernel placed without a "
             "sort (no row had more than one entrant): over "
             "oryx_topk_chunks_folded, the share of fired chunks that cost "
             "an insert and not the 36 stages",
             lambda: float(self.chunks_inserted)),
            ("oryx_topk_mean_batch",
             "achieved mean coalesced batch size (coalesced/dispatches "
             "over the process lifetime; >1 means requests are sharing "
             "device dispatches)",
             lambda: (
                 self.coalesced / self.dispatches if self.dispatches else 0.0
             )),
            ("oryx_topk_host_fallbacks",
             "requests scored on the host because the device was down",
             lambda: float(self.host_fallbacks)),
            ("oryx_topk_device_failovers",
             "wedged-dispatch failovers declared by the watchdog",
             lambda: float(self.device_failovers)),
            ("oryx_topk_device_down",
             "1 while top-k serving is on the degraded host path",
             lambda: 1.0 if self._device_down.is_set() else 0.0),
            ("oryx_topk_queue_depth",
             "requests waiting for a device dispatch right now; at "
             "oryx.serving.api.shed.max-queue new submits shed with 503",
             # len() is one GIL-atomic read and the depth gauge is
             # advisory; taking the dispatch lock on every scrape would
             # contend with the hot path for a number that is stale the
             # moment it renders
             lambda: float(len(self._queue))),  # oryxlint: disable=guarded-by
            ("oryx_topk_flops_total",
             "analytic FLOPs dispatched to device top-k scoring "
             "(rate over oryx_device_peak_flops = serving MFU)",
             lambda: float(self.flops_scored)),
            ("oryx_device_peak_flops",
             "dense peak FLOP/s of the serving chip at the dtype of the "
             "most recent dispatch (int8/bf16/f32 tables, ops/flops.py; "
             "0 when unknown or not a TPU)",
             lambda: float(self._device_peak() or 0.0)),
        ):
            reg.gauge(name, help_text).set_function(fn)

    def _device_peak(self) -> float | None:
        # NEVER resolve this on the scrape path: jax.devices() initializes
        # the backend, which can block — a /metrics GET must not be able
        # to stall the server. _note_device() fills it in from an array
        # that is already on-device at dispatch time.
        return None if self._peak_flops is ... else self._peak_flops

    def _note_device(self, y) -> None:
        if self._peak_flops is not ...:
            return
        try:
            d = next(iter(y.devices()))
            self._on_accel = getattr(d, "platform", "cpu") not in ("cpu",)
            if getattr(d, "platform", "") == "tpu":
                from oryx_tpu.ops.flops import peak_flops_for_kind

                self._device_kind = getattr(d, "device_kind", "") or ""
                self._peak_flops = peak_flops_for_kind(self._device_kind)
            else:
                self._peak_flops = None
        except Exception:  # non-jax stub matrices in tests
            self._peak_flops = None
        # hand the resolved chip peak to the live-MFU accounting (it must
        # never resolve jax.devices() itself on a scrape path)
        _PERF.note_peak("serving", self._device_peak())

    def _peak_for_matrix(self, y) -> float | None:
        """Chip peak at the dtype this dispatch actually streams (int8 for
        a QuantizedMatrix, bf16/f32 otherwise) — cached per dtype, resolved
        from the device kind _note_device captured. The live MFU gauge's
        denominator follows the most recent dispatch's dtype; a quantized
        deployment therefore reads against the int8 peak, never flattering
        itself against bf16."""
        if self._device_kind is None:
            return self._peak_flops if self._peak_flops is not ... else None
        from oryx_tpu.ops.flops import normalize_dtype, peak_flops_for_kind

        dtype = normalize_dtype(str(getattr(y, "dtype", "") or "bfloat16"))
        peak = self._peak_by_dtype.get(dtype, ...)
        if peak is ...:
            peak = peak_flops_for_kind(self._device_kind, dtype)
            self._peak_by_dtype[dtype] = peak
        self._peak_flops = peak  # the oryx_device_peak_flops gauge tracks it
        return peak

    # -- public API --------------------------------------------------------

    def submit(
        self,
        vec: np.ndarray,
        k: int,
        y,
        host_mat: np.ndarray | None = None,
        cosine: bool = False,
        host_norms: np.ndarray | None = None,
        recall: float = 1.0,
        valid_rows: int | None = None,
        score_mode: str = "exact",
    ) -> tuple[np.ndarray, np.ndarray]:
        """Score vec against device matrix y, returning (values, indices)
        for the top-k rows. Blocks until the coalesced dispatch completes.

        host_mat (the row-aligned f32 host copy of y) enables degraded
        host-side scoring when the device transport is wedged; host_norms
        caches its row norms for cosine fallbacks. recall < 1 selects the
        approximate device kernel (host fallback stays exact). valid_rows
        marks the real-data prefix of a capacity-padded matrix: the fused
        kernel scores no row past the largest count of a dispatch's group,
        and the FLOP accounting counts none on any path (off the fused
        kernel, and beside a request with a longer prefix, the caller
        still filters padding indices from results).
        score_mode labels the dispatch's perfstats record (exact |
        quantized | approx) for per-mode observability.
        """
        return self.submit_nowait(
            vec, k, y, host_mat=host_mat, cosine=cosine,
            host_norms=host_norms, recall=recall, valid_rows=valid_rows,
            score_mode=score_mode,
        ).result()

    def submit_nowait(
        self,
        vec: np.ndarray,
        k: int,
        y,
        host_mat: np.ndarray | None = None,
        cosine: bool = False,
        host_norms: np.ndarray | None = None,
        recall: float = 1.0,
        valid_rows: int | None = None,
        score_mode: str = "exact",
    ) -> Future:
        """submit() without the wait: returns the Future of (values,
        indices). Deferred endpoints chain post-processing onto it instead
        of parking a worker thread per in-flight request."""
        return self.submit_many_nowait(
            (vec,), k, y, host_mat=host_mat, cosine=cosine,
            host_norms=host_norms, recall=recall, valid_rows=valid_rows,
            score_mode=score_mode,
        )[0]

    def submit_many_nowait(
        self,
        vecs,
        k: int,
        y,
        host_mat: np.ndarray | None = None,
        cosine: bool = False,
        host_norms: np.ndarray | None = None,
        recall: float = 1.0,
        valid_rows: int | None = None,
        score_mode: str = "exact",
    ) -> list[Future]:
        """submit_nowait() of several rows of one caller, one Future each:
        the rows are queued under one hold of the lock, so the dispatcher
        picks them together and they ride one dispatch (a basket's
        positions wait for one kernel, not two). The first row carries the
        caller's ledger; its phases are the request's."""
        # queue-wait measures from here to the dispatcher picking the
        # batch up; the ledger is the submitting request's (thread-local,
        # installed by ServingApp.dispatch_nowait — None off the request
        # path, e.g. a test's or a probe's submits)
        t_enq = time.monotonic()
        ledger = current_ledger()
        if ledger is not None:
            # the slice between the last stamped phase (parse/auth) and
            # this enqueue is routing + handler pre-work building the
            # query (model lookup, user-vector fetch) — charge it to
            # parse so the budget keeps tiling the request wall-clock
            # instead of leaking it between auth and queue_wait
            tail = ledger.last_end()
            if tail is not None and tail < t_enq:
                ledger.add("parse", t_enq - tail, start=tail)
        # parent = the submitting request's span (thread-current, set by
        # ServingApp.dispatch_nowait)
        parent = current_span() if _TRACER.enabled else None
        rows = []
        for vec in vecs:
            p = _Pending(
                np.asarray(vec, dtype=np.float32), int(k), y, Future(),
                host_mat, cosine, host_norms, float(recall), valid_rows,
                score_mode,
            )
            p.t_enq = t_enq
            p.ledger = None if rows else ledger
            p.trace_parent = parent
            rows.append(p)
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if self.max_queue > 0 and len(self._queue) + len(rows) > self.max_queue:
                # saturation: refuse honestly instead of queueing without
                # bound. Raised under the lock so the depth check and the
                # refusal are one decision; the exception renders as
                # 503 + Retry-After at the app boundary.
                from oryx_tpu.common.flightrec import get_flightrec
                from oryx_tpu.common.metrics import get_registry
                from oryx_tpu.serving.app import ShedLoad

                get_registry().counter("oryx_serving_shed_total").inc()
                # flight EPISODE marker: one bounded disk append per 5s
                # per storm (the episode_s gate is a dict probe on every
                # other shed), so the black box records that a shed storm
                # happened without per-request I/O under this lock
                get_flightrec().record(
                    kind="shed-episode", episode_s=5.0,
                    queue_depth=len(self._queue),
                )
                raise ShedLoad(
                    f"top-k queue saturated ({len(self._queue)} deep)",
                    retry_after_sec=self.retry_after_sec,
                )
            # the down-check must happen under the lock: a check-then-queue
            # race against the watchdog's failover would park this request
            # on a wedged device with nothing left to fail it over
            down = self._device_down.is_set()
            # refresh the probe target every submit: recovery must test the
            # matrix that will actually be served, and holding only the
            # last-DISPATCHED y would pin a swapped-out model's device
            # buffer for the whole outage
            self._last_y = y
            if not down:
                self._ensure_thread()
                self._ensure_watchdog()
                self._queue.extend(rows)
                self._cond.notify()
        if down:
            self._maybe_probe()
            n = sum(p.resolve_on_host() for p in rows)
            if n:
                with self._lock:
                    self.host_fallbacks += n
                _PERF.note_fallback(n)
        return [p.future for p in rows]

    def close(self) -> None:
        """Stop taking submits; the dispatcher launches what is queued and
        the fetch thread resolves what is in flight. What a wedged thread
        still holds after the joins is failed, so no Future stays
        pending."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            self._fetch_cond.notify_all()
            threads = (self._thread, self._fetcher)
        deadline = time.monotonic() + 5.0
        for t in threads:
            if t is not None:
                t.join(timeout=max(0.0, deadline - time.monotonic()))
        with self._lock:
            self._last_y = None
            left = list(self._inflight.values()) + self._queue
            self._inflight.clear()
            self._queue = []
        err = RuntimeError("batcher closed with the request unresolved")
        for p in left:
            span = p.take_dev_span()
            if span is not None:
                _TRACER.finish(span, error="closed")
            try_set_exception(p.future, err)

    # -- dispatcher --------------------------------------------------------

    def _ensure_thread(self) -> None:  # oryxlint: holds=_lock
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="oryx-topk-batcher", daemon=True
            )
            # the fetch thread serves this dispatcher alone: it ends when
            # the dispatcher is superseded
            self._fetcher = threading.Thread(
                target=self._fetch_loop, args=(self._thread,),
                name="oryx-topk-fetch", daemon=True,
            )
            self._thread.start()
            self._fetcher.start()

    def _ensure_watchdog(self) -> None:  # oryxlint: holds=_lock
        if self._watchdog is None or not self._watchdog.is_alive():
            self._watchdog = threading.Thread(
                target=self._watch, name="oryx-topk-watchdog", daemon=True
            )
            self._watchdog.start()

    def _dispatcher_wait(self, me) -> str | None:  # oryxlint: holds=_lock
        """The region the dispatcher waits in now, or None when it should
        pick (or find that it must stop): `batcher.idle` with nothing
        queued, `batcher.full` with MAX_UNRESOLVED dispatches unresolved."""
        if self._thread is not me:
            return None
        if not self._queue:
            return None if self._closed else "batcher.idle"
        if len(self._unresolved) >= MAX_UNRESOLVED:
            return "batcher.full"
        return None

    def _busy_since(self) -> float | None:  # oryxlint: holds=_lock
        """Since when the device owes the dispatcher an answer: the launch
        of the oldest unresolved dispatch, else the pick it is launching."""
        if self._unresolved:
            return self._unresolved[0][6][0]  # the handle's cost: t0 first
        return self._picking

    def _run(self) -> None:  # oryxlint: offloop (dedicated dispatcher thread)
        # Two threads share the pipeline. This one (`oryx-topk`) picks and
        # launches, in dispatch order, and is the only one that issues
        # device work, so the device runs the scans in the order of their
        # numbers. Each launch starts the async device->host copies of its
        # results and hands its handles to the fetch thread
        # (`oryx-topk-fetch`, _fetch_loop), which blocks in their fetch and
        # distributes them. A queued request is launched at once while
        # fewer than MAX_UNRESOLVED dispatches are unresolved: it does not
        # wait for the fetch and distribute of the one before.
        # This thread's top-level regions tile its life: `batcher.idle`
        # (nothing queued, whatever is in flight), `batcher.full` (queued,
        # and MAX_UNRESOLVED unresolved), `batcher.pick`, one
        # `batcher.launch` a group; the hand-over is a few microseconds
        # under the lock between them.
        me = threading.current_thread()
        name_thread("oryx-topk")
        tr = _TRACER
        while True:
            with self._cond:
                wait = self._dispatcher_wait(me)
            if wait is not None:
                with tr.region(wait), self._cond:
                    while self._dispatcher_wait(me) == wait:
                        self._cond.wait()
                continue
            batch: list[_Pending] = []
            behind = 0
            try:
                with tr.region("batcher.pick"):
                    with self._cond:
                        if self._thread is not me or not self._queue:
                            # superseded after a wedge (a fresh dispatcher
                            # owns the queue now; whatever this one still
                            # holds was already failed over by the
                            # watchdog), or closed with nothing left to
                            # launch (the fetch thread resolves the rest)
                            return
                        batch, self._queue = self._queue[: self.max_batch], self._queue[self.max_batch:]
                        for p in batch:
                            self._inflight[id(p)] = p
                        self._picking = time.monotonic()
                        behind = len(self._unresolved)
                    t_pick, groups = self._group(batch)
                launched = self._launch_groups(groups, t_pick) if groups else []
            except Exception as e:  # pragma: no cover - defensive: a failure
                # before the per-group guard (grouping, imports) must fail
                # the whole batch, not kill the thread with futures pending
                log.exception("batcher launch failed")
                for p in batch:
                    try_set_exception(p.future, e)
                launched = []
            with self._cond:
                if self._thread is not me:
                    # superseded mid-launch: the watchdog failed this
                    # batch over and the replacement owns the pipeline
                    return
                self._picking = None
                self._unresolved.extend(launched)
                # every launch after the pick's first is behind it, and
                # the first too when an earlier pick's is unresolved
                self.launched_behind += max(0, len(launched) - (0 if behind else 1))
                for p in batch:
                    if p.future.done():  # its group failed over to the host
                        self._inflight.pop(id(p), None)
                # also when nothing launched: a closing fetch thread waits
                # for the pick to end
                self._fetch_cond.notify()

    def _fetch_loop(self, owner: threading.Thread) -> None:  # oryxlint: offloop (dedicated fetch thread)
        # The dispatcher's second thread: resolves the launched handles in
        # dispatch order. Its fetch blocks in np.asarray, which releases
        # the interpreter lock while the results are on their way, and its
        # retire frees a place in the pipeline for the dispatcher. Its
        # top-level regions tile its life: `batcher.await` (nothing
        # launched to fetch), `batcher.fetch`, `batcher.distribute` (both
        # in _resolve), `batcher.retire`.
        name_thread("oryx-topk-fetch")
        tr = _TRACER
        while True:
            with tr.region("batcher.await"), self._fetch_cond:
                while (
                    self._thread is owner
                    and not self._unresolved
                    # closed, and the dispatcher has nothing left to launch
                    and not (
                        self._closed and not self._queue
                        and self._picking is None
                    )
                ):
                    self._fetch_cond.wait()
                if self._thread is not owner or not self._unresolved:
                    return
                item = self._unresolved[0]
            self._resolve(item)
            with tr.region("batcher.retire"), self._cond:
                if self._thread is not owner:
                    # superseded while the fetch sat on a wedged transport:
                    # the watchdog failed this group over and cleared the
                    # pipeline the replacement now fills
                    return
                self._unresolved.popleft()
                for p in item[0]:
                    self._inflight.pop(id(p), None)
                self._cond.notify()

    def _launch(
        self, batch: list[_Pending]
    ) -> list[tuple[list[_Pending], int, object, object, object, tuple, tuple]]:
        """Issue one device dispatch per (matrix, k-bucket) group and start
        the async result copies; returns the in-flight group handles."""
        t_pick, groups = self._group(batch)
        return self._launch_groups(groups, t_pick)

    def _group(
        self, batch: list[_Pending]
    ) -> tuple[float, dict[tuple[int, int, float], list[_Pending]]]:
        """The end of the pick: queue-wait ends, the batch is grouped by
        matrix and k-bucket and counted."""
        tr = _TRACER
        # queue-wait ends now: the dispatcher owns the batch
        t_pick = time.monotonic()
        for p in batch:
            if p.ledger is not None and p.t_enq:
                p.ledger.add("queue_wait", t_pick - p.t_enq, start=p.t_enq)
        if tr.enabled:
            for p in batch:
                if p.t_enq:
                    tr.record_interval(
                        "batcher.queue_wait", p.t_enq, t_pick,
                        parent=p.trace_parent,
                    )

        groups: dict[tuple[int, int, float], list[_Pending]] = {}
        for p in batch:
            n = p.y.shape[0]
            kb = min(k_bucket(p.k), n)
            groups.setdefault((id(p.y), kb, p.recall), []).append(p)

        # under the lock: a wedged-then-unwedged dispatcher can overlap
        # its replacement here, and unlocked += loses updates
        # [oryxlint guarded-by fix]
        with self._lock:
            self.dispatches += len(groups)
            self.coalesced += len(batch)
        return t_pick, groups

    def _launch_groups(
        self, groups: dict[tuple[int, int, float], list[_Pending]], t_pick: float
    ) -> list[tuple[list[_Pending], int, object, object, object, tuple, tuple]]:
        from oryx_tpu.ops import als

        tr = _TRACER
        launched = []
        gap_pending = True  # classify the inter-dispatch idle gap once
        for (_, kb, recall), group in groups.items():
            # failures stay inside their group: a bad shape / OOM against
            # one target matrix must not fail requests scoring another
            shape_key = None
            n_disp = next(self._dispatch_seq)
            try:
                faults.fire("serving.device")
                t0 = time.monotonic()
                y = group[0].y
                b = len(group)
                self._note_device(y)
                padded = _pad_rows(b, self._on_accel)
                with tr.region(
                    "batcher.launch", cpu=True, dispatch=n_disp, rows=b,
                    padded=padded, k_bucket=kb,
                ):
                    with tr.region("batcher.launch.form"):
                        # a serving view is stored with room to grow: the
                        # fused kernel is handed the count of its items and
                        # neither streams nor scores the rows behind them,
                        # and the MFU figure counts the real-data prefix on
                        # every path. Requests of one group share y; one
                        # submitted against a shorter id list is scored
                        # over the longest and drops what its own list does
                        # not name (apps/als/serving.py _post_pairs)
                        n_rows = max(
                            p.valid_rows or y.shape[0] for p in group
                        )
                        # ... and at the published feature count (the
                        # queries'), not the view's lane-padded width
                        features = int(group[0].vec.shape[-1])
                        group_flops = 2.0 * b * n_rows * features
                        # per-dtype peak: a quantized (int8) dispatch's MFU
                        # window divides by the int8 peak, an exact bf16 one
                        # by bf16
                        _PERF.set_peak("serving", self._peak_for_matrix(y))
                        # keyed on the FULL (capacity) shape: the serving
                        # view pads rows up a bucket ladder precisely so
                        # store growth keeps hitting these compiled entries
                        shape_key = (
                            padded, kb, recall, tuple(y.shape),
                            str(getattr(y, "dtype", "")),
                        )
                        first_compile = False
                        with self._cond:
                            # recovery probes re-test against the latest
                            # matrix; the probe thread reads it under the
                            # same lock [oryxlint guarded-by fix: these
                            # three were unlocked]
                            self._last_y = y
                            self.flops_scored += group_flops
                            if shape_key not in self._compiled_shapes:
                                # first dispatch of this shape may
                                # cold-compile for minutes: give the hang
                                # watchdog compile grace (for THIS shape,
                                # until it resolves) so it doesn't misread
                                # the compile as a hung device and fail the
                                # device path over to host scoring
                                first_compile = True
                                self._compiling[shape_key] = (
                                    time.monotonic() + self.compile_timeout
                                )
                        for p in group:
                            if p.ledger is not None:
                                # picked -> this group starts forming
                                p.ledger.add(
                                    "batch_wait", t0 - t_pick, start=t_pick
                                )
                        t_pad = time.monotonic()
                        # at the view's width (its pad lanes stay zero)
                        # and in the dtype the path scores in: the real
                        # rows alone are cast, here, as they are copied in
                        xs = np.zeros(
                            (padded, y.shape[1]),
                            dtype=als.query_dtype(y, kb, recall),
                        )
                        for i, p in enumerate(group):
                            xs[i, :features] = p.vec
                        pad_s = time.monotonic() - t_pad
                        for p in group:
                            if p.ledger is not None:
                                p.ledger.add("pad", pad_s, start=t_pad)
                        if tr.enabled:
                            # device span: dispatch issue until the host
                            # fetch resolves (_resolve); one span per
                            # request so every request's trace tree shows
                            # its own device time, and names the dispatch
                            # that caused the wait
                            for p in group:
                                if p.t_enq:
                                    p.dev_span = [tr.start(
                                        "batcher.device",
                                        parent=p.trace_parent,
                                        k=kb, batch=b, rows=padded,
                                        dispatch=n_disp,
                                    )]
                    with tr.region("batcher.issue"):
                        t_disp = time.monotonic()
                        if gap_pending:
                            # the idle gap between the previous dispatch
                            # finishing and this one being issued, split
                            # by measured cause
                            gap_pending = False
                            self._classify_gap(t_disp)
                        with tr.region("batcher.issue.upload"):
                            # whatever topk_dot_batch would do to its
                            # operands before its jitted call, done here so
                            # that the two are timed apart. The block was
                            # formed as the call takes it and nothing is
                            # uploaded for it: what runs here is the two
                            # counts' host array (and, for a sharded or
                            # chunked matrix, the block's one upload before
                            # the fan-out)
                            xd, rows_d = als.stage_topk_operands(
                                xs, y, k=kb, recall=recall, rows=b,
                                n_valid=int(n_rows),
                            )
                        with tr.region("batcher.issue.call"):
                            # the block and the counts ride the jitted
                            # call as numpy operands: it transfers them.
                            # chunks: the fused kernel's counts (chunks
                            # fired, walked, tiles sorted, chunks inserted),
                            # None on every other path.
                            # rows: the kernel walks no row block past the
                            # group's b real rows and no item block past
                            # the view's n_rows items (both counts are in
                            # rows_d on the fused path)
                            vals, idx, chunks = als.topk_dot_batch(
                                xd, y, k=kb, recall=recall,
                                counted=True, rows=rows_d,
                            )
                        with tr.region("batcher.issue.copy"):
                            try:
                                vals.copy_to_host_async()
                                idx.copy_to_host_async()
                                if chunks is not None:
                                    chunks.copy_to_host_async()
                            except AttributeError:  # non-jax array (test stubs)
                                pass
                        t_issued = time.monotonic()
                    with self._lock:
                        # the device is busy from here: the next idle gap
                        # starts when its results land (_resolve)
                        self._gap_mark = max(self._gap_mark, t_issued)
                    if first_compile:
                        # the jit call traces+compiles synchronously on
                        # the first dispatch of a shape, then enqueues: the
                        # call duration IS the compile stall (a warm call
                        # returns in microseconds). Feed the compile
                        # telemetry, charge the stall to the device's idle
                        # account, and mark it as a distinct waterfall span
                        # — the first dispatch after a generation swap
                        # lands here by construction (a new matrix identity
                        # is a new shape signature).
                        compile_s = t_issued - t_disp
                        _PA.record_compile("serving", compile_s)
                        _PA.record_idle_gap("compile_stall", compile_s)
                        if tr.enabled:
                            tr.record_interval(
                                "batcher.compile_stall", t_disp, t_issued,
                                parent=group[0].trace_parent,
                                k=kb, rows=padded,
                            )
                    # per-dispatch cost accounting, finalized at resolve
                    # time (wall-clock runs dispatch → host fetch
                    # materialized): occupancy = real rows / the
                    # capacity-padded view shape. The dispatch's number
                    # rides along to the DispatchRecord.
                    tp = group[0].trace_parent
                    cost = (
                        t0, group_flops,
                        _dispatch_bytes(padded, features, y, kb),
                        b, padded, int(n_rows), int(y.shape[0]),
                        tp.trace_id if tp is not None else None,
                        group[0].score_mode,
                        t_disp, n_disp,
                    )
                    launched.append(
                        (group, kb, vals, idx, chunks, shape_key, cost)
                    )
            except Exception as e:
                log.exception("batcher group dispatch failed (k=%d)", kb)
                # no compile is in flight anymore: drop the grace entry,
                # or a real transport wedge on a compiled shape would sit
                # behind this shape's stale compile deadline
                if shape_key is not None:
                    with self._cond:
                        self._compiling.pop(shape_key, None)
                self._fail_group_over(group, e)
        return launched

    def _classify_gap(self, t_disp: float) -> None:
        """The idle gap from the device last known busy to this issue, by
        cause: what the dispatcher's regions gained since the issue before
        (of THIS thread: a superseding dispatcher starts from zero)."""
        me = threading.get_ident()
        idle = thread_region_seconds("batcher.idle")
        full = thread_region_seconds("batcher.full")
        with self._lock:
            seen = self._gap_seen if self._gap_seen[0] == me else (me, 0.0, 0.0)
            causes = classify_idle_gap(
                t_disp - self._gap_mark, wait_s=idle - seen[1],
                serialize_s=full - seen[2], down_s=self._gap_down,
            )
            self._gap_seen = (me, idle, full)
            self._gap_down = 0.0
            self._gap_mark = t_disp
        for cause, s in causes.items():
            _PA.record_idle_gap(cause, s)

    def _fail_group_over(self, group: list[_Pending], e: Exception) -> None:
        """A device dispatch/transfer ERROR (not a wedge — the watchdog
        owns those): serve the group exactly on the host instead of
        failing it. Requests without a host matrix get the error; the
        watchdog's concurrent drain may be host-resolving these same
        futures, and resolve_on_host/try_set absorb the lost race."""
        n = 0
        for p in group:
            if p.host_mat is not None:
                if p.resolve_on_host(e):
                    n += 1
            else:
                span = p.take_dev_span()
                if span is not None:
                    _TRACER.finish(span, error=type(e).__name__)
                try_set_exception(p.future, e)
        if n:
            with self._lock:
                self.host_fallbacks += n
            # visible degraded-mode accounting: count the host dispatches
            # and zero the live MFU window — host throughput during the
            # outage must not read as healthy device utilization
            _PERF.note_fallback(n)

    def _resolve(
        self, item: tuple[list[_Pending], int, object, object, object, tuple, tuple]
    ) -> None:
        group, kb, vals_dev, idx_dev, chunks_dev, shape_key, cost = item
        (t0, flops, bytes_moved, b, padded, valid, cap, trace_id,
         mode, t_disp, n_disp) = cost
        try:
            tr = _TRACER
            with tr.region("batcher.fetch", dispatch=n_disp):
                with tr.region("batcher.fetch.vals"):
                    vals = np.asarray(vals_dev)
                with tr.region("batcher.fetch.idx"):
                    idx = np.asarray(idx_dev)
                folded = total = tiles = inserted = None
                blocks = skipped = items_skipped = None
                with tr.region("batcher.fetch.chunks"):
                    counts = None if chunks_dev is None else np.asarray(chunks_dev)
                if counts is not None:
                    folded, total, tiles, inserted = (int(c) for c in counts)
                    # the kernel walks whole row blocks over the view's
                    # valid item blocks: its own count of chunks walked
                    # says how many
                    from oryx_tpu.ops.pallas_topk import dispatch_grid

                    y = group[0].y
                    blocks, per_block, behind = dispatch_grid(
                        padded, y.shape, y.dtype, n_valid=valid
                    )
                    walked = total // per_block if per_block else 0
                    skipped = blocks - walked
                    # the view's capacity behind its items, which each
                    # walked row block left alone
                    items_skipped = walked * behind
                t_fetch = time.monotonic()
            with tr.region("batcher.distribute", dispatch=n_disp):
                # results are on the host: the dispatch's device work +
                # fetch is complete — record its cost (FLOPs/bytes/wall/
                # occupancy) into the live perf accounting
                _PERF.record_dispatch(
                    "serving",
                    flops=flops, bytes_moved=bytes_moved,
                    wall_s=t_fetch - t0, rows=b, padded_rows=padded,
                    valid_rows=valid, capacity_rows=cap, trace_id=trace_id,
                    t_start=t0, score_mode=mode,
                    dispatch=n_disp, k_bucket=kb,
                    chunks_folded=folded, chunks_total=total,
                    row_blocks=blocks, row_blocks_skipped=skipped,
                    fold_tiles=tiles, chunks_inserted=inserted,
                    item_chunks_skipped=items_skipped,
                )
                # the dispatch completed, so this shape's compile is done:
                # drop its grace window and never grant it one again. Both
                # under the lock — the watchdog iterates
                # _compiling.values() holding it (an unlocked pop
                # mid-iteration kills the watchdog thread with
                # RuntimeError), and _launch's membership probe of
                # _compiled_shapes reads under it too
                with self._cond:
                    self._compiled_shapes.add(shape_key)
                    self._compiling.pop(shape_key, None)
                    # the device finished this dispatch when the fetch
                    # landed: the next idle gap starts here. An earlier
                    # down-window slice predates the device finishing —
                    # outside the new gap window by construction — so it
                    # resets with it.
                    if t_fetch > self._gap_mark:
                        self._gap_mark = t_fetch
                        self._gap_down = 0.0
                for i, p in enumerate(group):
                    k_eff = min(p.k, kb)
                    span = p.take_dev_span()
                    if span is not None:
                        _TRACER.finish(span)
                    if p.ledger is not None:
                        # dispatch issue -> results fetched to host
                        p.ledger.add("device", t_fetch - t_disp, start=t_disp)
                    # the watchdog may have host-resolved this request
                    # while the fetch above sat on a wedged transport — and
                    # may win the race BETWEEN a done() check and the set;
                    # try_set absorbs the lost race instead of failing the
                    # rest of the group
                    try_set_result(p.future, (vals[i, :k_eff], idx[i, :k_eff]))
                with self._lock:
                    if total is not None:
                        self.chunks_folded += folded
                        self.chunks_total += total
                        self.row_blocks += blocks
                        self.row_blocks_skipped += skipped
                        self.item_chunks_skipped += items_skipped
                        self.fold_tiles += tiles
                        self.chunks_inserted += inserted
        except Exception as e:
            log.exception("batcher group resolve failed (k=%d)", kb)
            with self._cond:
                self._compiling.pop(shape_key, None)
            # a device->host transfer ERROR degrades to host scoring like
            # a dispatch error does (wedges — hangs — stay the watchdog's)
            self._fail_group_over(group, e)

    # -- watchdog: wedged-transport failover -------------------------------

    def _watch(self) -> None:  # oryxlint: offloop (watchdog thread)
        while True:
            time.sleep(min(1.0, self.device_timeout / 4))
            with self._cond:
                if self._closed:
                    return
                busy = self._busy_since()
                now = time.monotonic()
                wedged = (
                    busy is not None
                    and now - busy > self.device_timeout
                    # a first-dispatch shape may still be cold-compiling:
                    # grace holds only while such a shape is in flight and
                    # its own compile deadline hasn't passed
                    and now > max(self._compiling.values(), default=0.0)
                )
                if not wedged:
                    continue
                # Fail over: mark the device down FIRST so new submits take
                # the host path, then resolve everything the wedged
                # dispatcher owns plus the whole queue on the host.
                self.device_failovers += 1
                self._device_down.set()
                self._down_since = now  # idle-gap failover_backoff window
                self._probe_at = time.monotonic() + self.probe_interval
                stuck = list(self._inflight.values()) + self._queue
                self._inflight.clear()
                self._queue = []
                self._unresolved.clear()
                self._picking = None
                self._compiling.clear()  # abandoned with the dispatcher
                # supersede the wedged dispatcher and its fetch thread;
                # whichever of them waits wakes to leave
                self._thread = self._fetcher = None
                self._cond.notify_all()
                self._fetch_cond.notify_all()
            log.error(
                "device dispatch stuck > %.0fs — failing %d requests over "
                "to host scoring and marking the device down",
                self.device_timeout,
                len(stuck),
            )
            err = RuntimeError(
                f"device dispatch exceeded {self.device_timeout}s"
            )

            # drain concurrently: serial host scoring of a MAX_BATCH-deep
            # backlog would add minutes of extra wait on top of the
            # timeout the callers already paid
            def _drain(chunk: list[_Pending]) -> None:
                n = 0
                for p in chunk:
                    if p.resolve_on_host(err):
                        n += 1
                with self._lock:
                    self.host_fallbacks += n
                _PERF.note_fallback(n)

            n_threads = min(8, max(1, len(stuck) // 32 + 1))
            if n_threads == 1:
                _drain(stuck)
            else:
                drains = [
                    threading.Thread(
                        target=_drain, args=(stuck[i::n_threads],),
                        name=f"oryx-topk-drain-{i}", daemon=True,
                    )
                    for i in range(n_threads)
                ]
                for t in drains:
                    t.start()
                for t in drains:
                    t.join()

    def _maybe_probe(self) -> None:
        """While the device is down, periodically test it with a tiny
        dispatch in a disposable thread (a probe into a wedged transport
        hangs forever — it must never block a request path). On success the
        device path resumes."""
        with self._lock:
            if (
                self._probing
                and time.monotonic() - self._probe_started > self.device_timeout
            ):
                # the probe itself hung on the wedged transport; abandon it
                # (its thread can never be cancelled) or no probe would
                # ever run again and the device path could never resume
                self._probing = False
            if (
                self._probing
                or self._last_y is None
                or time.monotonic() < self._probe_at
            ):
                return
            self._probing = True
            self._probe_started = time.monotonic()
            y = self._last_y

        def probe() -> None:  # oryxlint: offloop (disposable probe thread)
            ok = False
            try:
                from oryx_tpu.ops.als import topk_dot_batch

                z = np.zeros((1, y.shape[1]), dtype=np.float32)
                vals, idx = topk_dot_batch(z, y, k=1)
                np.asarray(idx)
                ok = True
            except Exception:
                log.info("device probe failed; staying on host path")
            with self._lock:
                self._probing = False
                self._probe_at = time.monotonic() + self.probe_interval
                if ok and self._device_down.is_set():
                    log.warning("device probe succeeded — resuming device path")
                    self._device_down.clear()
                    if self._down_since:
                        # the whole down window was device idle by fiat:
                        # charge it to failover_backoff in the next gap
                        self._gap_down += time.monotonic() - self._down_since
                        self._down_since = 0.0

        threading.Thread(
            target=probe, name="oryx-topk-probe", daemon=True
        ).start()
