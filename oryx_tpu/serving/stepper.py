"""Batched encoder step for the seq app: the second kind of device step.

`TopKBatcher` has one kind of step, a top-k scan. A session encoder needs
another before it: a PREFILL over the session's 15-100 events and, for an
encoder that generates (ops/sdar.py), a few STEPS over a block of positions
that read the prefill's keys and values. Both are far too heavy to run once
a request in the request's thread (a pass streams the model's weights), so
requests are admitted to cache SLOTS on the device and share dispatches:

    cycle:  pick      take what was submitted; admit waiting requests to
                      free slots (at most `prefill_rows`, in arrival order)
            prefill   ONE prefill dispatch (the admitted sessions, padded to
                      the length bucket of the longest)
            step      ONE step dispatch (every block in flight, whatever
                      its step: those admitted this cycle take step 0)
            fetch     block on the PREVIOUS cycle's results (depth-1
                      pipeline, as TopKBatcher._run: the fetch of cycle N
                      overlaps the device work of cycle N+1)
            distribute  finished requests' hidden rows to their futures

These are the thread's regions too (`stepper.idle` is its wait with nothing
to step), and they tile its life (docs/observability.md).

A request's state never visits the host between steps: the host counts the
steps it launched (a block takes exactly `encoder.steps`) and fetches a
block's rows with the dispatch of its last step. What the host builds for a
dispatch (`pack`'s arrays; a step's slots, lengths, live and step, fresh
numpy arrays every cycle) reaches the device as arguments of the jitted call
itself: `stepper.prefill.call` and `stepper.step.call` transfer the
dispatch's operands, and nothing is uploaded before them. The GRU is a
prefill with no steps. The encoder is reached through the seam of ops/seq.py
only.

Shapes are few and fixed (`prefill_rows` x each length bucket, `step_rows`
blocks) and all compile before an engine's first request (`warm`); an
answer does not depend on what shared its dispatches (every row of every
product is its own).

Counters (docs/observability.md): steps and their real and padded tokens by
kind, blocks, denoising steps, slots in use; the expert layer's routed
pairs, experts touched, busiest expert's pairs and (for a layer that holds a
share of its experts) the pairs sent elsewhere are counted ON THE DEVICE by
the step itself (ops/moe.py) and fetched with its result, and so are a
hyper-connected model's Sinkhorn tallies (ops/xing.py): its largest error
since the last scrape and its matrices left unconverged.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import NamedTuple

import numpy as np

from oryx_tpu.common.metrics import get_registry
from oryx_tpu.common.perfattr import current_ledger, get_perfattr
from oryx_tpu.common.tracing import get_tracer, name_thread
from oryx_tpu.serving.futureutil import try_set_exception, try_set_result

log = logging.getLogger(__name__)

_TRACER = get_tracer()
_PA = get_perfattr()


class Encoded(NamedTuple):
    """What the encoder hands the catalog scan for one request."""

    hidden: np.ndarray            # [block, d] float32, one row a position
    rows: np.ndarray | None       # [block] view rows fixed (None: none fixed)
    steps: np.ndarray | None      # [block] the step that fixed each


class _Req:
    __slots__ = (
        "prepared", "length", "future", "ledger", "t_enq", "t_pick",
        "t_first", "slot", "step",
    )

    def __init__(self, prepared, length: int, future: Future):
        self.prepared = prepared
        self.length = length
        self.future = future
        self.ledger = current_ledger()
        self.t_enq = time.monotonic()
        self.t_pick = self.t_first = 0.0
        self.slot = -1
        self.step = 0


class Engine:
    """One model generation's encoder on the device: its parameters, the
    slots' state and who holds them. Made by the serving model; after that
    the stepper's thread alone touches state, slots and lists."""

    def __init__(self, encoder, params, head=None):
        self.encoder = encoder
        self.params = encoder.device_params(params)
        # () -> what `encoder.step` takes as `head` (the catalog view the
        # step's logits are over); None for an encoder with no steps
        self.head = head
        self.slots = max(1, encoder.step_rows)
        self.state = None
        self.free = list(range(self.slots))[::-1]
        self.waiting: deque[_Req] = deque()
        self.active: list[_Req] = []
        self.warmed = False

    @property
    def idle(self) -> bool:
        return not self.waiting and not self.active


# what a dispatch tallies on the device, by name, in a prefill's third result
# and in a step's `out` alike, where the model makes it: "counts" int32[3] or
# [4] (ops/moe.py); "hc_error" float32[] the largest Sinkhorn error over its
# real tokens and "hc_unconverged" int32[] its matrices left unconverged
# (ops/xing.py)
TALLIES = ("counts", "hc_error", "hc_unconverged")


def _copy_tallies(result: dict) -> None:
    for name in TALLIES:
        if name in result:
            result[name].copy_to_host_async()


class _LargestSinceScrape:
    """A gauge's value that is the largest observed since it was last read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._largest = 0.0  # guarded-by: _lock

    def observe(self, value: float) -> None:
        with self._lock:
            self._largest = max(self._largest, value)

    def read(self) -> float:
        with self._lock:
            value, self._largest = self._largest, 0.0
        return value


class _Metrics:
    def __init__(self):
        reg = get_registry()
        self.steps = reg.counter(
            "oryx_seq_steps_total",
            "Encoder dispatches of the seq stepper, by kind (prefill | denoise | decode)",
            labeled=True,
        )
        self.tokens = reg.counter(
            "oryx_seq_step_tokens_total",
            "Tokens of the seq stepper's dispatches, by kind and by tokens "
            "(real | padded: the dispatch's whole shape)",
            labeled=True,
        )
        self.head_rows = reg.counter(
            "oryx_seq_head_rows_total",
            "View rows of the catalog head's passes, one a step dispatch, by rows "
            "(walked: the blocks that hold a valid row | skipped: the capacity behind them)",
            labeled=True,
        )
        self.hc_tokens = reg.counter(
            "oryx_seq_hc_tokens_total",
            "Token slots of a hyper-connected model's sublayer boundaries, a dispatch's shape once a "
            "sublayer, by tokens (walked: the blocks that hold a live token | skipped: the rest)",
            labeled=True,
        )
        self.blocks = reg.counter(
            "oryx_seq_blocks_total", "Blocks the seq stepper finished generating"
        )
        self.denoise = reg.counter(
            "oryx_seq_denoise_steps_total",
            "Denoising steps taken by the blocks the seq stepper finished",
        )
        self.slots = reg.gauge(
            "oryx_seq_slots_in_use", "Cache slots of the seq stepper held by a request"
        )
        self.state_bytes = reg.gauge(
            "oryx_seq_slot_state_bytes",
            "Bytes of the seq stepper's cache slots on the device, by kind of state "
            "(recurrent: a fixed size a slot | kv, latent, rope_key, full_kv: a row a position | "
            "window_kv: a row a position up to the attention's window)",
            labeled=True,
        )
        self.routed = reg.counter(
            "oryx_moe_routed_total",
            "(token, expert) pairs the expert layers routed to an expert they hold, counted on the device",
        )
        self.elsewhere = reg.counter(
            "oryx_moe_routed_elsewhere_total",
            "(token, expert) pairs routed to an expert held on another chip (a layer told "
            "its share computes none of them), counted on the device",
        )
        self.touched = reg.counter(
            "oryx_moe_experts_touched_total",
            "Experts that received a token, summed over steps and layers",
        )
        self.busiest = reg.counter(
            "oryx_moe_expert_tokens_max_total",
            "The busiest expert's tokens, summed over steps and layers",
        )
        self.hc_error = _LargestSinceScrape()
        reg.gauge(
            "oryx_seq_hc_sinkhorn_error",
            "Largest |row or column sum - 1| of a hyper-connected model's mixing matrices after its "
            "Sinkhorn iterations, over the real tokens of the dispatches since the last scrape",
        ).set_function(self.hc_error.read)
        self.hc_unconverged = reg.counter(
            "oryx_seq_hc_unconverged_total",
            "A hyper-connected model's mixing matrices whose row or column sums miss 1 by more than "
            "1e-3 after its Sinkhorn iterations, over the real tokens and sublayers of its dispatches",
        )


class SeqStepper:
    """Coalesces session encodes into batched prefill and step dispatches."""

    _shared: "SeqStepper | None" = None
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls) -> "SeqStepper":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            return cls._shared

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: list[tuple[Engine, _Req]] = []
        self._thread: threading.Thread | None = None
        self._closed = False
        self._cycle_seq = itertools.count(1)
        self._compiled: set[tuple] = set()
        self._m = _Metrics()
        self.cycles = 0

    # -- submit ------------------------------------------------------------

    def submit(self, engine: Engine, prepared) -> Future:
        """One request's `encoder.prepare` output -> Future of `Encoded`."""
        fut: Future = Future()
        req = _Req(prepared, engine.encoder.length(prepared), fut)
        if req.ledger is not None:
            # routing + building the query since the last stamped phase
            tail = req.ledger.last_end()
            if tail is not None and tail < req.t_enq:
                req.ledger.add("parse", req.t_enq - tail, start=tail)
        with self._cond:
            if self._closed:
                raise RuntimeError("stepper is closed")
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="oryx-seq-stepper", daemon=True
                )
                self._thread.start()
            self._queue.append((engine, req))
            self._cond.notify()
        return fut

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=5)

    # -- the dispatcher thread ---------------------------------------------

    def _run(self) -> None:  # oryxlint: offloop (dedicated dispatcher thread)
        name_thread("oryx-seq")
        tr = _TRACER
        engines: dict[int, Engine] = {}
        inflight: list[tuple] = []
        while True:
            if not inflight and not engines:
                with tr.region("stepper.idle"), self._cond:
                    while not self._queue and not self._closed:
                        self._cond.wait()
            n = next(self._cycle_seq)
            with tr.region("stepper.pick", cpu=True, cycle=n):
                with self._cond:
                    if self._closed and not self._queue and not inflight and not engines:
                        return
                    queued, self._queue = self._queue, []
                for engine, req in queued:
                    engines[id(engine)] = engine
                    engine.waiting.append(req)
                picked = []
                for engine in list(engines.values()):
                    try:
                        picked.append((engine, self._admit(engine)))
                    except Exception as e:  # noqa: BLE001 - as in the launch below
                        log.exception("stepper warm-up failed")
                        self._fail(engine, e)
            launched = []
            for engine, admitted in picked:
                try:
                    item = self._cycle(engine, n, admitted)
                except Exception as e:  # noqa: BLE001 - fail the engine's requests, keep the thread
                    log.exception("stepper cycle failed")
                    self._fail(engine, e, admitted)
                    item = None
                if item is not None:
                    launched.append(item)
            for engine in list(engines.values()):
                if engine.idle:
                    del engines[id(engine)]
            for item in inflight:
                self._resolve(item)
            inflight = launched

    def _fail(self, engine: Engine, e: Exception, admitted: list = ()) -> None:
        # `admitted`: requests a failed cycle had taken off `waiting` and
        # (an encoder without steps) put nowhere else yet
        for req in [*admitted, *engine.waiting, *engine.active]:
            try_set_exception(req.future, e)
        engine.waiting.clear()
        engine.active.clear()
        engine.free = list(range(engine.slots))[::-1]
        engine.state = None  # a donated state may be gone: start over

    def _first_use(self, key: tuple, t0: float) -> None:
        """The first dispatch of a shape traces and compiles inside its
        call: feed the compile telemetry, as the batcher does."""
        if key not in self._compiled:
            self._compiled.add(key)
            _PA.record_compile("seq", time.monotonic() - t0)

    def warm(self, engine: Engine) -> None:
        """Compile every shape the engine will use, on padding rows alone
        (they write the scratch slot and reach no expert)."""
        enc = engine.encoder
        if enc.steps and engine.state is None:
            engine.state = enc.init_state(engine.slots)
            for kind, n_bytes in enc.state_bytes(engine.slots).items():
                self._m.state_bytes.set(n_bytes, state=kind)
        for bucket in enc.length_buckets:
            t0 = time.monotonic()
            packed = enc.pack([], bucket, [], engine.slots)
            engine.state, hidden, _ = enc.prefill(engine.params, engine.state, *packed)
            np.asarray(hidden)
            self._first_use((enc.name, "prefill", bucket, id(engine.params)), t0)
        if enc.steps:
            t0 = time.monotonic()
            pad = np.full((enc.step_rows,), engine.slots, dtype=np.int32)
            zero = np.zeros((enc.step_rows,), dtype=np.int32)
            engine.state, out = enc.step(
                engine.params, engine.state, engine.head(), pad, zero,
                np.zeros((enc.step_rows,), dtype=bool), zero,
            )
            np.asarray(out["row"])
            self._first_use((enc.name, "step", id(engine.params)), t0)
        engine.warmed = True

    def _admit(self, engine: Engine) -> list[_Req]:
        """The pick's part of one engine: waiting requests to free slots."""
        enc = engine.encoder
        if not engine.warmed:
            self.warm(engine)
        t_pick = time.monotonic()
        admitted: list[_Req] = []
        while (
            engine.waiting and len(admitted) < enc.prefill_rows
            and (not enc.steps or engine.free)
        ):
            req = engine.waiting.popleft()
            if enc.steps:
                req.slot = engine.free.pop()
            req.t_pick = t_pick
            admitted.append(req)
        return admitted

    def _cycle(self, engine: Engine, n: int, admitted: list[_Req]):
        """Launch this cycle's dispatches for what `_admit` admitted, return
        what `_resolve` needs (None when there was nothing to launch)."""
        enc = engine.encoder
        tr = _TRACER
        if not admitted and not engine.active:
            return None
        hidden = tallied = out = None
        finished: list[tuple[int, _Req]] = []
        if admitted:
            bucket = min(
                b for b in enc.length_buckets if b >= max(r.length for r in admitted)
            )
            real = sum(r.length for r in admitted)
            with tr.region(
                "stepper.prefill", cpu=True, cycle=n, rows=len(admitted),
                padded=enc.prefill_rows, tokens=real, bucket=bucket,
            ):
                t0 = time.monotonic()
                with tr.region("stepper.prefill.pack"):
                    packed = enc.pack(
                        [r.prepared for r in admitted], bucket,
                        [r.slot for r in admitted], engine.slots,
                    )
                with tr.region("stepper.prefill.call"):
                    engine.state, hidden, tallied = enc.prefill(
                        engine.params, engine.state, *packed
                    )
                with tr.region("stepper.prefill.copy"):
                    if not enc.steps:
                        hidden.copy_to_host_async()
                    _copy_tallies(tallied)
                    self._first_use((enc.name, "prefill", bucket, id(engine.params)), t0)
                    self._m.steps.inc(kind="prefill")
                    self._m.tokens.inc(real, kind="prefill", tokens="real")
                    self._m.tokens.inc(enc.prefill_rows * bucket, kind="prefill", tokens="padded")
                    if enc.steps:
                        engine.active.extend(admitted)
        if enc.steps and engine.active:
            rows = engine.active[: enc.step_rows]
            per_row = enc.step_tokens
            with tr.region(
                "stepper.step", cpu=True, cycle=n, kind=enc.step_kind, rows=len(rows),
                padded=enc.step_rows, tokens=len(rows) * per_row,
            ):
                t0 = time.monotonic()
                with tr.region("stepper.step.fill"):
                    slots = np.full((enc.step_rows,), engine.slots, dtype=np.int32)
                    lengths = np.zeros((enc.step_rows,), dtype=np.int32)
                    step = np.zeros((enc.step_rows,), dtype=np.int32)
                    live = np.zeros((enc.step_rows,), dtype=bool)
                    for i, r in enumerate(rows):
                        slots[i], lengths[i], step[i], live[i] = r.slot, r.length, r.step, True
                        r.step += 1
                with tr.region("stepper.step.call"):
                    engine.state, out = enc.step(
                        engine.params, engine.state, engine.head(), slots, lengths, live, step,
                    )
                with tr.region("stepper.step.copy"):
                    finished = [(i, r) for i, r in enumerate(rows) if r.step >= enc.steps]
                    _copy_tallies(out)
                    if finished:
                        for key in ("z", "row", "step"):
                            out[key].copy_to_host_async()
                    self._first_use((enc.name, "step", id(engine.params)), t0)
                    self._m.steps.inc(kind=enc.step_kind)
                    self._m.tokens.inc(len(rows) * per_row, kind=enc.step_kind, tokens="real")
                    self._m.tokens.inc(enc.step_rows * per_row, kind=enc.step_kind, tokens="padded")
                    if "head_rows" in out:
                        walked, skipped = out["head_rows"]
                        self._m.head_rows.inc(walked, rows="walked")
                        self._m.head_rows.inc(skipped, rows="skipped")
                    # a finished block's rows ride this dispatch's result: its
                    # slot is free for the next cycle's prefill (the device
                    # runs in order)
                    for _, r in finished:
                        engine.active.remove(r)
                        engine.free.append(r.slot)
        for result in (tallied or {}, out or {}):
            if "hc_tokens" in result:
                walked, skipped = result["hc_tokens"]
                self._m.hc_tokens.inc(walked, tokens="walked")
                self._m.hc_tokens.inc(skipped, tokens="skipped")
        self._m.slots.set(engine.slots - len(engine.free) if enc.steps else 0)
        self.cycles += 1
        return n, enc, admitted, hidden, tallied, finished, out

    def _resolve(self, item: tuple) -> None:
        n, enc, admitted, hidden_dev, tallied, finished, out = item
        tr = _TRACER
        try:
            with tr.region("stepper.fetch", cycle=n):
                # (routed here, touched, busiest[, routed elsewhere]) of ops/moe.py
                counts = np.zeros((4,), dtype=np.int64)
                hc_error, unconverged = None, 0
                for result in (tallied or {}, out or {}):
                    if "counts" in result:
                        c = np.asarray(result["counts"])
                        counts[: len(c)] += c
                    if "hc_error" in result:
                        hc_error = max(hc_error or 0.0, float(np.asarray(result["hc_error"])))
                    if "hc_unconverged" in result:
                        unconverged += int(np.asarray(result["hc_unconverged"]))
                hidden = np.asarray(hidden_dev) if not enc.steps else None
                if finished:
                    z, row, step = (np.asarray(out[k]) for k in ("z", "row", "step"))
                t_fetch = time.monotonic()
            with tr.region("stepper.distribute", cpu=True, cycle=n):
                if counts.any():
                    self._m.routed.inc(float(counts[0]))
                    self._m.touched.inc(float(counts[1]))
                    self._m.busiest.inc(float(counts[2]))
                    self._m.elsewhere.inc(float(counts[3]))
                if hc_error is not None:
                    self._m.hc_error.observe(hc_error)
                    self._m.hc_unconverged.inc(float(unconverged))
                for i, req in enumerate(admitted):
                    # its prefill (and its first step) ran in this cycle
                    req.t_first = t_fetch
                    if not enc.steps:
                        self._finish(req, t_fetch, Encoded(
                            np.asarray(hidden[i:i + 1], dtype=np.float32), None, None
                        ))
                for i, req in finished:
                    self._m.blocks.inc()
                    self._m.denoise.inc(req.step)
                    self._finish(req, t_fetch, Encoded(
                        np.asarray(z[i], dtype=np.float32), row[i].astype(np.int64),
                        step[i].astype(np.int64),
                    ))
        except Exception as e:  # noqa: BLE001 - a transfer error fails these requests only
            log.exception("stepper resolve failed")
            for req in admitted + [r for _, r in finished]:
                try_set_exception(req.future, e)

    @staticmethod
    def _finish(req: _Req, t_done: float, result: Encoded) -> None:
        led = req.ledger
        if led is not None:
            # `encode`: admission to the last hidden state on the host; its
            # parts are stages (they tile it, and join no budget of their own)
            led.add("encode", t_done - req.t_enq, start=req.t_enq)
            led.add_stage("encode_wait", req.t_pick - req.t_enq)
            led.add_stage("prefill", req.t_first - req.t_pick)
            led.add_stage("denoise", t_done - req.t_first)
        try_set_result(req.future, result)
