"""What the session app's encoder kinds share (`seq-serving`, `ssm-serving`,
`joyai-serving`; not a kind itself: no configuration names it): the ONE `run`
of a cell of `/recommend-next` through ServingLayer over HTTP, one process
holding the chip, load from a generator process (benchmarks/seqgen.py).

A kind file keeps what is its architecture's and hands it over as a `Kind`:
the model from the seed (`build`), its plain reference and the comparison of
the sampled answers (`check`, `summarise` with its limits), the invariants of
its own counters (`invariants`), the programs it runs by their names on the
device trace (`programs`, `compiled_texts`) and the scopes inside them.
Everything else is here: the server around the model, the probes, the
warm-up, the window, the collector's pauses, the scrape's deltas, the trace's
reduction, the sample asked again, the entries of `compared` every encoder
cell holds, the notes.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

import numpy as np

from benchmarks import latency, seqgen, seqtrace, timeline, xplane
from benchmarks.kinds.als_serving import _get, _sleep_until, queued_ahead_share, scrape

CHECK_REQUESTS = 32
WARM_MIN_S = 5.0
WARM_CYCLES = 5
TRACE_MAX_S = 12.0
# a device gap is named by the thread that feeds the device: the stepper's
# regions tile its thread's life (idle, pick, prefill, step, fetch,
# distribute), so a gap says whether the device waited for an arrival or for
# the host's turn; the batcher's where no stepper region covers it
PREFER = ("stepper.", timeline.REGION_PREFIX)
READING_KEYS = ("score_err", "rounding", "stated_err", "fixed_gap", "overlap", "candidate_gap")


@dataclass(frozen=True)
class Kind:
    """What one encoder kind brings to the shared `run`."""

    name: str                      # the prefix of its lines on stderr
    programs: dict[str, str]       # {the stepper's kind of dispatch: the program's name on the device trace}
    scopes: tuple[str, ...]        # the named scopes inside the programs, first match wins
    build: Callable                # (cell, seed, info) -> (serving, manager, state, e_host)
    check: Callable                # (config, traffic, state, e_host, served) -> (readings, reference forwards)
    summarise: Callable            # (readings, dtype) -> entries of `compared`
    invariants: Callable           # (config, final, started, sent, timed_out) -> entries of `compared`
    compiled_texts: Callable       # (model) -> {program name: [compiled text, ...]}
    position: str                  # what one reading of an answer is called: a "block" or a "basket" position
    reserved_ids: int = 0          # ids at the vocabulary's end that are no item ([MASK])
    slot_states: tuple[str, ...] = ()  # the kinds of `oryx_seq_slot_state_bytes{state}` it reports


def holds(compared: dict) -> list[str]:
    """The names of the compared numbers that break their limit (a number
    that could not be read breaks it)."""
    ops = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b, "==": lambda a, b: a == b}
    return [
        name for name, (value, how, limit) in compared.items()
        if value is None or not ops[how](value, limit)
    ]


def summarise(
    per_request: list[list[dict]], score_tight: float, stated_tight: float, score_loose: float,
    min_overlap: int, min_overlap_worst: int,
) -> dict:
    """The compared numbers of a kind's readings over the sampled requests,
    under that kind's limits: by position of the answer the quartile over the
    requests (the worst position's is reported), and the worst reading of all."""
    flat = [o for req in per_request for o in req]
    positions = len(per_request[0]) if per_request else 0

    def by_position(key, q, pick):
        read = []
        for b in range(positions):
            values = [req[b][key] for req in per_request if req[b][key] is not None]
            if values:
                read.append(float(np.percentile(values, q)))
        return pick(read) if read else None

    def worst(key, pick):
        values = [o[key] for o in flat if o[key] is not None]
        return pick(values) if values else None

    out = {"malformed_answers": [sum(1 for o in flat if o["fault"]), "==", 0]}
    if worst("stated_err", max) is not None:  # a configuration that states a rounding
        out["stated_err_quartile"] = [by_position("stated_err", 25, max), "<=", stated_tight]
    out.update(
        score_err_quartile=[by_position("score_err", 25, max), "<=", score_tight],
        score_err_worst=[worst("score_err", max), "<=", score_loose],
        fixed_gap_worst=[worst("fixed_gap", max), "<=", score_loose],
        candidate_gap_worst=[worst("candidate_gap", max), "<=", score_loose],
        overlap_quartile=[by_position("overlap", 25, min), ">=", min_overlap],
        overlap_worst=[worst("overlap", min), ">=", min_overlap_worst],
    )
    return out


def serve(cell: dict, state):
    """The server around an adopted model, started: the program as it ships,
    default reference.conf plus what a read-only server on mem:// brokers
    with port 0 needs. (serving, manager); the caller closes `serving`."""
    import jax

    from oryx_tpu.apps.seq.serving import SeqServingModel, SeqServingModelManager
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://bench"
    overlay = {
        "oryx.id": "bench",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.seq",
        ],
        "oryx.monitoring.flight.dir": str(Path(cell["scratch"]) / "flight"),
    }
    if jax.devices()[0].platform == "tpu":
        overlay["oryx.compute.platform"] = "tpu"
    cfg = load_config(overlay=overlay)
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = SeqServingModelManager(cfg)
    manager.model = SeqServingModel(state, sync=manager.sync)
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    return serving, manager


def _ratio(delta: dict, kind: str) -> float | None:
    n = delta.get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0)
    real = delta.get(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}', 0.0)
    return real / n if n else None


def run(kind: Kind, cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    import jax

    from oryx_tpu.common.perfstats import get_perfstats

    config, traffic = cell["config"], cell["traffic"]
    n_items = config["vocab_size"] - kind.reserved_ids
    # the generator's names for the answer's positions and its steps
    # (benchmarks/seqgen.py); a configuration of a basket says `basket` for both
    for key in ("block_length", "denoise_steps"):
        if traffic[key] != config.get(key, config.get("basket")):
            raise ValueError(f"traffic and configuration disagree on {key}")
    serving, manager, state, e_host = kind.build(cell, seed, info)

    # the cyclic collector stops every thread of the server while it runs:
    # time each collection (gc_pause_share)
    collections: list[tuple[float, float]] = []  # (monotonic start, seconds)

    def on_gc(phase: str, _info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            collections.append((now, 0.0))
        else:
            collections[-1] = (collections[-1][0], now - collections[-1][0])

    gc.callbacks.append(on_gc)
    base = f"http://127.0.0.1:{serving.port}"
    gen = None
    try:
        started = scrape(base)  # before this run's first request
        # -- warm-up, part 1: one request uploads the view and compiles (or
        # loads) every shape of the encoder and the scan's; a second, alone,
        # times one request
        t_prime = time.monotonic()
        probe = seqgen.draw_sessions(seed + 1, n_items, traffic, 2)
        for attempt, session in zip(("first", "cycle"), probe):
            t_req = time.monotonic()
            status, body = _get(f"{base}{seqgen.session_path(traffic, session)}")
            if status != 200:
                raise RuntimeError(f"priming request -> {status}: {body[:200]!r}")
            cycle_s = time.monotonic() - t_req
            info(phase=f"prime_{attempt}", seconds=cycle_s)
        warm_s = float(math.ceil(max(WARM_MIN_S, WARM_CYCLES * cycle_s)))
        spec = {
            "port": serving.port, "seed": seed, "traffic": traffic, "items": n_items,
            "seconds": seconds, "warm_s": warm_s,
        }
        gen = subprocess.Popen(
            [sys.executable, seqgen.__file__, json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "JAX_PLATFORMS")},
        )
        if gen.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not start")

        # -- warm-up, part 2: the cell's own traffic, then the window
        t0 = time.monotonic() + 0.25
        gen.stdin.write(json.dumps({"t0": t0}) + "\n")
        gen.stdin.flush()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        _sleep_until(t_open)
        setup_s = time.time() - t_process
        before = scrape(base)
        trace_out = timeline_out = found = None
        if trace:
            trace_dir = Path(cell["scratch"]) / "trace" / cell["name"]  # the cell's own: see xplane.find_xplane
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            _sleep_until(t_open + 0.25)
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            _sleep_until(min(time.monotonic() + TRACE_MAX_S, t_close - 0.5))
            jax.profiler.stop_trace()
            found = xplane.find_xplane(trace_dir)
            if found:
                trace_out = xplane.reduce_trace(found, prefer=PREFER)
                timeline_out = timeline.parse(found)
        _sleep_until(t_close)
        after = scrape(base)
        ring = get_perfstats().records_since(t_open - 1.0)
        records = [r for r in ring if t_open <= r.t_start < t_close]
        pauses = [s for t, s in collections if t_open <= t < t_close]
        out, _ = gen.communicate(timeout=seconds + 240)
        result = json.loads(out.strip().splitlines()[-1])
        gen = None

        # -- correctness, outside the timing: sampled requests of the window,
        # asked again together, each answer against the kind's reference
        good, attempted, failed = latency.window_latencies(result)
        n_requests = len(result["due"])
        sessions = seqgen.draw_sessions(seed, n_items, traffic, n_requests)
        in_window = np.flatnonzero(np.asarray(result["in_window"], dtype=bool))
        rng = np.random.default_rng([int(seed), 3])
        sample = rng.choice(in_window, size=min(CHECK_REQUESTS, len(in_window)), replace=False).tolist()
        with ThreadPoolExecutor(len(sample)) as pool:
            answers = list(pool.map(
                lambda i: _get(f"{base}{seqgen.session_path(traffic, sessions[i])}"), sample
            ))
        faults, served = [], []
        for i, (status, body) in zip(sample, answers):
            if status != 200:
                faults.append(f"request {i}: status {status}")
            else:
                served.append((json.loads(body), sessions[i]))
        t_ref = time.monotonic()
        readings, forwards = kind.check(config, traffic, state, e_host, served)
        info(phase="reference", seconds=time.monotonic() - t_ref, forwards=forwards, readings=[
            [[None if o[k] is None else round(o[k], 6) for k in READING_KEYS] for o in req] for req in readings
        ])
        for req in readings:
            faults += [f"a {kind.position} position: {o['fault']}" for o in req if o["fault"]]
        final = scrape(base)
        wrong_bodies = sum(
            n for error, n in result["errors"].items()
            if error in ("unparsable", "wrong_block", "wrong_count", "known_item")
        )
        delta = {s: after[s] - before.get(s, 0.0) for s in after}
        compiles = sum(v for s, v in delta.items() if s.startswith("oryx_xla_compiles_total"))
        # every session sent: the probes, the generator's, the sample asked again
        sent = list(probe) + sessions + [sessions[i] for i in sample]
        timed_out = sum(n for error, n in result["errors"].items() if error == "timeout")
        compared = dict(
            {"requests_compared": [len(readings), "==", len(sample)]},
            **kind.summarise(readings, config["dtype"]),
            wrong_bodies_in_window=[wrong_bodies, "==", 0],
            compiles_in_window=[compiles, "==", 0],
            # the kind's own counters over the whole run, read when nothing is in flight
            **kind.invariants(config, final, started, sent, timed_out),
            host_fallbacks=[delta.get("oryx_topk_host_fallbacks", 0.0), "==", 0],
            topk_shapes=[len({(r.padded_rows, r.k_bucket) for r in records}), "==", 1],
            dispatches_not_exact=[sum(1 for r in records if r.score_mode != "exact"), "==", 0],
            good_in_window=[len(good), ">=", 1],
        )
        faults += [f"{name} = {compared[name][0]} breaks its limit" for name in holds(compared)]
        for f in faults:
            print(f"{kind.name}: {f}", file=sys.stderr)

        steps_out = None
        if found:
            steps_out = seqtrace.split(seqtrace.parse(found), kind.compiled_texts(manager.model), kind.scopes)
        late = [ms for ms, w in zip(result["late_ms"], result["in_window"]) if w and ms is not None]
        if steps_out:
            info(phase="steps", steps=steps_out)
        notes = {f"{step}_tokens_per_step": _ratio(delta, step) for step in kind.programs}
        if kind.slot_states:
            notes["slot_state_bytes"] = {
                s: final.get(f'oryx_seq_slot_state_bytes{{state="{s}"}}') for s in kind.slot_states
            }
        info(
            generator_processes=1, connections_opened=result["connections_opened"],
            errors=result["errors"], warm_s=warm_s,
            in_flight_at_window_end=latency.in_flight_at(result, warm_s + seconds),
            prime_s=t_open - t_prime,
            gen_late_p95_ms=latency.percentile(late, 95) if late else None,
            latency_p95_ms=latency.percentile(good, 95) if good else None,
            collector_pauses_s=[round(s, 4) for s in pauses if s > 0.05],
            dispatches=len(records),
            rows_per_dispatch=sum(r.rows for r in records) / len(records) if records else None,
            shapes=sorted({(r.padded_rows, r.k_bucket) for r in records}),
            queued_ahead_share=queued_ahead_share(records),
            encoder_steps=sum(delta.get(f'oryx_seq_steps_total{{kind="{step}"}}', 0.0) for step in kind.programs),
            **notes,
        )
    finally:
        gc.callbacks.remove(on_gc)
        if gen is not None:
            gen.kill()
            gen.wait()
        serving.close()

    return {
        "correct": not faults and bool(good),
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "end_to_end": {"p50_ms": latency.percentile(good, 50) if good else None},
        "sources": {
            "config": config,
            "traffic": traffic,
            "counters": delta,
            "dispatch_records": [
                {"rows": r.rows, "padded_rows": r.padded_rows, "k_bucket": r.k_bucket}
                for r in records
            ],
            "generator": {"late_ms": late, "latency_ms": good},
            "collector": {"window_s": seconds, "pauses_s": pauses},
            "trace": trace_out,
            "timeline": timeline_out,
            # the traced window's device time by encoder program and scope
            "steps": steps_out,
        },
        "compared": compared,
    }
