"""Open-loop generator of kind `seq-serving`: each request a fresh session.

The sibling of loadgen.py for `GET /recommend-next/{items}`: the same
client, clock and arrivals (imported from it), another draw and another
body. Everything a run sends is a pure function of (--seed, the traffic
file, the number of item ids): `draw_sessions`, `draw_schedule`. The
process never imports jax.

Run as a child by kinds/seq_serving.py with loadgen.py's protocol, except
that the warm-up's length comes with the spec (the sessions are drawn before
`READY`): prints `READY`, reads `{"t0"}`, sends, prints one JSON object of
per-request arrays (the keys loadgen.py prints, so latency.py reads both).
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

if __name__ == "__main__":  # run as a script: the repo's root has to be on the path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.loadgen import _Client, _rng, draw_arrivals  # noqa: E402


def draw_lengths(rng: np.random.Generator, traffic: dict, n: int) -> np.ndarray:
    """Session lengths: log-normal with the mix's median and sigma, rounded
    and clipped to its range."""
    lo, hi = traffic["events"]
    raw = rng.lognormal(np.log(float(traffic["events_median"])), float(traffic["events_sigma"]), size=n)
    return np.clip(np.rint(raw), lo, hi).astype(np.int64)


def draw_sessions(seed: int, n_items: int, traffic: dict, n: int) -> list[np.ndarray]:
    """`n` sessions (item rows, oldest first): the length from
    `draw_lengths`, the items DISTINCT within a session and Zipf(`zipf_s`)
    over the `n_items` item ids (rank r drawn with weight r**-s, ranks
    mapped to items by a seeded permutation). Session i does not depend
    on `n`."""
    lengths = draw_lengths(_rng(seed, 21), traffic, n)
    rng = _rng(seed, 23)
    weights = np.arange(1, n_items + 1, dtype=np.float64) ** -float(traffic["zipf_s"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    item_of_rank = _rng(seed, 24).permutation(n_items)
    sessions = []
    for length in lengths.tolist():
        picked: list[int] = []
        seen: set[int] = set()
        while len(picked) < length:
            ranks = np.minimum(np.searchsorted(cdf, rng.random(2 * length)), n_items - 1)
            for r in ranks.tolist():
                if r not in seen:
                    seen.add(r)
                    picked.append(r)
                    if len(picked) == length:
                        break
        sessions.append(item_of_rank[np.asarray(picked, dtype=np.int64)])
    return sessions


def draw_schedule(seed: int, traffic: dict, warm_s: float, seconds: float) -> dict:
    """Due times (seconds after t0) and whether each request is due inside
    the measured window [warm_s, warm_s + seconds). Request i sends session
    i of `draw_sessions(seed, ..., n=len(due))`."""
    rng = _rng(seed, 22)
    rate = float(traffic["rate_per_s"])
    warm = draw_arrivals(rng, rate, warm_s)
    window = warm_s + draw_arrivals(rng, rate, seconds)
    return {
        "due": np.concatenate([warm, window]),
        "in_window": np.concatenate([np.zeros(len(warm), bool), np.ones(len(window), bool)]),
    }


def session_path(traffic: dict, session: np.ndarray) -> str:
    return traffic["path"].format(items="/".join(f"i{r}" for r in session.tolist()))


def check_body(body: bytes, traffic: dict, session: set[int]) -> str | None:
    """None when the body is one entry a block position, each {"item",
    "step", "next": how_many [item, score] pairs, none of them an item of
    the session}, the steps a permutation of 0..steps-1; else what is wrong."""
    block, steps, how_many = traffic["block_length"], traffic["denoise_steps"], traffic["how_many"]
    try:
        entries = json.loads(body)
        fixed = [int(e["item"][1:]) for e in entries]
        order = sorted(int(e["step"]) for e in entries)
        pages = [[int(p[0][1:]) for p in e["next"]] for e in entries]
    except (ValueError, TypeError, IndexError, KeyError):
        return "unparsable"
    if len(fixed) != block or order != list(range(steps)):
        return "wrong_block"
    if any(len(page) != how_many for page in pages):
        return "wrong_count"
    if any(session.intersection(page) for page in pages):
        return "known_item"
    return None


async def _drive(port: int, t0: float, sched: dict, sessions: list, traffic: dict) -> dict:
    client = _Client(port)
    timeout_s = float(traffic["timeout_s"])
    due = sched["due"]
    n = len(due)
    late_ms = np.full(n, np.nan)
    latency_ms = np.full(n, np.nan)
    done_at = np.full(n, np.nan)
    errors: dict[str, int] = {}

    async def fire(i: int) -> None:
        request = (
            f"GET {session_path(traffic, sessions[i])} HTTP/1.1\r\nHost: bench\r\n"
            "Accept: application/json\r\n\r\n"
        ).encode()
        try:
            t_send, status, body = await asyncio.wait_for(client.get(request), timeout_s)
            t_done = time.monotonic()
            late_ms[i] = (t_send - (t0 + due[i])) * 1e3
            done_at[i] = t_done - t0
            wrong = (
                f"status_{status}" if status != 200
                else check_body(body, traffic, set(sessions[i].tolist()))
            )
        except asyncio.TimeoutError:
            wrong = "timeout"
        except (OSError, asyncio.IncompleteReadError, ValueError) as e:
            wrong = type(e).__name__
        if wrong is None:
            latency_ms[i] = (t_done - (t0 + due[i])) * 1e3
        else:
            errors[wrong] = errors.get(wrong, 0) + 1

    tasks = []
    for i in range(n):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(asyncio.ensure_future(fire(i)))
    await asyncio.gather(*tasks)
    return {
        "due": due.tolist(),
        "in_window": sched["in_window"].tolist(),
        "late_ms": late_ms.tolist(),
        "latency_ms": latency_ms.tolist(),
        "done_at": done_at.tolist(),
        "errors": errors,
        "connections_opened": client.opened,
    }


def main(argv: list[str]) -> int:
    spec = json.loads(argv[0])
    traffic = spec["traffic"]
    sched = draw_schedule(spec["seed"], traffic, spec["warm_s"], spec["seconds"])
    sessions = draw_sessions(spec["seed"], spec["items"], traffic, len(sched["due"]))
    print("READY", flush=True)
    start = json.loads(sys.stdin.readline())
    out = asyncio.run(_drive(spec["port"], start["t0"], sched, sessions, traffic))
    print(json.dumps(out).replace("NaN", "null"), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
