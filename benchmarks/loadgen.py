"""Open-loop HTTP load generator: one OS process, one asyncio loop.

Everything a run sends is a pure function of (--seed, the traffic file, the
configuration's population): `draw_known`, `draw_schedule`. The process
never imports jax (a second process on the chip fails) and never waits for
a free connection: a request with no idle keep-alive connection opens one.

Run as a child by a kind module: it prints `READY`, reads one JSON line
`{"t0": <time.monotonic()>, "warm_s": <seconds>}` from stdin, sends the
schedule relative to t0, and prints one JSON object of per-request arrays
when every response has arrived or timed out. CLOCK_MONOTONIC is shared by the processes of
one machine, so due times, the server's dispatch records and the window
edges are on one clock.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import numpy as np

# the server closes a keep-alive connection idle for 30 s
# (serving/aserver.py READ_TIMEOUT); never reuse one that is near it
MAX_IDLE_S = 15.0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def draw_known(seed: int, population: dict, traffic: dict) -> list[np.ndarray]:
    """Known-item rows of each active user: the count drawn once, uniformly
    from the mix's range, the items distinct and uniform over the catalog."""
    rng = _rng(seed, 1)
    lo, hi = traffic["known_items"]
    n_items = population["items"]
    counts = rng.integers(lo, hi + 1, size=population["active_users"])
    return [rng.choice(n_items, size=int(c), replace=False) for c in counts]


def draw_arrivals(rng: np.random.Generator, rate: float, seconds: float) -> np.ndarray:
    """A Poisson process on [0, seconds) conditioned on its expected count:
    round(rate * seconds) uniform order statistics. Every seed sends the
    same number of requests; only their times differ."""
    n = int(round(rate * seconds))
    return np.sort(rng.random(n)) * seconds


def draw_schedule(
    seed: int, population: dict, traffic: dict, warm_s: float, seconds: float
) -> dict:
    """Due times (seconds after t0), the user of each request and whether it
    is due inside the measured window [warm_s, warm_s + seconds)."""
    rng = _rng(seed, 2)
    rate = float(traffic["rate_per_s"])
    warm = draw_arrivals(rng, rate, warm_s)
    window = warm_s + draw_arrivals(rng, rate, seconds)
    n_active = population["active_users"]
    # Zipf over the active users: rank r drawn with weight r**-s, ranks
    # mapped to users by a seeded permutation
    weights = np.arange(1, n_active + 1, dtype=np.float64) ** -float(traffic["zipf_s"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    n = len(warm) + len(window)
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n)), n_active - 1)
    users = rng.permutation(n_active)[ranks]
    return {
        "due": np.concatenate([warm, window]),
        "user": users,
        "in_window": np.concatenate(
            [np.zeros(len(warm), bool), np.ones(len(window), bool)]
        ),
    }


def check_body(body: bytes, how_many: int, known: set[int]) -> str | None:
    """None when the body is exactly how_many [item, score] pairs, none of
    them a known item; else what is wrong."""
    try:
        pairs = json.loads(body)
        rows = [int(p[0][1:]) for p in pairs]
    except (ValueError, TypeError, IndexError):
        return "unparsable"
    if len(rows) != how_many:
        return "wrong_count"
    if known.intersection(rows):
        return "known_item"
    return None


class _Client:
    def __init__(self, port: int):
        self.port = port
        self.idle: list[tuple[float, asyncio.StreamReader, asyncio.StreamWriter]] = []
        self.opened = 0

    async def _conn(self):
        now = time.monotonic()
        while self.idle:
            t_used, reader, writer = self.idle.pop()  # LIFO: the hot ones stay hot
            if now - t_used < MAX_IDLE_S and not reader.at_eof():
                return reader, writer
            writer.close()
        self.opened += 1
        return await asyncio.open_connection("127.0.0.1", self.port)

    async def get(self, request: bytes) -> tuple[float, int, bytes]:
        """(monotonic time of the send, status, body)."""
        reader, writer = await self._conn()
        t_send = time.monotonic()
        try:
            writer.write(request)
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head[9:12])
            length = 0
            for line in head.split(b"\r\n")[1:]:
                if line[:15].lower() == b"content-length:":
                    length = int(line[15:])
            body = await reader.readexactly(length) if length else b""
        except BaseException:
            writer.close()
            raise
        self.idle.append((time.monotonic(), reader, writer))
        return t_send, status, body


async def _drive(port: int, t0: float, sched: dict, known: list, traffic: dict) -> dict:
    client = _Client(port)
    timeout_s = float(traffic["timeout_s"])
    how_many = int(traffic["how_many"])
    path = traffic["path"]
    due = sched["due"]
    n = len(due)
    late_ms = np.full(n, np.nan)
    latency_ms = np.full(n, np.nan)
    done_at = np.full(n, np.nan)
    errors: dict[str, int] = {}

    async def fire(i: int) -> None:
        user = int(sched["user"][i])
        request = (
            f"GET {path.format(user=user)} HTTP/1.1\r\nHost: bench\r\n"
            "Accept: application/json\r\n\r\n"
        ).encode()
        try:
            t_send, status, body = await asyncio.wait_for(client.get(request), timeout_s)
            t_done = time.monotonic()
            late_ms[i] = (t_send - (t0 + due[i])) * 1e3
            done_at[i] = t_done - t0
            wrong = (
                f"status_{status}" if status != 200
                else check_body(body, how_many, set(known[user].tolist()))
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
    traffic, population = spec["traffic"], spec["population"]
    known = draw_known(spec["seed"], population, traffic)
    print("READY", flush=True)
    start = json.loads(sys.stdin.readline())
    sched = draw_schedule(
        spec["seed"], population, traffic, start["warm_s"], spec["seconds"]
    )
    out = asyncio.run(_drive(spec["port"], start["t0"], sched, known, traffic))
    # NaN (a failed request) is not JSON: null
    print(json.dumps(out).replace("NaN", "null"), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
