"""Shared helpers for the per-app end-to-end lambda-slice suites."""

import urllib.error
import urllib.request


def http_request(method, url, body=None, accept="application/json"):
    req = urllib.request.Request(
        url, method=method, data=body, headers={"Accept": accept}
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class WedgeHook:
    """Monkeypatch target simulating a wedged device transport: blocks
    topk_dot_batch until released, then delegates to the real kernel.

    block_first_only=True blocks just the first call (a transient wedge);
    False blocks every call until release (a dead transport)."""

    def __init__(self, real_fn, block_first_only=True, timeout=30):
        import threading

        self.release = threading.Event()
        self.calls = 0
        self._real = real_fn
        self._first_only = block_first_only
        self._timeout = timeout

    def __call__(self, xs, y, k, **kwargs):
        self.calls += 1
        if (self.calls == 1 or not self._first_only) and not self.release.is_set():
            self.release.wait(timeout=self._timeout)
        return self._real(xs, y, k=k, **kwargs)


def region_tiling(spans, tid, top_level):
    """How well one thread's regions tile, from the tracer ring's spans.

    -> (covered, by_parent): `covered` is the summed wall of the thread's
    top-level regions (names in `top_level`) after the first one, over the
    thread's time from the first one's exit to the last one's; `by_parent`
    is {parent name: summed wall of its children / its own summed wall} for
    every region of the thread that had children."""
    mine = sorted((s for s in spans if s.tid == tid), key=lambda s: s.start)
    top = [s for s in mine if s.name in top_level]
    assert all(s.parent is None for s in top), "a top-level region opened inside another"
    covered = sum(s.duration for s in top[1:]) / (top[-1].end - top[0].end)
    own, kids = {}, {}
    for s in mine:
        if s.parent is not None:
            kids[s.parent.name] = kids.get(s.parent.name, 0.0) + s.duration
    for s in mine:
        if s.name in kids:
            own[s.name] = own.get(s.name, 0.0) + s.duration
    return covered, {name: kids[name] / own[name] for name in kids}
