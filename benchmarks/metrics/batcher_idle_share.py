"""Batcher: share of the window the dispatcher's thread spent in
`batcher.idle`, its wait with nothing queued and nothing in flight, in
percent. With the other top-level regions (`pick`, `launch`, `fetch`,
`distribute`, `retire`) it tiles the thread's life."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.window_share(src, "batcher.idle")
