"""HTTP frontend: how late the event loops' 50 ms heartbeats fired, mean over
the window and the loops, in ms (`oryx_http_loop_lag_seconds{loop}`): the
time a callback that was ready waited for its loop's thread."""


def read(src):
    c = src.get("counters") or {}
    n = sum(v for s, v in c.items() if s.startswith("oryx_http_loop_lag_seconds_count{"))
    total = sum(v for s, v in c.items() if s.startswith("oryx_http_loop_lag_seconds_sum{"))
    return total / n * 1e3 if n else None
