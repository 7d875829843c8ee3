"""Device: 1 - union of device-op intervals over the traced window, in
percent, from the profiler trace."""


def read(src):
    trace = src.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
