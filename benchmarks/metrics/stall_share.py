"""Python runtime: share of the window in which an event loop's heartbeat
was 100 ms or more overdue, in percent (`oryx_stall_seconds_total` over the
window's length): the stalls in which every request waits while the device
does what it always does. 0.0 in a run whose tail is ordinary; each stall
also leaves one `stall` line that says what the threads were in."""


def read(src):
    c = src.get("counters") or {}
    window = (src.get("collector") or {}).get("window_s")
    if "oryx_stall_seconds_total" not in c or not window:
        return None
    return c["oryx_stall_seconds_total"] / window * 100.0
