"""Shared by the PhaseLedger readers: phase seconds per request."""


def per_request_ms(src, phases):
    c = src.get("counters") or {}
    # requests answered through the device in the window
    n = c.get('oryx_request_phase_seconds_count{phase="device"}', 0.0)
    if not n:
        return None
    total = sum(
        c.get(f'oryx_request_phase_seconds_sum{{phase="{p}"}}', 0.0) for p in phases
    )
    return total / n * 1e3
