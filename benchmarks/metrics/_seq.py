"""Shared by the readers of the seq stepper's counters (serving/stepper.py):
deltas over the window, by kind of dispatch."""

KINDS = ("prefill", "denoise")


def steps(src, kind):
    return (src.get("counters") or {}).get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0)


def tokens(src, kind, which):
    c = src.get("counters") or {}
    return c.get(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="{which}"}}', 0.0)


def all_steps(src):
    return sum(steps(src, k) for k in KINDS)


def all_tokens(src, which):
    return sum(tokens(src, k, which) for k in KINDS)
