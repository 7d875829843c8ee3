"""Batched encoder step, kind xing-serving: mean device time of one dispatch
of the xing programs (`jit_prefill`, `jit_decode_step`), weighted by their
counts, from the traced window's `XLA Modules` events (benchmarks/seqtrace.py).
The reader is `ssm_step_ms`'s (the kinds' programs carry the same names; each
program's own mean goes to stderr under that name)."""

from benchmarks.metrics.ssm_step_ms import read  # noqa: F401
