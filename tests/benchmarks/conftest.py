"""Isolation for the tests of tests/benchmarks/ that run a kind in this process.

Kind seq-serving holds its counters' invariants over the TOTALS since the
process began (benchmarks/kinds/seq_serving.py `invariants`: every real token
through every expert layer's k experts, every block its denoise steps). In a
run of its own the stepper's counters start at zero; in a pytest worker that
has already served another encoder (an end-to-end test of a decoder counts its
prefills' tokens and pairs under the same series) the totals mix two models'
experts a token and layers, and a sound run reads pairs "dropped". Each test of
that file starts from those counters as a fresh process has them, and what was
counted before is put back after it."""

import pytest

from oryx_tpu.common.metrics import get_registry

# the series kind seq-serving reads as totals since the process began
_TOTALS = (
    "oryx_seq_steps_total", "oryx_seq_step_tokens_total", "oryx_seq_blocks_total",
    "oryx_seq_denoise_steps_total", "oryx_moe_routed_total",
)


@pytest.fixture(autouse=True)
def _counters_of_a_fresh_process(request, monkeypatch):
    if request.module.__name__.rpartition(".")[2] == "test_seq_serving":
        reg = get_registry()
        for name in _TOTALS:
            monkeypatch.setattr(reg.counter(name), "_values", {})
    yield
