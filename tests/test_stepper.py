"""The seq stepper's thread on a stubbed encoder (no device): its regions
tile its life, as the top-k dispatcher's do (tests/test_batcher.py)."""

import time

import numpy as np

from oryx_tpu.serving.stepper import Engine, SeqStepper


class _Dev:
    """A device result: a copy can be started, and reading it takes 1 ms."""

    def __init__(self, value):
        self._value = np.asarray(value)

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.001)
        return self._value


class _StubEncoder:
    """An encoder that generates: a prefill and `steps` steps a request, each
    call a 2 ms sleep (the host's part of a dispatch)."""

    name = "stub"
    steps = 4
    step_kind = "denoise"
    step_tokens = 2
    step_rows = 4
    prefill_rows = 2
    length_buckets = (8,)

    def length(self, prepared):
        return len(prepared)

    def device_params(self, params):
        return params

    def init_state(self, slots):
        return {"slots": slots}

    def state_bytes(self, slots):
        return {"kv": 8 * slots}

    def pack(self, prepared, bucket, slots, n_slots):
        return (np.zeros((self.prefill_rows, bucket), np.int32),)

    def prefill(self, params, state, tokens):
        time.sleep(0.002)
        return state, _Dev(np.zeros((self.prefill_rows, 3), np.float32)), None

    def step(self, params, state, head, slots, lengths, live, step):
        time.sleep(0.002)
        n = self.step_rows
        return state, {
            "z": _Dev(np.ones((n, self.step_tokens, 3), np.float32)),
            "row": _Dev(np.zeros((n, self.step_tokens), np.int32)),
            "step": _Dev(np.zeros((n, self.step_tokens), np.int32)),
        }


def test_the_steppers_regions_tile_its_life():
    """Over fifty cycles of a stub that sleeps 2 ms a call: the top-level
    regions (idle, pick, prefill, step, fetch, distribute) cover the thread's
    time to within 5 %, and each launch's children cover the launch."""
    from e2e_common import region_tiling

    from oryx_tpu.common.tracing import get_tracer, region_totals

    tr = get_tracer()
    tr.configure(enabled=True, capacity=8192)
    tr.clear()
    before = region_totals()
    stepper = SeqStepper()
    engine = Engine(_StubEncoder(), {}, head=lambda: None)
    try:
        for i in range(14):
            got = stepper.submit(engine, [1, 2, 3]).result(timeout=30)
            assert got.hidden.shape == (2, 3) and got.rows.shape == (2,)
            if i % 5 == 4:
                time.sleep(0.01)  # let it reach its idle wait now and then
        tid = stepper._thread.ident
    finally:
        stepper.close()
        spans = tr.snapshot()
        tr.configure(enabled=False, capacity=2048)
    after = region_totals()

    def moved(name, i=2):
        return after[name][i] - before.get(name, (0.0, 0.0, 0))[i]

    assert moved("stepper.prefill") == 14 and moved("stepper.step") == 14 * 4
    assert stepper.cycles >= 50
    top = {
        "stepper.idle", "stepper.pick", "stepper.prefill", "stepper.step",
        "stepper.fetch", "stepper.distribute",
    }
    # the engine's warm-up (its compiles, once) is inside the first pick
    covered, by_parent = region_tiling(spans, tid, top)
    assert 0.95 <= covered <= 1.0001, covered
    assert set(by_parent) == {"stepper.prefill", "stepper.step"}
    for parent, share in by_parent.items():
        assert 0.95 <= share <= 1.0001, (parent, share)
    # the calls' sleeps are in the `.call` children alone
    assert 0.028 <= moved("stepper.prefill.call", 0) < 0.5
    assert 0.112 <= moved("stepper.step.call", 0) < 1.0
    assert moved("stepper.step.fill", 0) < 0.25 * moved("stepper.step.call", 0)
