"""The seq stepper's thread on a stubbed encoder (no device): its regions
tile its life, as the top-k dispatcher's do (tests/test_batcher.py). Then on
the four real encoders at their tests' small sizes: a dispatch's host operands
reach the device as arguments of the jitted call itself (the seam's comment,
ops/seq.py)."""

import importlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
        return state, _Dev(np.zeros((self.prefill_rows, 3), np.float32)), {}

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


# ---- the real encoders: host operands ride the jitted call -------------------

GENERATING = ("sdar", "jamba", "joyai")


def _tiny(name):
    """(encoder, parameters, head, requests' prepared inputs, {call: (module,
    the jitted program's name)}) of one encoder at its own test file's small
    size, with two length buckets (32 and 40) where it generates."""
    from oryx_tpu.ops import seq

    rng = np.random.default_rng(11)
    if name == "gru":
        dim, window = 8, 3
        enc = seq.GruEncoder(dim, window)
        params = {k: np.asarray(v) for k, v in seq.init_gru_params(jax.random.PRNGKey(0), dim).items()}
        prepared = [
            (rng.standard_normal((window, dim)).astype(np.float32), np.ones((window,), np.float32))
            for _ in range(11)
        ]
        return enc, params, None, prepared, {"prefill": (seq, "encode_vectors")}
    t = importlib.import_module(f"test_{name}")
    mod = importlib.import_module(f"oryx_tpu.ops.{name}")
    weights = t._weights()
    enc = getattr(mod, f"{name.capitalize()}Encoder")(t.CFG._replace(max_len=40), jnp.float32)
    view = jnp.asarray(weights[1])
    if name == "sdar":
        row_token = jnp.arange(view.shape[0], dtype=jnp.int32)
    else:  # jamba's embedding is tied: its step takes none
        row_token = weights[2] if name == "joyai" else None
    prepared = [
        rng.choice(t.N_ITEMS, size=n, replace=False).astype(np.int32)
        for n in (5, 36, 11, 24, 33, 7, 40, 15, 28, 9, 38)
    ]
    programs = {"prefill": (mod, "prefill"), "step": (mod, f"{enc.step_kind}_step")}
    return enc, weights[0], (view, t.N_ITEMS, row_token), prepared, programs


def _drive(stepper, engine, prepared):
    """The requests in three waves, so that cycles differ in their live sets
    and prefills in their buckets; every one answered."""
    futures = []
    for wave in (prepared[:1], prepared[1:6], prepared[6:]):
        futures += [stepper.submit(engine, p) for p in wave]
        futures[-1].result(timeout=120)
    return [f.result(timeout=120) for f in futures]


@pytest.mark.parametrize(
    "name,call",
    [(n, c) for n in ("gru",) + GENERATING for c in ("prefill", "step") if (n, c) != ("gru", "step")],
)
def test_a_dispatchs_host_operands_ride_the_jitted_call(name, call, monkeypatch):
    """Over a few cycles of the real stepper: between the start of a cycle
    (its `pack`, its `fill`) and the jitted program nothing calls
    `jnp.asarray` or `jax.device_put`, nor after it before the cycle ends, and
    the program is handed what `pack` and `_cycle` built: `np.ndarray`s, and
    the head's valid rows as an `np.int32`."""
    enc, params, head, prepared, programs = _tiny(name)
    seam = threading.local()  # .on: in a cycle and not inside a jitted program
    pending: list[str] = []
    eager: dict[str, list[str]] = {c: [] for c in (*programs, "after")}
    received: dict[str, list[tuple]] = {c: [] for c in programs}

    def spied(which, real):
        def program(*args):
            received[which].append(args)
            eager[which] += pending
            pending.clear()
            seam.on = False
            try:
                return real(*args)
            finally:
                seam.on = True
        return program

    for which, (mod, attr) in programs.items():
        monkeypatch.setattr(mod, attr, spied(which, getattr(mod, attr)))
    if name != "gru":  # a generating encoder holds its programs (ops/decoder.py DecoderEncoder)
        monkeypatch.setattr(type(enc), "programs", (getattr(*programs["prefill"]), getattr(*programs["step"])))

    def spy(attr, real):
        def eager_upload(*args, **kw):
            if getattr(seam, "on", False):
                pending.append(attr)
            return real(*args, **kw)
        return eager_upload

    monkeypatch.setattr(jax, "device_put", spy("device_put", jax.device_put))
    monkeypatch.setattr(jnp, "asarray", spy("asarray", jnp.asarray))
    cycle = SeqStepper._cycle

    def in_cycle(self, *args):
        seam.on = True
        try:
            return cycle(self, *args)
        finally:
            seam.on = False
            eager["after"] += pending
            pending.clear()

    monkeypatch.setattr(SeqStepper, "_cycle", in_cycle)
    stepper = SeqStepper()
    engine = Engine(enc, params, head=lambda: head)
    try:
        assert len(_drive(stepper, engine, prepared)) == len(prepared)
    finally:
        stepper.close()
    assert stepper.cycles >= 3 and len(received[call]) >= 3
    assert eager[call] == [] and eager["after"] == []
    for args in received[call]:
        if name == "gru":
            host, n_valid = args[1:], None
        elif call == "prefill":
            host, n_valid = args[3:], None
        else:
            host, n_valid = args[-4:], args[4]
        assert [type(a) for a in host] == [np.ndarray] * len(host)
        assert n_valid is None or (type(n_valid) is np.int32 and n_valid == head[1])


@pytest.mark.parametrize("name", GENERATING)
def test_a_window_adds_nothing_to_a_jitted_programs_cache(name):
    """`warm` calls the wrappers with the kinds of operand a cycle does, so
    after it each program holds ONE entry a shape (a prefill a bucket, one
    step) and ten and more cycles with different live sets and both buckets
    add none: nothing is traced inside a window."""
    enc, params, head, prepared, programs = _tiny(name)
    jitted = {call: getattr(mod, attr) for call, (mod, attr) in programs.items()}
    for program in jitted.values():
        program.clear_cache()
    stepper = SeqStepper()
    engine = Engine(enc, params, head=lambda: head)
    try:
        stepper.warm(engine)
        warmed = {call: program._cache_size() for call, program in jitted.items()}
        assert warmed == {"prefill": len(enc.length_buckets), "step": 1} and len(enc.length_buckets) == 2
        _drive(stepper, engine, prepared)
    finally:
        stepper.close()
    assert stepper.cycles >= 10
    assert {call: program._cache_size() for call, program in jitted.items()} == warmed


def _basket(enc, params, head, prepared, programs, uploaded):
    """Two sessions' prefill and steps -> (every step's out, the state after
    the last) as numpy: through the seam with the host's arrays, or
    (`uploaded`) by the jitted programs on the same operands uploaded first,
    as the wrappers did before."""
    prefill = getattr(*programs["prefill"])
    step_program = getattr(*programs["step"])
    view, n_valid, row_token = head
    state = enc.init_state(enc.step_rows)
    bucket = min(b for b in enc.length_buckets if b >= max(enc.length(p) for p in prepared))
    packed = enc.pack(prepared, bucket, list(range(len(prepared))), enc.step_rows)
    if uploaded:
        state = prefill(enc.cfg, params, state, *(jnp.asarray(a) for a in packed))[0]
    else:
        state = enc.prefill(params, state, *packed)[0]
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    lengths = np.zeros(enc.step_rows, np.int32)
    live = np.zeros(enc.step_rows, bool)
    for i, p in enumerate(prepared):
        slots[i], lengths[i], live[i] = i, enc.length(p), True
    outs = []
    for i in range(enc.steps):
        rows = (slots, lengths, live, np.full(enc.step_rows, i, np.int32))
        if uploaded:
            fixed = (view, jnp.int32(n_valid)) + (() if row_token is None else (row_token,))
            state, out = step_program(enc.cfg, params, state, *fixed, *(jnp.asarray(a) for a in rows))
        else:
            state, out = enc.step(params, state, head, *rows)
        outs.append({k: np.asarray(v) for k, v in out.items() if k != "head_rows"})
    return outs, jax.tree_util.tree_map(np.asarray, state)


@pytest.mark.parametrize("name", GENERATING)
def test_a_basket_from_host_operands_is_the_uploaded_operands_basket(name):
    """The same programs on the same values: every step's out and the slots'
    state after the last are equal array for array, whoever transferred."""
    enc, params, head, prepared, programs = _tiny(name)
    host = _basket(enc, params, head, prepared[:2], programs, uploaded=False)
    uploaded = _basket(enc, params, head, prepared[:2], programs, uploaded=True)
    assert len(host[0]) == enc.steps
    for ours, theirs in zip(jax.tree_util.tree_leaves(host), jax.tree_util.tree_leaves(uploaded), strict=True):
        np.testing.assert_array_equal(ours, theirs)


@pytest.mark.parametrize("name", GENERATING)
def test_every_step_dispatch_counts_the_heads_rows(name):
    """`oryx_seq_head_rows_total` moves once a step dispatch: walked + skipped
    is the view's rows, walked the blocks that hold a valid row. The view here
    has capacity behind its items, two blocks, one of them live."""
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.ops.pallas_head import HEAD_BLOCK_ROWS

    enc, params, (view, n_valid, row_token), prepared, _ = _tiny(name)
    rows = 2 * HEAD_BLOCK_ROWS
    view = jnp.pad(view, ((0, rows - view.shape[0]), (0, 0)))
    if row_token is not None:
        row_token = jnp.pad(row_token, (0, rows - row_token.shape[0]), constant_values=-1 if name == "joyai" else 0)
    reg = get_registry()
    steps = reg.counter("oryx_seq_steps_total", labeled=True)
    head_rows = reg.counter("oryx_seq_head_rows_total", labeled=True)

    def read():
        return (
            steps.value(kind=enc.step_kind), head_rows.value(rows="walked"), head_rows.value(rows="skipped")
        )

    before = read()
    stepper = SeqStepper()
    engine = Engine(enc, params, head=lambda: (view, n_valid, row_token))
    try:
        assert len(_drive(stepper, engine, prepared[:6])) == 6
    finally:
        stepper.close()
    n, walked, skipped = (a - b for a, b in zip(read(), before))
    assert n >= enc.steps and n_valid <= HEAD_BLOCK_ROWS
    assert (walked, skipped) == (n * HEAD_BLOCK_ROWS, n * HEAD_BLOCK_ROWS)
