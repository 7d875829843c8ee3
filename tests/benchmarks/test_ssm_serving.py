"""Kind `ssm-serving` (ISSUE 37): its traffic and configuration files, its
plain reference in layer-sized pieces against the program's, the comparison
that decides `correct` with the three controls that have to fail it, the
operations and bytes of a dispatch and of its scan, and a CPU rehearsal of
benchmarks/run.py on a test-only tiny cell. No chip: nothing here is a device
number."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import listed
from benchmarks import seqgen
from benchmarks.kinds import ssm_serving
from benchmarks.run import find
from ssm_controls import CONTROLS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
TRAFFIC_FILES = [
    f for p in PATHS for f in sorted((REPO / p / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind") == "ssm-serving"
]
REAL = json.loads((REPO / "benchmarks" / "configs" / "jamba2-3b.json").read_text())
TINY = json.loads(find(PATHS, "configs/jamba-tiny.json").read_text())
TINY_TRAFFIC = json.loads(find(PATHS, "traffic/next-tiny.json").read_text())


# -- the traffic is a pure function of the seed -----------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_sessions_and_schedule_are_pure_functions_of_the_seed(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    n_items = 65_536
    a = seqgen.draw_sessions(seed, n_items, traffic, 400)
    b = seqgen.draw_sessions(seed, n_items, traffic, 300)
    c = seqgen.draw_sessions(seed + 1, n_items, traffic, 300)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))  # session i does not depend on n
    assert not all(np.array_equal(x, y) for x, y in zip(b, c))
    lo, hi = traffic["events"]
    lengths = np.asarray([len(s) for s in a])
    assert lengths.min() >= lo and lengths.max() <= hi
    assert abs(np.median(lengths) - traffic["events_median"]) <= 0.25 * traffic["events_median"]
    assert all(len(set(s.tolist())) == len(s) for s in a)  # distinct within a session
    assert all(0 <= s.min() and s.max() < n_items for s in a)
    buckets = {k_bucket(traffic["how_many"] + n + 8) for n in range(lo, hi + 1)}
    assert buckets == {traffic["k_bucket"]}
    s1 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    s2 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    assert np.array_equal(s1["due"], s2["due"])
    assert int(s1["in_window"].sum()) == round(traffic["rate_per_s"] * 40.0)
    # the generator's names for the basket and its steps
    assert traffic["block_length"] == traffic["denoise_steps"] == 4


def test_the_cell_runs_basket4s_sessions_at_a_rate_on_a_rung_of_five():
    mine = json.loads((REPO / "benchmarks" / "traffic" / "next4.json").read_text())
    theirs = json.loads((REPO / "benchmarks" / "traffic" / "basket4.json").read_text())
    same = ("path", "how_many", "zipf_s", "events", "events_median", "events_sigma", "k_bucket", "timeout_s")
    assert all(mine[k] == theirs[k] for k in same)  # the two encoder cells differ in the architecture alone
    assert mine["rate_per_s"] % 5 == 0 and mine["rate_per_s"] > 0


# -- the configuration file ---------------------------------------------------------

def test_the_configuration_holds_every_published_number_and_cuts_nothing():
    catalog = {  # the source's config.json, every number of it (the catalog's row)
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_size": 2560, "intermediate_size": 8192, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "max_position_embeddings": 262144,
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "vocab_size": 65536,
    }
    # PR 37's entries: present, once, its eight metrics together (wherever in the lists)
    entry, _cell, _mine = listed.entries_of(BENCH, *listed.ADDED[1])
    assert [k for k, v in catalog.items() if REAL.get(k) != v] == entry["reduced"] == []
    assert REAL["published"] == {} and REAL["kind"] == "ssm-serving"
    assert REAL["tie_word_embeddings"] is True and REAL["mamba_conv_bias"] is True
    assert REAL["mamba_proj_bias"] is False and REAL["model_type"] == "jamba" and REAL["hidden_act"] == "silu"
    assert entry["source"] == "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    assert set(REAL["assumed"]) >= {"layer_order", "positions", "inner_norms", "bos", "max_len", "basket", "weights"}
    assert "a padded position changes no state" in REAL["guarantees"]
    # what one chip holds: the whole model, 3.03B parameters
    from oryx_tpu.ops import jamba

    cfg = jamba.JambaConfig.from_extensions(ssm_serving.extensions(REAL).get)
    assert cfg.layers == 28 and cfg.d_inner == 5120 and cfg.head_dim == 128 and cfg.basket == 4
    held = 2 * jamba.param_count(cfg)
    assert 0.37 < held / 16e9 < 0.39  # 6.06 GB of the chip's 16


# -- the operations and bytes of the algorithm ------------------------------------------

def test_step_and_scan_work_at_the_published_widths():
    # a token through 28 layers: 2 x the 2.86B parameters of the layers, and the recurrence
    per_token = ssm_serving.step_work(1, 0, 0, REAL)
    layers = 26 * 104.16e6 + 2 * 76.68e6
    assert per_token == pytest.approx(2.0 * layers, rel=0.02)
    # ISSUE 37: a prefill of 8 x 32 slots is 1.55 TFLOP, of 8 x 100 4.85
    assert ssm_serving.step_work(256, 16, 0, REAL) == pytest.approx(1.55e12, rel=0.08)
    assert ssm_serving.step_work(800, 50, 0, REAL) == pytest.approx(4.85e12, rel=0.08)
    head = ssm_serving.step_work(1, 0, 1, REAL) - per_token
    assert head == 2.0 * 2560 * 65_536
    assert ssm_serving.step_work(1, 100, 0, REAL) - per_token == 2 * 2.0 * 2.0 * 2560 * 100
    flops, moved = ssm_serving.scan_work(1, 0, REAL)
    assert flops == 2.0 * 4 * 5120 + 7.0 * 16 * 5120 + 3.0 * 5120
    assert moved == 4.0 * (5 * 5120 + 2 * 16)
    # a sequence's state: 16 + 3 rows of 5,120 float32, read and written
    assert ssm_serving.scan_work(0, 1, REAL)[1] == 2 * 4.0 * 19 * 5120
    # every dispatch streams the layers' 5.72 GB; a step also the catalog's 0.34 GB
    assert ssm_serving.weight_bytes(REAL) == pytest.approx(5.72e9, rel=0.01)
    step = ssm_serving.step_bytes(4, 4, True, REAL)
    assert step - ssm_serving.weight_bytes(REAL) == pytest.approx(
        65_536 * 2560 * 2 + 4 * 2 * 26 * 19 * 5120 * 4 + 4 * 2 * 2 * 128 * 2
    )
    assert ssm_serving.step_bytes(24, 1, False, REAL) < step


# -- the kind's reference against the program's ---------------------------------------------

def _tiny_model(seed=5, dtype="float32"):
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    ext = dict(ssm_serving.extensions(TINY), dtype=dtype)
    enc = jamba.JambaEncoder.from_extensions(ext.get)
    tensors = jamba.init_tensors(enc.cfg, seed, enc.dtype)
    e = ssm_serving.draw_catalog(seed, TINY["vocab_size"], TINY["hidden_size"])
    tensors["E_in"] = jnp.asarray(e, enc.dtype)
    return enc, jamba.params_of(enc.cfg, tensors, enc.dtype), e, jnp


def test_the_kinds_reference_is_the_programs_reference():
    enc, params, e, jnp = _tiny_model()
    from oryx_tpu.ops import jamba

    rng = np.random.default_rng(0)
    tokens = np.zeros((2, 28), np.int32)
    tokens[0, :13] = rng.choice(500, size=13, replace=False)
    tokens[1] = rng.choice(500, size=28, replace=False)
    z = np.asarray(ssm_serving.ref_hidden(TINY, params, tokens))
    assert z.shape == (2, 28, 64)
    theirs = np.asarray(jamba.reference_forward(enc.cfg, params, jnp.asarray(tokens[0, :13])))
    np.testing.assert_allclose(z[0, :13], theirs, atol=2e-6)  # causal: the padding behind changes nothing
    np.testing.assert_allclose(
        z[1], np.asarray(jamba.reference_forward(enc.cfg, params, jnp.asarray(tokens[1]))), atol=2e-6
    )


def test_the_reference_with_the_stated_rounding_is_the_served_arithmetic():
    """bfloat16 weights and activations: the float32 reference differs from
    the served form by the rounding; the same plain pass with every product's
    inputs at bfloat16's values differs from it by the order of accumulation
    alone."""
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    enc, params, e, _ = _tiny_model(dtype="bfloat16")
    rng = np.random.default_rng(2)
    session = rng.choice(500, size=12, replace=False).astype(np.int32)
    state = enc.init_state(enc.step_rows)
    state, _, _ = enc.prefill(params, state, *enc.pack([session], 24, [0], enc.step_rows))
    served, _ = jamba._token_hidden(
        enc.cfg, params, state, jnp.asarray([0]), jnp.asarray([11]), jnp.asarray([True])
    )
    served = np.asarray(served[0])
    exact = np.asarray(ssm_serving.ref_hidden(TINY, params, session[None]))[0, -1]
    stated = np.asarray(ssm_serving.ref_hidden(TINY, params, session[None], act=jnp.bfloat16))[0, -1]
    scale = np.abs(exact).max()
    assert 1e-4 < np.abs(served - exact).max() / scale < 3e-2   # the rounding
    assert np.abs(served - stated).max() / scale < 2e-5         # the same arithmetic


# -- the comparison on hand-made answers ----------------------------------------------------

def _answer_from(logits, session, how_many=10):
    entries = []
    for b in range(4):
        open_ = logits[b].copy()
        open_[session] = -np.inf
        top = np.argsort(-open_, kind="stable")[:how_many]
        entries.append({
            "item": f"i{int(np.argmax(logits[b]))}", "step": b,
            "next": [[f"i{r}", float(logits[b][r])] for r in top],
        })
    return entries


def test_compare_and_summarise_on_hand_made_answers():
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((4, 500)).astype(np.float32)
    session = np.asarray([3, 4, 5])
    answer = _answer_from(logits, session)
    tokens = ssm_serving.basket_tokens(TINY, answer, session)
    assert tokens.tolist() == [3, 4, 5] + [int(e["item"][1:]) for e in answer[:3]]
    out = ssm_serving.compare(TINY, answer, session, logits, 10)
    assert all(o["fault"] is None and o["score_err"] == 0 and o["overlap"] == 10 for o in out)
    assert all(o["fixed_gap"] == 0 and o["candidate_gap"] == 0 and o["stated_err"] is None for o in out)
    assert ssm_serving.holds(ssm_serving.summarise([out] * 16, "float32")) == []
    # scores a little off in every request: the quartile sees it
    off = json.loads(json.dumps(answer))
    for e in off:
        e["next"] = [[i, s * (1 + 1e-2)] for i, s in e["next"]]
    bad = ssm_serving.compare(TINY, off, session, logits, 10)
    assert "score_err_quartile" in ssm_serving.holds(ssm_serving.summarise([bad] * 16, "float32"))
    # held against the stated rounding too where the configuration states one
    low = ssm_serving.compare(TINY, answer, session, logits, 10, rounded=logits * (1 + 1e-2))
    assert all(o["stated_err"] > 1e-3 and o["rounding"] > 1e-3 for o in low)
    assert "stated_err_quartile" in ssm_serving.holds(ssm_serving.summarise([low] * 16, "bfloat16"))
    # an item fed back that the reference ranks far below its best
    worse = json.loads(json.dumps(answer))
    worse[1]["item"] = f"i{int(np.argmin(logits[1]))}"
    gap = ssm_serving.compare(TINY, worse, session, logits, 10)
    assert "fixed_gap_worst" in ssm_serving.holds(ssm_serving.summarise([gap] * 16, "float32"))
    # a wrong form: three entries, steps out of order, an item of the session served
    assert ssm_serving.basket_tokens(TINY, answer[:3], session) is None
    swapped = [answer[1], answer[0]] + answer[2:]
    assert ssm_serving.basket_tokens(TINY, swapped, session) is None
    assert ssm_serving.compare(TINY, answer[:3], session, None, 10)[0]["fault"]
    known = json.loads(json.dumps(answer))
    known[0]["next"][0][0] = "i3"
    assert "session" in ssm_serving.compare(TINY, known, session, logits, 10)[0]["fault"]
    assert "malformed_answers" in ssm_serving.holds(
        ssm_serving.summarise([ssm_serving.compare(TINY, answer[:3], session, None, 10)])
    )


# -- the kind's whole run on the CPU, sound and with each control ----------------------------------

@pytest.mark.parametrize(
    "control,dtype,failing",
    [
        (None, "float32", set()),
        (None, "bfloat16", set()),
        # at this size the state weighs little: a bfloat16 state reads 3e-6 of the
        # largest logit, which the float32 limit sees and the bfloat16 ones cannot
        # (on the chip, at the published widths, it is `stated_err_quartile` that
        # catches it: PERF.md)
        ("state_in_bfloat16", "float32", {"score_err_quartile"}),
        ("conv_tail_not_carried", "float32", {"score_err_quartile"}),
        ("conv_tail_not_carried", "bfloat16", {"stated_err_quartile"}),
        ("padding_advances_the_state", "float32", {"score_err_quartile"}),
    ],
    ids=["sound", "sound_bfloat16", "state_in_bfloat16", "conv_tail_not_carried",
         "conv_tail_not_carried_bfloat16", "padding_advances_the_state"],
)
def test_a_fault_under_the_timed_path_reads_not_correct(control, dtype, failing, tmp_path, monkeypatch):
    """The kind's whole run in this process (run.py's look for a chip is
    skipped), the program broken underneath by each control: `correct` is
    false exactly when a compared number breaks its limit, and the tight
    limit on the scores is among them."""
    import jax

    if control:
        CONTROLS[control](monkeypatch.setattr)
    cell = {"config": dict(TINY, dtype=dtype), "traffic": TINY_TRAFFIC, "chips": 1, "scratch": str(tmp_path)}
    try:
        out = ssm_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # the next test traces the sound program again
    # on the CPU the batcher pads rows to powers of two: a burst may meet a row
    # count the warm-up never saw, so shapes and compiles are the chip's to hold
    host_side = {"compiles_in_window", "topk_shapes"}
    broken = set(ssm_serving.holds(out["compared"])) - host_side
    assert broken >= failing and (failing or not broken), out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert out["compared"]["requests_compared"][:2] == [10, "=="]  # every request of a 1 s window
    assert out["compared"]["steps_per_basket"][0] == 4 and out["compared"]["dropped_events"][0] == 0
    assert out["correct"] is (not ssm_serving.holds(out["compared"]))
    assert ("stated_err_quartile" in out["compared"]) is (dtype == "bfloat16")
    src = out["sources"]
    assert set(src) >= {"counters", "dispatch_records", "generator", "collector", "trace", "timeline", "config", "traffic"}
    assert src["counters"]['oryx_seq_steps_total{kind="decode"}'] > 0


def test_cpu_rehearsal_prints_the_shared_layers_metrics(tmp_path):
    """run.py end to end on the test-only cell jamba-tiny.next-tiny, found by
    name alone: the counters' and spans' metrics of the shared layers print;
    the device's do not (a CPU trace has no device plane), nor the kind's own
    (the cell is on no metric's list)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", "jamba-tiny.next-tiny",
         "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] == 20
    listed.printed_on_the_cpu_holds(BENCH, "jamba-tiny.next-tiny", last["metrics"])
    assert last["compared"]["steps_per_basket"] == [4.0, "==", 4]
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")


def test_the_kind_fails_at_once_on_a_tree_without_the_decoder(tmp_path, monkeypatch):
    """The parent of ISSUE 37 has no ops/jamba.py: the kind raises before any
    set-up (run.py then exits 1 with no result line)."""
    import builtins

    real_import = builtins.__import__

    def no_jamba(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "oryx_tpu.ops" and "jamba" in (fromlist or ()):
            raise ImportError("cannot import name 'jamba' from 'oryx_tpu.ops'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_jamba)
    cell = {"config": TINY, "traffic": TINY_TRAFFIC, "chips": 1, "scratch": str(tmp_path)}
    t0 = time.monotonic()
    with pytest.raises(ImportError):
        ssm_serving.run(cell, 1, 1.0, False, time.time(), lambda **kv: None)
    assert time.monotonic() - t0 < 5.0
