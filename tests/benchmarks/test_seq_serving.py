"""Kind `seq-serving` (ISSUE 33): its traffic files, its plain reference
against the program's, the comparison that decides `correct` with the three
controls that have to fail it, the operations and bytes of a step, the trace
split, and a CPU rehearsal of benchmarks/run.py on a test-only tiny cell.
No chip: nothing here is a device number."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import listed
from benchmarks import seqgen, seqtrace
from benchmarks.kinds import seq_serving
from benchmarks.run import find
from seq_controls import CONTROLS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
TRAFFIC_FILES = [
    f for p in PATHS for f in sorted((REPO / p / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind") == "seq-serving"
]
REAL = json.loads((REPO / "benchmarks" / "configs" / "sdar-30b-a3b-6l.json").read_text())
TINY = json.loads(find(PATHS, "configs/sdar-tiny.json").read_text())
TINY_TRAFFIC = json.loads(find(PATHS, "traffic/basket-tiny.json").read_text())


# -- the traffic is a pure function of the seed -----------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_sessions_and_schedule_are_pure_functions_of_the_seed(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    n_items = 151_935
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
    # Zipf(1.0): the most popular item is in about 1 of 12 events' worth of draws
    counts = np.bincount(np.concatenate(a), minlength=n_items)
    assert counts.max() / len(a) > 0.3  # in a third of the sessions or more
    # k = howMany + events + 8 lands in the one bucket the file states
    buckets = {k_bucket(traffic["how_many"] + n + 8) for n in range(lo, hi + 1)}
    assert buckets == {traffic["k_bucket"]}
    s1 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    s2 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    assert np.array_equal(s1["due"], s2["due"])
    rate = traffic["rate_per_s"]
    assert int(s1["in_window"].sum()) == round(rate * 40.0)
    assert len(s1["due"]) == round(rate * 6.0) + round(rate * 40.0)
    path = seqgen.session_path(traffic, a[0])
    assert path.startswith("/recommend-next/i") and path.endswith("?howMany=10")


def test_check_body_holds_an_answer_to_its_form():
    t = TINY_TRAFFIC
    page = [[f"i{j}", 1.0 - 0.01 * j] for j in range(10)]
    body = [{"item": f"i{20 + b}", "step": (b + 1) % 4, "next": page} for b in range(4)]
    enc = lambda x: json.dumps(x).encode()  # noqa: E731
    assert seqgen.check_body(enc(body), t, {11, 12}) is None
    assert seqgen.check_body(enc(body), t, {3}) == "known_item"
    assert seqgen.check_body(enc(body[:3]), t, set()) == "wrong_block"
    two_at_one = [dict(body[0], step=2)] + body[1:]
    assert seqgen.check_body(enc(two_at_one), t, set()) == "wrong_block"
    short = [dict(body[0], next=page[:9])] + body[1:]
    assert seqgen.check_body(enc(short), t, set()) == "wrong_count"
    assert seqgen.check_body(b"<html>", t, set()) == "unparsable"
    assert seqgen.check_body(enc(page), t, set()) == "unparsable"  # the GRU's answer


# -- the configuration file ---------------------------------------------------------

def test_the_configuration_holds_the_published_widths_and_the_cut():
    catalog = {  # the source's config.json, every number of it
        "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "vocab_size": 151936,
    }
    # PR 33's entries: present, once, its seven metrics together (wherever in the lists)
    entry, _cell, _mine = listed.entries_of(BENCH, *listed.ADDED[0])
    differs = [k for k, v in catalog.items() if REAL.get(k) != v]
    assert differs == entry["reduced"] == ["num_hidden_layers"]
    assert REAL["num_hidden_layers"] == 6 and REAL["published"]["num_hidden_layers"] == 48
    assert REAL["mlp_only_layers"] == [] and REAL["norm_topk_prob"] is True
    assert REAL["tie_word_embeddings"] is False and REAL["model_type"] == "sdar_moe"
    assert set(REAL["assumed"]) >= {"block_length", "denoise_steps", "mask_id", "noise_schedule"}
    # what one chip holds: 6 x 623.1M in the layers, the vocabulary twice
    from oryx_tpu.ops import sdar

    ext = seq_serving.extensions(REAL)
    cfg = sdar.SdarConfig.from_extensions(ext.get)
    per_layer = sum(int(np.prod(s)) for s in sdar.layer_shapes(cfg).values())
    assert per_layer == 18_874_368 + 262_144 + 603_979_776 + 2 * 2048 + 2 * 128  # ISSUE 33's 623.1M and the norms
    held = 2 * (sdar.param_count(cfg) + (cfg.vocab - 1) * cfg.hidden)
    assert 0.5 < held / 16e9 < 0.6  # 8.7 GB of the chip's 16


# -- the operations and bytes of the algorithm ------------------------------------------

def test_step_and_expert_layer_work_at_the_published_widths():
    flops, moved = seq_serving.moe_work(16, 81, REAL)
    assert flops == 16 * (2.0 * 2048 * 128 + 8 * 3 * 2.0 * 2048 * 768)
    assert moved == pytest.approx(81 * 3 * 2048 * 768 * 2 + 2048 * 128 * 2 + 16 * 2048 * 8)
    # a token through six layers: 0.68 GFLOP (ISSUE 33), the scores apart
    assert seq_serving.step_work(1, 0, 0, REAL) == pytest.approx(0.68e9, rel=0.01)
    # a block position also takes logits over 151,935 items
    head = seq_serving.step_work(1, 0, 1, REAL) - seq_serving.step_work(1, 0, 0, REAL)
    assert head == 2.0 * 2048 * 151_935
    assert seq_serving.step_work(1, 100, 0, REAL) > seq_serving.step_work(1, 0, 0, REAL)


# -- the kind's reference against the program's, and the bfloat16 rounding ----------------

def _tiny_model(seed=5, dtype="float32"):
    import jax.numpy as jnp

    from oryx_tpu.ops import sdar

    ext = dict(seq_serving.extensions(TINY), dtype=dtype)
    enc = sdar.SdarEncoder.from_extensions(ext.get)
    params = sdar.params_of(enc.cfg, sdar.init_tensors(enc.cfg, seed, enc.dtype))
    e_out = seq_serving.draw_catalog(seed, TINY["vocab_size"] - 1, TINY["hidden_size"])
    return enc, params, e_out, jnp


def test_the_kinds_reference_is_the_programs_reference():
    enc, params, e_out, jnp = _tiny_model()
    from oryx_tpu.ops import sdar

    rng = np.random.default_rng(0)
    n = 9
    tokens = np.zeros(enc.cfg.positions, np.int32)
    tokens[:n] = rng.choice(500, size=n, replace=False)
    tokens[n:n + 4] = [enc.cfg.mask_id, 17, enc.cfg.mask_id, enc.cfg.mask_id]
    zb = seq_serving.ref_block_hidden(TINY, params, jnp.asarray(tokens), jnp.int32(n))
    theirs = sdar.reference_forward(enc.cfg, params, jnp.asarray(tokens), jnp.int32(n))[n:n + 4]
    np.testing.assert_allclose(np.asarray(zb), np.asarray(theirs), atol=2e-6)
    logits = seq_serving.ref_logits(zb, jnp.asarray(e_out))
    np.testing.assert_allclose(logits, np.asarray(zb) @ e_out.T, atol=1e-5)


def test_catalog_rows_are_bfloat16_values():
    import jax.numpy as jnp

    e = seq_serving.draw_catalog(3, 1000, 64)
    assert e.dtype == np.float32 and 0.015 < e.std() < 0.025
    back = np.asarray(jnp.asarray(e, dtype=jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(back, e)
    x = np.asarray([1.0 + 2.0 ** -9, 1.0 + 3 * 2.0 ** -9, -0.3], dtype=np.float32)
    want = np.asarray(jnp.asarray(x, dtype=jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(seq_serving.to_bfloat16_values(x.copy()), want)  # ties to even


# -- the comparison on hand-made answers ----------------------------------------------------

def _answer_from(logits_of_step, session, fixed_order, how_many=10):
    """A sound answer: at step s position fixed_order[s] is fixed to its argmax."""
    entries = [None] * 4
    for step, b in enumerate(fixed_order):
        logits = logits_of_step[step][b]
        open_ = logits.copy()
        open_[session] = -np.inf
        top = np.argsort(-open_, kind="stable")[:how_many]
        entries[b] = {
            "item": f"i{int(np.argmax(logits))}", "step": step,
            "next": [[f"i{r}", float(logits[r])] for r in top],
        }
    return entries


def test_replay_and_summarise_on_hand_made_answers():
    rng = np.random.default_rng(1)
    steps = [rng.standard_normal((4, 500)).astype(np.float32) for _ in range(4)]
    session = np.asarray([3, 4, 5])
    seen = []

    def block_logits(tokens):
        seen.append(tokens.copy())
        return steps[len(seen) - 1]

    answer = _answer_from(steps, session, [2, 0, 3, 1])
    out = seq_serving.replay(TINY, answer, session, block_logits, 10)
    assert all(o["fault"] is None and o["score_err"] == 0 and o["overlap"] == 10 for o in out)
    assert all(o["fixed_gap"] == 0 and o["candidate_gap"] == 0 for o in out)
    # the replay fixed the served items in the served order, [MASK] elsewhere
    assert seen[0].tolist() == [3, 4, 5, 500, 500, 500, 500]
    assert seen[1][3 + 2] == int(answer[2]["item"][1:]) and seen[1][3] == 500
    assert sum(1 for t in seen[3][3:] if t == 500) == 1
    compared = seq_serving.summarise([out] * 16, "float32")
    assert seq_serving.holds(compared) == []
    # scores a little off in every request: the quartile sees it
    seen.clear()
    off = json.loads(json.dumps(answer))
    for e in off:
        e["next"] = [[i, s * (1 + 1e-2)] for i, s in e["next"]]
    bad = seq_serving.replay(TINY, off, session, block_logits, 10)
    assert "score_err_quartile" in seq_serving.holds(seq_serving.summarise([bad] * 16, "float32"))
    # off in a minority of the requests (a routing step): only the worst moves
    few = seq_serving.summarise([bad] * 3 + [out] * 13, "float32")
    assert few["score_err_quartile"][0] == 0 and few["score_err_worst"][0] > 0
    assert seq_serving.holds(few) == []
    # a wrong form
    seen.clear()
    assert seq_serving.replay(TINY, answer[:3], session, block_logits, 10)[0]["fault"]
    assert "malformed_answers" in seq_serving.holds(seq_serving.summarise([
        seq_serving.replay(TINY, answer[:3], session, block_logits, 10)
    ]))


# -- the trace split ---------------------------------------------------------------------------

def test_scopes_come_from_the_compiled_text_and_ops_from_the_enclosing_program():
    text = """
HloModule jit_denoise_step
fused_computation.1 { %p = f32[4]{0} parameter(0) }
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(denoise_step)/sdar.moe/mul" source_file="x.py"}
  ROOT %custom-call.7 = f32[4]{0} custom-call(%b), metadata={op_name="jit(denoise_step)/sdar.attn/dot_general"}
  copy.3 = f32[4]{0} copy(%c), metadata={op_name="jit(denoise_step)/sdar.head/reduce_max"}
  %bitcast.9 = f32[4]{0} bitcast(%d)
}"""
    table = seqtrace.scopes_of(text)
    assert table["fusion.1"].endswith("sdar.moe/mul") and "sdar.attn" in table["custom-call.7"]
    assert "sdar.head" in table["copy.3"] and "bitcast.9" not in table
    assert seqtrace.program_name("jit_prefill(123)") == "jit_prefill"
    assert seqtrace.op_name("%fusion.1 = f32[4]{0} fusion(f32[4] %a)") == "fusion.1"
    parsed = {"programs": {
        "jit_denoise_step(9)": {"count": 2, "seconds": 1.0, "ops": {"fusion.1": 0.5, "custom-call.7": 0.2, "bitcast.9": 0.1}},
        "jit_other(1)": {"count": 5, "seconds": 9.0, "ops": {"fusion.1": 9.0}},
    }}
    other = text.replace("fusion.1 ", "fusion.77 ")  # another shape of the program: knows fewer ops
    out = seqtrace.split(parsed, {"jit_denoise_step": [other, text]}, seq_serving.SCOPES)
    assert set(out) == {"jit_denoise_step"}
    got = out["jit_denoise_step"]
    assert got["count"] == 2 and got["seconds"] == 1.0
    assert got["scoped"] == {"sdar.moe": 0.5, "sdar.attn": 0.2, "sdar.head": 0.0}
    assert got["unscoped"] == pytest.approx(0.1)
    assert seqtrace.split(None, {}, seq_serving.SCOPES) == {}


# -- the kind's whole run on the CPU, sound and with each control ----------------------------------

def test_the_reference_with_the_stated_rounding_is_the_served_arithmetic():
    """bfloat16 weights and activations: the float32 reference differs from
    the served form by the rounding (about 1e-3 of the largest logit); the
    same plain forward with every product's inputs at bfloat16's values
    differs from it by the order of accumulation alone."""
    import jax.numpy as jnp

    enc, params, e_out, _ = _tiny_model(dtype="bfloat16")
    rng = np.random.default_rng(2)
    n = 11
    prefix = rng.choice(500, size=n, replace=False).astype(np.int32)
    state = enc.init_state(enc.step_rows)
    bucket = min(b for b in enc.length_buckets if b >= n)
    state, _, _ = enc.prefill(params, state, *enc.pack([prefix], bucket, [0], enc.step_rows))
    from oryx_tpu.ops import sdar

    args = (jnp.asarray([0]), jnp.asarray([n]), jnp.asarray([True]))
    served = np.asarray(sdar._block_hidden(enc.cfg, params, state, *args)[0][0])
    tokens = np.zeros(enc.cfg.positions, np.int32)
    tokens[:n], tokens[n:n + 4] = prefix, enc.cfg.mask_id
    exact = np.asarray(seq_serving.ref_block_hidden(TINY, params, jnp.asarray(tokens), jnp.int32(n)))
    stated = np.asarray(
        seq_serving.ref_block_hidden(TINY, params, jnp.asarray(tokens), jnp.int32(n), act=jnp.bfloat16)
    )
    scale = np.abs(exact).max()
    assert 1e-4 < np.abs(served - exact).max() / scale < 3e-2   # the rounding
    assert np.abs(served - stated).max() / scale < 1e-5         # the same arithmetic


@pytest.mark.parametrize(
    "control,dtype,failing",
    [
        (None, "float32", set()),
        (None, "bfloat16", set()),
        ("int8_experts", "float32", {"score_err_quartile"}),
        ("one_expert_short", "float32", {"score_err_quartile"}),
        ("one_expert_short", "bfloat16", {"stated_err_quartile", "score_err_quartile"}),
        ("causal_block", "float32", {"score_err_quartile"}),
    ],
    ids=["sound", "sound_bfloat16", "int8_experts", "one_expert_short", "one_expert_short_bfloat16",
         "causal_block"],
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
        out = seq_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # the next test traces the sound program again
    # on the CPU the batcher pads rows to powers of two: a burst may meet a row
    # count the warm-up never saw, so shapes and compiles are the chip's to hold
    host_side = {"compiles_in_window", "topk_shapes"}
    broken = set(seq_serving.holds(out["compared"])) - host_side
    assert broken >= failing and (failing or not broken), out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert out["compared"]["requests_compared"][:2] == [10, "=="]  # every request of a 1 s window
    assert out["compared"]["steps_per_block"][0] == 4 and out["compared"]["dropped_pairs"][0] == 0
    assert out["correct"] is (not seq_serving.holds(out["compared"]))
    assert ("stated_err_quartile" in out["compared"]) is (dtype == "bfloat16")
    src = out["sources"]
    assert set(src) >= {"counters", "dispatch_records", "generator", "collector", "trace", "timeline", "config", "traffic"}
    assert src["counters"]['oryx_seq_steps_total{kind="denoise"}'] > 0


def test_cpu_rehearsal_prints_the_kinds_metrics_and_no_others(tmp_path):
    """run.py end to end on the test-only cell sdar-tiny.basket-tiny, found
    by name alone: the counters' and spans' metrics of this kind and of the
    shared layers print; the device's do not (a CPU trace has no device plane)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", "sdar-tiny.basket-tiny",
         "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] == 20
    # a cell of BENCHMARK.json would also print the kind's own; this one is on no
    # metric's list, so it prints those without a list alone (`listed.cpu_names`)
    listed.printed_on_the_cpu_holds(BENCH, "sdar-tiny.basket-tiny", last["metrics"])
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")


def test_the_kind_fails_at_once_on_a_tree_without_the_block(tmp_path, monkeypatch):
    """The parent of ISSUE 33 has no ops/sdar.py: the kind raises before any
    set-up (run.py then exits 1 with no result line)."""
    import builtins

    real_import = builtins.__import__

    def no_sdar(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "oryx_tpu.ops" and "sdar" in (fromlist or ()):
            raise ImportError("cannot import name 'sdar' from 'oryx_tpu.ops'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_sdar)
    cell = {"config": TINY, "traffic": TINY_TRAFFIC, "chips": 1, "scratch": str(tmp_path)}
    t0 = time.monotonic()
    with pytest.raises(ImportError):
        seq_serving.run(cell, 1, 1.0, False, time.time(), lambda **kv: None)
    assert time.monotonic() - t0 < 5.0
