"""Kind `trinity-serving` (ISSUE 44): its traffic and configuration files, its
plain reference a layer at a time against the program's, the comparison that
decides `correct` with the six controls that have to fail it, the operations
and bytes of a dispatch, of its expert layer (the HELD share) and of its
attention, and a CPU rehearsal of benchmarks/run.py on a test-only tiny cell.
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
from benchmarks import seqgen
from benchmarks.kinds import trinity_serving
from benchmarks.run import find, metrics_of
from trinity_controls import CONTROLS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
CELL = "trinity-large-5l.next4ep"
TRAFFIC_FILES = [
    f for p in PATHS for f in sorted((REPO / p / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind") == "trinity-serving"
]
REAL = json.loads((REPO / "benchmarks" / "configs" / "trinity-large-5l.json").read_text())
TINY = json.loads(find(PATHS, "configs/trinity-tiny.json").read_text())
TINY_TRAFFIC = json.loads(find(PATHS, "traffic/next-ep-tiny.json").read_text())
SLIDING, FULL = "sliding_attention", "full_attention"
# the source's config.json, every key of the catalog's row
# (/opt/skills/guides/model-configs/architectures.jsonl, Trinity-Large-Preview)
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072,
    "intermediate_size": 12288, "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
MINE = [
    "trinity_encode_ms_per_req", "trinity_step_ms", "trinity_step_tokens", "trinity_pad_share", "trinity_step_mfu",
    "trinity_step_hbm_roofline", "trinity_moe_roofline", "trinity_experts_touched", "trinity_held_pair_share",
    "trinity_attn_share", "trinity_attn_roofline", "trinity_head_share",
]


# -- the traffic is a pure function of the seed -----------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_sessions_and_schedule_are_pure_functions_of_the_seed(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    n_items = 200_192
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


def test_the_cell_runs_next4s_sessions_at_a_rate_on_a_rung_of_five():
    mine = json.loads((REPO / "benchmarks" / "traffic" / "next4ep.json").read_text())
    next4 = json.loads((REPO / "benchmarks" / "traffic" / "next4.json").read_text())
    next4moe = json.loads((REPO / "benchmarks" / "traffic" / "next4moe.json").read_text())
    # every key but the kind, the rate and the reason is next4's and next4moe's: the four
    # encoder cells differ in the architecture (and their rates) alone
    for theirs in (next4, next4moe):
        assert set(mine) == set(theirs)
        assert all(mine[k] == theirs[k] for k in mine if k not in ("kind", "rate_per_s", "why"))
    assert mine["kind"] == "trinity-serving" and mine["rate_per_s"] % 5 == 0 and mine["rate_per_s"] > 0
    assert f"at {mine['rate_per_s']} req/s" in mine["why"] and "ladder" in mine["why"]
    # the sessions never reach the window: at the published width its mask clips nothing in this cell
    assert mine["events"][1] + REAL["basket"] <= REAL["max_len"] + REAL["basket"] < REAL["sliding_window"]


# -- the configuration file ---------------------------------------------------------

def test_the_configuration_holds_every_published_number_and_cuts_depth_and_the_experts_held():
    # this PR's entries: present, once, its twelve metrics in order and together (never "last")
    entry, cell, mine = listed.entries_of(BENCH, "trinity-large-5l", CELL, lambda name: name.startswith("trinity_"), 12)
    changed = [k for k, v in CATALOG.items() if REAL.get(k, "absent") != v]
    assert sorted(changed) == sorted(entry["reduced"]) == ["layer_types", "num_dense_layers", "num_experts", "num_hidden_layers"]
    assert sorted(REAL["reduced"]) == sorted(entry["reduced"])
    assert (REAL["num_hidden_layers"], REAL["num_dense_layers"], REAL["num_experts"]) == (5, 1, 32)
    assert REAL["layer_types"] == CATALOG["layer_types"][:5] == [SLIDING, SLIDING, SLIDING, FULL, SLIDING]
    # every kind among the four expert layers, a whole period
    assert set(REAL["layer_types"][1:]) == {SLIDING, FULL} and len(REAL["layer_types"][1:]) == CATALOG["global_attn_every_n_layers"]
    assert {k: REAL["published"][k] for k in ("num_hidden_layers", "num_dense_layers", "num_experts")} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
    }
    # the router keeps the published width, and eight chips share a layer
    assert REAL["num_experts_routed"] == 256 == REAL["chips_sharing_a_layer"] * REAL["num_experts"]
    assert "8 chips" in REAL["deployment"] and "32 a chip" in REAL["deployment"] and "pipeline" in REAL["deployment"]
    assert "EIGHTH" in REAL["deployment"] and "EIGHTH" in cell["why"]
    assert REAL["kind"] == "trinity-serving" and entry["file"] == "benchmarks/configs/trinity-large-5l.json"
    assert entry["source"] == "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json"
    assert REAL["source"].startswith(entry["source"]) and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert set(REAL["assumed"]) >= {
        "modelling", "norm_gains", "weights", "router_bias", "bos", "max_len", "basket", "cache_dtype", "held_share",
    }
    for said in ("gate", "sliding layers alone", "sqrt(hidden_size)", "1e-20", "four RMS norms"):
        assert said in REAL["assumed"]["modelling"], said
    assert "sent elsewhere" in REAL["guarantees"] and "a slot taken again starts empty" in REAL["guarantees"]
    # what one chip holds: the dense layer, four expert layers' shares, the embedding, the view
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity
    from oryx_tpu.ops.transfer import row_capacity, view_rows

    cfg = trinity.TrinityConfig.from_extensions(trinity_serving.extensions(REAL).get)
    assert (cfg.layers, cfg.experts, cfg.held, cfg.first_expert, cfg.basket) == (5, 256, 32, 0, 4)
    assert cfg.routing == {"scoring": "sigmoid", "scale": 2.448, "held": (0, 32)}
    assert cfg.layer_types == tuple(REAL["layer_types"]) and cfg.sliding_window == 4096 and cfg.eps == 1e-5
    rows = view_rows(row_capacity(200_192, 0.125), 3072, jnp.bfloat16)  # reference.conf's headroom
    assert rows == 229_376
    held = 2 * (trinity.param_count(cfg) + rows * 3072)
    assert held == pytest.approx(10.98e9, rel=2e-3) and 0.64 < held / (15.75 * 2**30) < 0.66
    assert cell["traffic"] == "next4ep" and [m["name"] for m in mine] == MINE
    # the cell reads the shared layers' metrics, the stepper's five and its own twelve
    names = {m["name"] for m in metrics_of(BENCH["per_layer"], CELL)}
    shared = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert names == shared | listed.STEPPER | set(MINE)
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"] or m["name"].endswith("_share"):
            assert m["unit"] == "%", m


# -- the operations and bytes of the algorithm ------------------------------------------

def test_the_work_functions_at_the_published_widths():
    s = trinity_serving._sizes(REAL)
    # ISSUE 44's arithmetic: attention 62.91M a layer, an expert 28.31M
    assert s["proj"] == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 == 62_914_560
    expert = 3 * 3072 * 3072
    assert expert == 28_311_552 and (s["dense"], s["moe"], s["sliding"], s["e"], s["held"]) == (1, 4, 4, 256, 32)
    # one token, no context: twice the parameters it runs through; its key and value written
    flops, moved = trinity_serving.attn_work(1, 0, 0, False, REAL)
    assert flops == 2.0 * 62_914_560 and moved == 2 * 62_914_560 + 4096 + 3072 * 8
    # a context position costs the 48 query heads a score and a value of 128 each
    assert trinity_serving.attn_work(1, 1, 0, False, REAL)[0] - flops == 2.0 * 2.0 * 6144
    # a step reads each sequence's cache once, 4,096 bytes a position (8 key-value heads), a prefill none
    read = trinity_serving.attn_work(4, 26, 4, True, REAL)[1] - trinity_serving.attn_work(4, 26, 0, True, REAL)[1]
    assert read == 4 * 26 * 4096
    assert trinity_serving.attn_work(4, 26, 4, False, REAL)[1] == trinity_serving.attn_work(4, 26, 0, False, REAL)[1]
    # the expert layer HERE: the router over all 256 and the shared expert a token, a routed expert a PAIR
    # computed here; the touched held experts' matrices and not the 32 held, never the 256
    flops, moved = trinity_serving.moe_work(8, 4, 3, REAL)
    assert flops == 8 * (2.0 * 3072 * 256 + 2.0 * expert) + 4 * 2.0 * expert
    assert moved == 4 * expert * 2 + 3072 * 256 * 2 + 256 * 4 + 8 * 3072 * 8
    # ISSUE 44: a prefill of 30 tokens reaches about 12 of the 32 held experts a layer: 0.69 GB a layer, + the shared
    assert trinity_serving.moe_work(30, 15, 12, REAL)[1] == pytest.approx(0.69e9 + 0.057e9, rel=0.02)
    per_token = trinity_serving.step_work(1, 0, 0, 0, REAL)
    assert per_token == 5 * 2.0 * 62_914_560 + 3 * 2.0 * 3072 * 12288 + 4 * (2.0 * 3072 * 256 + 2.0 * expert)
    assert trinity_serving.step_work(1, 0, 1, 0, REAL) - per_token == 2.0 * 3072 * 200_192
    assert trinity_serving.step_work(1, 0, 0, 2, REAL) - per_token == 2 * 2.0 * expert
    # a window narrower than the context clips the sliding layers' share alone
    narrow = dict(REAL, sliding_window=10)
    wide = trinity_serving.step_work(1, 50, 0, 0, REAL) - per_token
    assert wide == 5 * 2.0 * 2.0 * 6144 * 50
    assert trinity_serving.step_work(1, 50, 0, 0, narrow) - per_token == 2.0 * 2.0 * 6144 * (4 * 10 + 50)
    # a step of 1.4 sequences that touch 0.7 held experts a layer: ISSUE 44's "about 2.9 GB"
    # was for a head of 262,144 capacity rows; over the catalog's 200,192 rows it is 2.5 GB
    step = trinity_serving.step_bytes(1.4, 1.4, 26, 4 * 0.7, True, REAL)
    fixed = 5 * 2 * 62_914_560 + 3 * 3072 * 12288 * 2 + 4 * (expert * 2 + 3072 * 256 * 2 + 256 * 4)
    assert step == pytest.approx(fixed + 2.8 * expert * 2 + 200_192 * 3072 * 2, rel=2e-3)
    assert fixed == pytest.approx(1.09e9, rel=0.01) and 2.4e9 < step < 2.6e9
    # a prefill of 30 tokens that touch 12 held experts a layer: 3.8 GB, no head
    prefill = trinity_serving.step_bytes(30, 1.3, 12, 4 * 12, False, REAL)
    assert prefill == pytest.approx(fixed + 48 * expert * 2, rel=3e-3) and 3.7e9 < prefill < 3.9e9


# -- the kind's reference against the program's ---------------------------------------------

def _tiny_model(seed=5, dtype="float32"):
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity

    ext = dict(trinity_serving.extensions(TINY), dtype=dtype)
    enc = trinity.TrinityEncoder.from_extensions(ext.get)
    tensors = trinity.init_tensors(enc.cfg, seed, enc.dtype)
    return enc, trinity.params_of(enc.cfg, tensors, enc.dtype), jnp


def test_the_kinds_reference_is_the_programs_reference():
    """The window of 8 clips both sessions, and 4 of the 16 experts are held
    (the second share): the two references leave out the same part."""
    enc, params, jnp = _tiny_model()
    from oryx_tpu.ops import trinity

    assert enc.cfg.sliding_window == 8 and enc.cfg.routing["held"] == (4, 4)
    rng = np.random.default_rng(0)
    tokens = np.zeros((2, 28), np.int32)
    tokens[0, :13] = rng.choice(500, size=13, replace=False)
    tokens[1] = rng.choice(500, size=28, replace=False)
    z = np.asarray(trinity_serving.ref_hidden(TINY, params, tokens))
    assert z.shape == (2, 28, 64)
    theirs = np.asarray(trinity.reference_forward(enc.cfg, params, jnp.asarray(tokens[0, :13])))
    np.testing.assert_allclose(z[0, :13], theirs, atol=5e-6)  # causal: the padding behind changes nothing
    np.testing.assert_allclose(
        z[1], np.asarray(trinity.reference_forward(enc.cfg, params, jnp.asarray(tokens[1]))), atol=5e-6
    )
    # and it is the window that clips: a wider one is another function
    wide = np.asarray(trinity_serving.ref_hidden(dict(TINY, sliding_window=4096), params, tokens))
    assert np.abs(wide[1, 8:] - z[1, 8:]).max() > 1e-2 and np.abs(wide[1, :8] - z[1, :8]).max() < 5e-6


def test_the_reference_with_the_stated_rounding_is_the_served_arithmetic():
    """bfloat16 weights, activations and cache: the float32 reference differs
    from the served prefill by the rounding; the same plain pass with every
    product's inputs at bfloat16's values differs from it by the order of
    accumulation alone."""
    import jax.numpy as jnp

    enc, params, _ = _tiny_model(dtype="bfloat16")
    rng = np.random.default_rng(2)
    session = rng.choice(500, size=13, replace=False).astype(np.int32)
    state = enc.init_state(enc.step_rows)
    _, served, _ = enc.prefill(params, state, *enc.pack([session], 24, [0], enc.step_rows))
    from oryx_tpu.ops.sdar import rms_norm

    served = np.asarray(rms_norm(served, params["final_norm"], enc.cfg.eps)[0])
    exact = np.asarray(trinity_serving.ref_hidden(TINY, params, session[None, :-1]))[0, -1]
    stated = np.asarray(trinity_serving.ref_hidden(TINY, params, session[None, :-1], act=jnp.bfloat16))[0, -1]
    scale = np.abs(exact).max()
    assert 1e-4 < np.abs(served - exact).max() / scale < 3e-2   # the rounding
    assert np.abs(served - stated).max() / scale < 2e-5         # the same arithmetic


# -- the comparison on hand-made answers ----------------------------------------------------

def test_summarise_holds_this_kinds_own_limits():
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((4, 500)).astype(np.float32)
    session = np.asarray([3, 4, 5])
    entries = []
    for b in range(4):
        open_ = logits[b].copy()
        open_[session] = -np.inf
        top = np.argsort(-open_, kind="stable")[:10]
        entries.append({"item": f"i{int(np.argmax(logits[b]))}", "step": b,
                        "next": [[f"i{r}", float(logits[b][r])] for r in top]})
    out = trinity_serving.compare(TINY, entries, session, logits, 10)
    assert trinity_serving.holds(trinity_serving.summarise([out] * 16, "float32")) == []
    off = json.loads(json.dumps(entries))
    for e in off:
        e["next"] = [[i, s * (1 + 2 * trinity_serving.SCORE_TIGHT["bfloat16"])] for i, s in e["next"]]
    bad = trinity_serving.compare(TINY, off, session, logits, 10)
    assert "score_err_quartile" in trinity_serving.holds(trinity_serving.summarise([bad] * 16, "bfloat16"))
    low = trinity_serving.compare(TINY, entries, session, logits, 10, rounded=logits * (1 + 2 * trinity_serving.STATED_TIGHT))
    assert "stated_err_quartile" in trinity_serving.holds(trinity_serving.summarise([low] * 16, "bfloat16"))
    assert "stated_err_quartile" not in trinity_serving.summarise([out] * 16, "float32")
    # one request in four off: a routing step, which the quartile lets through and the worst reading holds
    few = trinity_serving.summarise([bad] * 4 + [out] * 12, "bfloat16")
    assert trinity_serving.holds(few) == []
    # the limits lie between the chip's sound readings and its controls' (PERF.md has both)
    assert trinity_serving.SCORE_TIGHT["float32"] < trinity_serving.STATED_TIGHT < trinity_serving.SCORE_TIGHT["bfloat16"]
    assert trinity_serving.SCORE_TIGHT["bfloat16"] < trinity_serving.SCORE_LOOSE < 1.0


# -- the kind's whole run on the CPU, sound and with each control ----------------------------------

@pytest.mark.parametrize(
    "control,dtype,failing",
    [
        (None, "float32", set()),
        (None, "bfloat16", set()),
        ("kv_cache_in_8_bits", "float32", {"score_err_quartile"}),
        ("kv_cache_in_8_bits", "bfloat16", {"stated_err_quartile"}),
        ("sliding_keys_not_rotated", "float32", {"score_err_quartile"}),
        ("attention_gate_left_out", "float32", {"score_err_quartile"}),
        ("shared_expert_left_out", "bfloat16", {"score_err_quartile", "stated_err_quartile"}),
        ("route_scale_left_out", "float32", {"score_err_quartile"}),
        ("another_chips_share_computed", "bfloat16", {"score_err_quartile", "stated_err_quartile"}),
    ],
    ids=["sound", "sound_bfloat16", "kv_cache_in_8_bits", "kv_cache_in_8_bits_bfloat16", "sliding_keys_not_rotated",
         "attention_gate_left_out", "shared_expert_left_out_bfloat16", "route_scale_left_out",
         "another_chips_share_computed_bfloat16"],
)
def test_a_fault_under_the_timed_path_reads_not_correct(control, dtype, failing, tmp_path, monkeypatch):
    """The kind's whole run in this process (run.py's look for a chip is
    skipped), the program broken underneath by each control: `correct` is
    false exactly when a compared number breaks its limit, and the tight
    limit on the scores is among them. bfloat16 where float32 is stated (the
    cache in 8 bits where bfloat16 is) fails one."""
    import jax

    if control:
        CONTROLS[control](monkeypatch.setattr)
    cell = {"name": "trinity-tiny.next-ep-tiny", "config": dict(TINY, dtype=dtype), "traffic": TINY_TRAFFIC,
            "chips": 1, "scratch": str(tmp_path)}
    try:
        out = trinity_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # the next test traces the sound program again
    # on the CPU the batcher pads rows to powers of two: a burst may meet a row
    # count the warm-up never saw, so shapes and compiles are the chip's to hold
    host_side = {"compiles_in_window", "topk_shapes"}
    broken = set(trinity_serving.holds(out["compared"])) - host_side
    assert broken >= failing and (failing or not broken), out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert out["compared"]["requests_compared"][:2] == [10, "=="]  # every request of a 1 s window
    assert out["compared"]["steps_per_basket"][0] == 4 and out["compared"]["dropped_events"][0] == 0
    # no control drops a pair, and a pair sent elsewhere is not a dropped pair
    assert out["compared"]["dropped_pairs"][0] == 0
    assert out["correct"] is (not trinity_serving.holds(out["compared"]))
    assert ("stated_err_quartile" in out["compared"]) is (dtype == "bfloat16")
    src = out["sources"]
    assert set(src) >= {"counters", "dispatch_records", "generator", "collector", "trace", "timeline", "config", "traffic"}
    counters = src["counters"]
    assert counters['oryx_seq_steps_total{kind="decode"}'] > 0
    assert counters["oryx_moe_experts_touched_total"] > 0  # fed by the decode steps too
    # 4 of the 16 experts are held: most pairs go elsewhere, and both kinds are counted
    assert 0 < counters["oryx_moe_routed_total"] < counters["oryx_moe_routed_elsewhere_total"]


def test_a_precision_below_the_stated_float32_fails_the_tolerance(tmp_path):
    """The program run in bfloat16 while the configuration states float32 (the
    reference then has no stated rounding to share): not `correct`, by the
    float32 limit on the scores."""
    import jax

    cell = {"name": "trinity-tiny.next-ep-tiny", "config": dict(TINY, dtype="bfloat16"), "traffic": TINY_TRAFFIC,
            "chips": 1, "scratch": str(tmp_path)}
    try:
        out = trinity_serving.run(cell, 2**31 + 13, 1.0, False, time.time(), lambda **kv: None)
    finally:
        jax.clear_caches()
    readings = out["compared"]["score_err_quartile"][0]
    assert readings > 10 * trinity_serving.SCORE_TIGHT["float32"]
    held_as_float32 = dict(out["compared"], score_err_quartile=[readings, "<=", trinity_serving.SCORE_TIGHT["float32"]])
    assert "score_err_quartile" in trinity_serving.holds(held_as_float32)


def test_cpu_rehearsal_prints_the_shared_layers_metrics(tmp_path):
    """run.py end to end on the test-only cell trinity-tiny.next-ep-tiny,
    found by name alone: the counters' and spans' metrics of the shared layers
    print (the readers without a `workloads` list, less the device's: a CPU
    trace has no device plane; `listed.cpu_names` is the rule), and none of
    the kind's own (the cell is on no metric's list)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", "trinity-tiny.next-ep-tiny",
         "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] == 20
    listed.printed_on_the_cpu_holds(BENCH, "trinity-tiny.next-ep-tiny", last["metrics"])
    assert not [name for name in last["metrics"] if name.startswith("trinity_")]
    assert last["compared"]["steps_per_basket"] == [4.0, "==", 4]
    assert last["compared"]["dropped_pairs"] == [0.0, "==", 0]
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")
    notes = [json.loads(ln)["info"] for ln in lines[:-1]]
    states = [n["slot_state_bytes"] for n in notes if "slot_state_bytes" in n]
    # two kinds of key-value state in one slot: three sliding layers of 8 rows, one full layer of 28
    assert states == [{"window_kv": 3 * 33 * 8 * 2 * 2 * 16 * 4.0, "full_kv": 33 * 28 * 2 * 2 * 16 * 4.0}]


def test_the_kind_fails_at_once_on_a_tree_without_the_decoder(tmp_path, monkeypatch):
    """The parent of ISSUE 44 has no ops/trinity.py: the kind raises before
    any set-up (run.py then exits 1 with no result line)."""
    import builtins

    real_import = builtins.__import__

    def no_trinity(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "oryx_tpu.ops" and "trinity" in (fromlist or ()):
            raise ImportError("cannot import name 'trinity' from 'oryx_tpu.ops'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_trinity)
    cell = {"name": "trinity-tiny.next-ep-tiny", "config": TINY, "traffic": TINY_TRAFFIC, "chips": 1,
            "scratch": str(tmp_path)}
    t0 = time.monotonic()
    with pytest.raises(ImportError):
        trinity_serving.run(cell, 1, 1.0, False, time.time(), lambda **kv: None)
    assert time.monotonic() - t0 < 5.0
