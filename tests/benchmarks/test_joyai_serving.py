"""Kind `joyai-serving` (ISSUE 41): its traffic and configuration files, its
plain reference a layer at a time against the program's, the comparison that
decides `correct` with the five controls that have to fail it, the operations
and bytes of a dispatch, of its expert layer and of its attention, and a CPU
rehearsal of benchmarks/run.py on a test-only tiny cell. No chip: nothing here
is a device number."""

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
from benchmarks.kinds import joyai_serving
from benchmarks.run import find
from joyai_controls import CONTROLS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
TRAFFIC_FILES = [
    f for p in PATHS for f in sorted((REPO / p / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind") == "joyai-serving"
]
REAL = json.loads((REPO / "benchmarks" / "configs" / "joyai-flash-5l.json").read_text())
TINY = json.loads(find(PATHS, "configs/joyai-tiny.json").read_text())
TINY_TRAFFIC = json.loads(find(PATHS, "traffic/next-moe-tiny.json").read_text())
# the source's config.json, every key of the catalog's row
# (/opt/skills/guides/model-configs/architectures.jsonl, JoyAI-LLM-Flash)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}


# -- the traffic is a pure function of the seed -----------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_sessions_and_schedule_are_pure_functions_of_the_seed(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    n_items = 129_280
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
    mine = json.loads((REPO / "benchmarks" / "traffic" / "next4moe.json").read_text())
    next4 = json.loads((REPO / "benchmarks" / "traffic" / "next4.json").read_text())
    basket4 = json.loads((REPO / "benchmarks" / "traffic" / "basket4.json").read_text())
    # every key next4 shares with basket4 is equal here too: the three encoder
    # cells differ in the architecture (and their rates) alone
    same = [k for k in next4 if k in basket4 and next4[k] == basket4[k]]
    assert set(same) >= {"path", "how_many", "zipf_s", "events", "events_median", "events_sigma", "k_bucket",
                         "timeout_s", "block_length", "denoise_steps"}
    assert all(mine[k] == next4[k] for k in same if k != "rate_per_s")
    assert set(mine) == set(next4) and mine["kind"] == "joyai-serving"
    assert mine["rate_per_s"] % 5 == 0 and mine["rate_per_s"] > 0


# -- the configuration file ---------------------------------------------------------

def test_the_configuration_holds_every_published_number_and_cuts_depth_alone():
    # PR 41's entries: present, once, its eleven metrics in order and together (never "last")
    entry, cell, mine = listed.entries_of(BENCH, *listed.ADDED[2])
    assert [k for k, v in CATALOG.items() if REAL.get(k, "absent") != v] == entry["reduced"] == ["num_hidden_layers"]
    assert REAL["published"] == {"num_hidden_layers": 40} and REAL["num_hidden_layers"] == 5
    assert REAL["kind"] == "joyai-serving" and entry["file"] == "benchmarks/configs/joyai-flash-5l.json"
    assert entry["source"] == "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert set(REAL["assumed"]) >= {"mtp", "weights", "router_bias", "bos", "max_len", "basket", "cache_dtype"}
    assert "num_nextn_predict_layers" in REAL["assumed"]["mtp"] and "not loaded" in REAL["assumed"]["mtp"].lower()
    assert "a padded position writes nothing" in REAL["guarantees"] and "absorbed" in REAL["guarantees"]
    assert "pipeline" in REAL["deployment"] and "35" in REAL["deployment"]
    # what one chip holds: the dense layer and four expert layers whole, the embedding, the view
    from oryx_tpu.ops import joyai
    import jax.numpy as jnp

    from oryx_tpu.ops.transfer import row_capacity, view_rows

    cfg = joyai.JoyaiConfig.from_extensions(joyai_serving.extensions(REAL).get)
    assert cfg.layers == 5 and cfg.experts == 256 and cfg.qk_dim == 192 and cfg.basket == 4 and cfg.routed_scale == 2.5
    rows = view_rows(row_capacity(129_280, 0.125), 2048, jnp.bfloat16)  # reference.conf's headroom
    assert rows == 163_840
    held = 2 * (joyai.param_count(cfg) + rows * 2048)
    assert held == pytest.approx(11.26e9, rel=2e-3) and 0.66 < held / (15.75 * 2**30) < 0.68
    assert cell["traffic"] == "next4moe"
    assert [m["name"] for m in mine] == [
        "joyai_encode_ms_per_req", "joyai_step_ms", "joyai_step_mfu", "joyai_step_hbm_roofline", "joyai_step_tokens",
        "joyai_pad_share", "joyai_moe_roofline", "joyai_moe_load_peak", "joyai_experts_touched", "mla_attn_share",
        "mla_attn_roofline",
    ]


# -- the operations and bytes of the algorithm ------------------------------------------

def test_the_work_functions_at_the_published_widths():
    s = joyai_serving._sizes(REAL)
    # ISSUE 41's arithmetic: attention 26.35M a layer, an expert 4.72M
    assert s["proj"] == 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048 == 26_345_472
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 and (s["dense"], s["moe"]) == (1, 4)
    # one token, no context: twice the parameters it runs through
    flops, moved = joyai_serving.attn_work(1, 0, 0, False, REAL)
    assert flops == 2.0 * 26_345_472 and moved == 2 * 26_345_472 + 1152 + 2048 * 8
    # a context position costs a head its 192 + 128 written, its 512 + 64 + 512 absorbed
    assert joyai_serving.attn_work(1, 1, 0, False, REAL)[0] - flops == 2.0 * 32 * 320
    assert joyai_serving.attn_work(1, 1, 0, True, REAL)[0] - flops == 2.0 * 32 * 1088
    # a step reads each sequence's cache once, 1,152 bytes a position, and writes one row
    assert joyai_serving.attn_work(4, 26, 4, True, REAL)[1] - joyai_serving.attn_work(4, 26, 0, True, REAL)[1] == 4 * 26 * 1152
    flops, moved = joyai_serving.moe_work(1, 8, REAL)
    assert flops == 2.0 * 2048 * 256 + 9 * 2.0 * expert                  # the router, 8 routed, 1 shared
    assert moved == 9 * expert * 2 + 2048 * 256 * 2 + 256 * 4 + 2048 * 8
    # ISSUE 41: 46 tokens reach about 195 experts: 1.8 GB a layer
    assert joyai_serving.moe_work(46, 195, REAL)[1] == pytest.approx(1.85e9, rel=0.01)
    per_token = joyai_serving.step_work(1, 0, 0, False, REAL)
    assert per_token == 5 * 2.0 * 26_345_472 + 3 * 2.0 * 2048 * 7168 + 4 * (2.0 * 2048 * 256 + 9 * 2.0 * expert)
    assert joyai_serving.step_work(1, 0, 1, True, REAL) - per_token == 2.0 * 2048 * 129_280
    # a step of 4 sequences that touch 30 experts a layer: ISSUE 41's 1.9-2.9 GB
    step = joyai_serving.step_bytes(4, 4, 26, 4 * 30, True, REAL)
    fixed = 5 * 2 * 26_345_472 + 3 * 2048 * 7168 * 2 + 4 * (expert * 2 + 2048 * 256 * 2 + 256 * 4)
    assert step == pytest.approx(fixed + 120 * expert * 2 + 129_280 * 2048 * 2, rel=2e-3)
    assert 1.9e9 < step < 2.9e9
    # a prefill of 46 tokens that touch 195 experts a layer: 7.4 GB of experts, no head
    prefill = joyai_serving.step_bytes(46, 2, 12, 4 * 195, False, REAL)
    assert prefill == pytest.approx(fixed + 780 * expert * 2, rel=2e-3) and 7.4e9 < prefill < 7.9e9


# -- the kind's reference against the program's ---------------------------------------------

def _tiny_model(seed=5, dtype="float32"):
    import jax.numpy as jnp

    from oryx_tpu.ops import joyai

    ext = dict(joyai_serving.extensions(TINY), dtype=dtype)
    enc = joyai.JoyaiEncoder.from_extensions(ext.get)
    tensors = joyai.init_tensors(enc.cfg, seed, enc.dtype)
    return enc, joyai.params_of(enc.cfg, tensors, enc.dtype), jnp


def test_the_kinds_reference_is_the_programs_reference():
    enc, params, jnp = _tiny_model()
    from oryx_tpu.ops import joyai

    rng = np.random.default_rng(0)
    tokens = np.zeros((2, 28), np.int32)
    tokens[0, :13] = rng.choice(500, size=13, replace=False)
    tokens[1] = rng.choice(500, size=28, replace=False)
    z = np.asarray(joyai_serving.ref_hidden(TINY, params, tokens))
    assert z.shape == (2, 28, 64)
    theirs = np.asarray(joyai.reference_forward(enc.cfg, params, jnp.asarray(tokens[0, :13])))
    np.testing.assert_allclose(z[0, :13], theirs, atol=2e-6)  # causal: the padding behind changes nothing
    np.testing.assert_allclose(
        z[1], np.asarray(joyai.reference_forward(enc.cfg, params, jnp.asarray(tokens[1]))), atol=2e-6
    )


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
    exact = np.asarray(joyai_serving.ref_hidden(TINY, params, session[None, :-1]))[0, -1]
    stated = np.asarray(joyai_serving.ref_hidden(TINY, params, session[None, :-1], act=jnp.bfloat16))[0, -1]
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
    out = joyai_serving.compare(TINY, entries, session, logits, 10)
    assert joyai_serving.holds(joyai_serving.summarise([out] * 16, "float32")) == []
    off = json.loads(json.dumps(entries))
    for e in off:
        e["next"] = [[i, s * (1 + 2 * joyai_serving.SCORE_TIGHT["bfloat16"])] for i, s in e["next"]]
    bad = joyai_serving.compare(TINY, off, session, logits, 10)
    assert "score_err_quartile" in joyai_serving.holds(joyai_serving.summarise([bad] * 16, "bfloat16"))
    low = joyai_serving.compare(TINY, entries, session, logits, 10, rounded=logits * (1 + 2 * joyai_serving.STATED_TIGHT))
    assert "stated_err_quartile" in joyai_serving.holds(joyai_serving.summarise([low] * 16, "bfloat16"))
    assert "stated_err_quartile" not in joyai_serving.summarise([out] * 16, "float32")
    # one request in four off: a routing step, which the quartile lets through and the worst reading holds
    few = joyai_serving.summarise([bad] * 4 + [out] * 12, "bfloat16")
    assert joyai_serving.holds(few) == []


# -- the kind's whole run on the CPU, sound and with each control ----------------------------------

@pytest.mark.parametrize(
    "control,dtype,failing",
    [
        (None, "float32", set()),
        (None, "bfloat16", set()),
        ("latent_cache_in_8_bits", "float32", {"score_err_quartile"}),
        ("latent_cache_in_8_bits", "bfloat16", {"stated_err_quartile"}),
        ("cached_key_not_rotated", "float32", {"score_err_quartile"}),
        ("bias_added_to_the_weights", "float32", {"score_err_quartile"}),
        ("shared_expert_left_out", "bfloat16", {"score_err_quartile", "stated_err_quartile"}),
        ("routed_scale_left_out", "float32", {"score_err_quartile"}),
    ],
    ids=["sound", "sound_bfloat16", "latent_cache_in_8_bits", "latent_cache_in_8_bits_bfloat16",
         "cached_key_not_rotated", "bias_added_to_the_weights", "shared_expert_left_out_bfloat16",
         "routed_scale_left_out"],
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
        out = joyai_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # the next test traces the sound program again
    # on the CPU the batcher pads rows to powers of two: a burst may meet a row
    # count the warm-up never saw, so shapes and compiles are the chip's to hold
    host_side = {"compiles_in_window", "topk_shapes"}
    broken = set(joyai_serving.holds(out["compared"])) - host_side
    assert broken >= failing and (failing or not broken), out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert out["compared"]["requests_compared"][:2] == [10, "=="]  # every request of a 1 s window
    assert out["compared"]["steps_per_basket"][0] == 4 and out["compared"]["dropped_events"][0] == 0
    assert out["compared"]["dropped_pairs"][0] == 0  # no control drops a pair: they break the arithmetic
    assert out["correct"] is (not joyai_serving.holds(out["compared"]))
    assert ("stated_err_quartile" in out["compared"]) is (dtype == "bfloat16")
    src = out["sources"]
    assert set(src) >= {"counters", "dispatch_records", "generator", "collector", "trace", "timeline", "config", "traffic"}
    assert src["counters"]['oryx_seq_steps_total{kind="decode"}'] > 0
    assert src["counters"]["oryx_moe_experts_touched_total"] > 0  # fed by the decode steps too


def test_cpu_rehearsal_prints_the_shared_layers_metrics(tmp_path):
    """run.py end to end on the test-only cell joyai-tiny.next-moe-tiny, found
    by name alone: the counters' and spans' metrics of the shared layers print
    (the readers without a `workloads` list, less the device's: a CPU trace has
    no device plane; `listed.cpu_names` is the rule), and none of the kind's
    own (the cell is on no metric's list)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", "joyai-tiny.next-moe-tiny",
         "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] == 20
    listed.printed_on_the_cpu_holds(BENCH, "joyai-tiny.next-moe-tiny", last["metrics"])
    assert last["compared"]["steps_per_basket"] == [4.0, "==", 4]
    assert last["compared"]["dropped_pairs"] == [0.0, "==", 0]
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")


def test_the_kind_fails_at_once_on_a_tree_without_the_decoder(tmp_path, monkeypatch):
    """The parent of ISSUE 41 has no ops/joyai.py: the kind raises before any
    set-up (run.py then exits 1 with no result line)."""
    import builtins

    real_import = builtins.__import__

    def no_joyai(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "oryx_tpu.ops" and "joyai" in (fromlist or ()):
            raise ImportError("cannot import name 'joyai' from 'oryx_tpu.ops'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_joyai)
    cell = {"config": TINY, "traffic": TINY_TRAFFIC, "chips": 1, "scratch": str(tmp_path)}
    t0 = time.monotonic()
    with pytest.raises(ImportError):
        joyai_serving.run(cell, 1, 1.0, False, time.time(), lambda **kv: None)
    assert time.monotonic() - t0 < 5.0
