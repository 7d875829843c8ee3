"""Kind `xing-serving`: its traffic and configuration files, its plain
reference a layer at a time against the program's, the comparison that
decides `correct` with the controls that have to fail it, the operations and
bytes of a dispatch, of its expert layer, of its attention and of its
hyper-connections, and a CPU rehearsal of benchmarks/run.py on a test-only
tiny cell. No chip: nothing here is a device number."""

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import listed
from benchmarks import seqgen
from benchmarks.kinds import joyai_serving, xing_serving
from benchmarks.run import find, metrics_of
from xing_controls import CONTROLS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
CELL = "xing4-29b-7l.next4long"
TRAFFIC_FILES = [
    f for p in PATHS for f in sorted((REPO / p / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind") == "xing-serving"
]
REAL = json.loads((REPO / "benchmarks" / "configs" / "xing4-29b-7l.json").read_text())
TINY = json.loads(find(PATHS, "configs/xing-tiny.json").read_text())
TINY_TRAFFIC = json.loads(find(PATHS, "traffic/next-long-tiny.json").read_text())
# the source's config.json, every key of the catalog's row
# (the model-configs guide's architectures.jsonl, Xing4.0-29B-A4B)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
    "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}
MINE = [
    "xing_encode_ms_per_req", "xing_step_ms", "xing_step_tokens", "xing_pad_share", "xing_step_mfu",
    "xing_step_hbm_roofline", "xing_moe_roofline", "xing_experts_touched", "xing_hc_share", "xing_hc_roofline",
    "xing_attn_share", "xing_attn_roofline", "xing_head_share", "xing_moe_load_peak",
]


# -- the traffic is a pure function of the seed -----------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_sessions_and_schedule_are_pure_functions_of_the_seed(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # a benchmark's seeds may not fit 32 signed bits
    n_items = 131_072
    a = seqgen.draw_sessions(seed, n_items, traffic, 400)
    b = seqgen.draw_sessions(seed, n_items, traffic, 300)
    c = seqgen.draw_sessions(seed + 1, n_items, traffic, 300)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))  # session i does not depend on n
    assert not all(np.array_equal(x, y) for x, y in zip(b, c))
    lo, hi = traffic["events"]
    lengths = np.asarray([len(s) for s in a])
    assert lengths.min() >= lo and lengths.max() <= hi
    assert abs(np.median(lengths) - traffic["events_median"]) <= 0.1 * traffic["events_median"]
    assert all(len(set(s.tolist())) == len(s) for s in a)  # distinct within a session
    assert all(0 <= s.min() and s.max() < n_items for s in a)
    buckets = {k_bucket(traffic["how_many"] + n + 8) for n in range(lo, hi + 1)}
    assert buckets == {traffic["k_bucket"]}
    s1 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    s2 = seqgen.draw_schedule(seed, traffic, 6.0, 40.0)
    assert np.array_equal(s1["due"], s2["due"])
    assert int(s1["in_window"].sum()) == round(traffic["rate_per_s"] * 40.0)
    assert traffic["block_length"] == traffic["denoise_steps"] == 4


def test_the_cell_fills_the_window_at_a_rate_on_a_rung_of_five():
    """Sessions of 64-100 events, median 90: every prefill runs in the
    100-position bucket, about nine tenths of it real, and about a sixth of
    the sessions reach the app's 100-event window; the rest of next4's keys
    as they are."""
    mine = json.loads((REPO / "benchmarks" / "traffic" / "next4long.json").read_text())
    next4 = json.loads((REPO / "benchmarks" / "traffic" / "next4.json").read_text())
    assert set(mine) == set(next4)
    lengths = ("events", "events_median", "events_sigma")
    assert all(mine[k] == next4[k] for k in mine if k not in ("kind", "rate_per_s", "why") + lengths)
    assert mine["events"] == [64, 100] and mine["events_median"] == 90 and mine["events_sigma"] == 0.1
    assert mine["kind"] == "xing-serving" and mine["rate_per_s"] % 5 == 0 and mine["rate_per_s"] >= 5
    assert f"at {mine['rate_per_s']} req/s" in mine["why"] and "ladder" in mine["why"]
    sessions = seqgen.draw_sessions(2**31 + 5, 131_072, mine, 3000)
    n = np.asarray([len(s) for s in sessions])
    assert 0.12 < np.mean(n == 100) < 0.2                          # about a sixth at the window
    assert np.all(n - 1 > 32) and 0.85 < np.mean(n - 1) / 100 < 0.95  # the 100-bucket, nine tenths real
    assert mine["events"][1] == REAL["max_len"]


# -- the configuration file ---------------------------------------------------------

def test_the_configuration_holds_every_published_number_and_cuts_depth_and_the_leading_dense_layers():
    # this PR's entries: present, once, its fourteen metrics in order and together (never "last")
    entry, cell, mine = listed.entries_of(BENCH, "xing4-29b-7l", CELL, lambda name: name.startswith("xing_"), 14)
    changed = [k for k, v in CATALOG.items() if REAL.get(k, "absent") != v]
    assert sorted(changed) == sorted(entry["reduced"]) == ["first_k_dense_replace", "num_hidden_layers"]
    assert sorted(REAL["reduced"]) == sorted(entry["reduced"])
    assert (REAL["num_hidden_layers"], REAL["first_k_dense_replace"]) == (7, 1)
    assert REAL["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    # every width as published, the streams and the iterations among them
    assert (REAL["hc_mult"], REAL["hc_sinkhorn_iters"], REAL["n_routed_experts"], REAL["vocab_size"]) == (4, 20, 64, 131072)
    assert "six-stage pipeline" in REAL["deployment"] and "Nothing is sharded" in REAL["deployment"]
    assert REAL["kind"] == "xing-serving" and entry["file"] == "benchmarks/configs/xing4-29b-7l.json"
    assert entry["source"] == "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
    assert REAL["source"].startswith(entry["source"]) and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell["why"]) <= 200 and cell["chips"] == 1 and cell["traffic"] == "next4long"
    assert set(REAL["assumed"]) >= {
        "maps", "streams_in_out", "hc_eps", "maps_norm", "maps_init", "rope_interleave", "yarn", "mtp", "weights",
        "router_bias", "bos", "max_len", "basket", "cache_dtype",
    }
    for said in ("factor 2", "sigmoid", "clamp", "20"):
        assert said in REAL["assumed"]["maps"], said
    for said in ("every sublayer's three maps are computed for every real token", "all 20 Sinkhorn iterations",
                 "the Sinkhorn and the mixes are float32", "a slot taken again starts empty"):
        assert said in REAL["guarantees"], said
    # what one chip holds: 11.31 GB of program arguments by the arithmetic
    import jax.numpy as jnp

    from oryx_tpu.ops import xing
    from oryx_tpu.ops.transfer import row_capacity, view_rows

    cfg = xing.XingConfig.from_extensions(xing_serving.extensions(REAL).get)
    assert (cfg.layers, cfg.experts, cfg.hc_mult, cfg.hc_iters, cfg.basket) == (7, 64, 4, 20, 4)
    assert cfg.yarn == (64.0, 4096, 32.0, 1.0, 1.0) and cfg.hc_clamp == (-30.0, 30.0) and cfg.routed_scale == 2.0
    rows = view_rows(row_capacity(131_072, 0.125), 3584, jnp.bfloat16)  # reference.conf's headroom
    assert rows == 163_840
    maps = 7 * 2 * (4 * 3584 * 24 + 3 + 24)  # float32, the rest bfloat16
    held = 2 * (xing.param_count(cfg) - maps) + 4 * maps + 2 * rows * 3584
    assert held == pytest.approx(11.31e9, rel=3e-3) and 0.66 < held / (15.75 * 2**30) < 0.68
    assert [m["name"] for m in mine] == MINE
    # the cell reads the shared layers' metrics, the stepper's five and its own fourteen
    names = {m["name"] for m in metrics_of(BENCH["per_layer"], CELL)}
    shared = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert names == shared | listed.STEPPER | set(MINE)
    assert {m["layer"] for m in mine} == {"batched encoder step", "expert layer", "hyper-connections",
                                          "latent attention", "catalog head"}


# -- the operations and bytes of the algorithm ------------------------------------------

def test_the_work_functions_at_the_published_widths():
    h, n, w = 3584, 4, 24
    flops, moved = xing_serving.hc_work(1, REAL)
    # a token's sublayer: the maps' product dominates, 2 x 14,336 x 24; the mix 2 x 16 x 3,584
    product, mix = 2.0 * n * h * w, 2.0 * n * n * h
    assert product == 688_128 and product < flops < 1.25 * (product + mix)
    sinkhorn = n * n * (1 + 4 * 20)
    assert flops == 3 * n * h + product + 4 * w + sinkhorn + 2 * n * h + mix + 3 * n * h
    # phi once (1.38 MB), a token's four float32 streams read and written once and the sublayer's output read
    assert moved == (n * h * w + 3 + w) * 4.0 + (2 * n * h + h) * 4.0
    assert xing_serving.hc_work(400, REAL)[1] - xing_serving.hc_work(0, REAL)[1] == 400 * 9 * h * 4
    # a prefill of 400 tokens moves about 57 KB a token a pass over 14 sublayers
    assert 14 * xing_serving.hc_work(400, REAL)[1] == pytest.approx(14 * (1.38e6 + 400 * 2.25 * 57.3e3), rel=0.01)
    # a dispatch is kind joyai-serving's work and every sublayer's hyper-connection
    for tokens, context, head, absorbed in ((1, 0, 0, False), (9, 92, 9, True), (360, 45, 0, False)):
        base = joyai_serving.step_work(tokens, context, head, absorbed, REAL)
        assert xing_serving.step_work(tokens, context, head, absorbed, REAL) == base + 14 * xing_serving.hc_work(tokens, REAL)[0]
    s = xing_serving._sizes(REAL)
    assert (s["dense"], s["moe"], s["e"], s["k"], s["f"]) == (1, 6, 64, 4, 1024)
    assert s["proj"] == 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 32 * 128 * 3584
    # a step of 9 live tokens touching about 28 of 64 experts a layer streams about 5.4 GB
    step = xing_serving.step_bytes(9, 9, 92, 6 * 28, True, REAL)
    assert 5.1e9 < step < 5.6e9
    # a prefill of 4 x 89 tokens touching all 64 experts a layer: 9.2 GB of weights, 0.66 GB of the streams
    prefill = xing_serving.step_bytes(356, 4, 45, 6 * 64, False, REAL)
    assert 14 * xing_serving.hc_work(356, REAL)[1] == pytest.approx(0.66e9, rel=0.01)
    assert 9.8e9 < prefill < 10.2e9


def test_the_rotation_and_the_divisor_are_the_programs():
    from oryx_tpu.ops import xing

    cfg = xing.XingConfig.from_extensions(xing_serving.extensions(REAL).get)
    np.testing.assert_allclose(xing_serving.frequencies(REAL), cfg.frequencies, rtol=1e-6)
    assert xing_serving.divisor(REAL) == pytest.approx(cfg.divisor, rel=1e-12)
    assert math.sqrt(192) / xing_serving.divisor(REAL) == pytest.approx(2.0047, abs=1e-4)
    plain = dict(REAL, rope_scaling=None)
    np.testing.assert_allclose(xing_serving.frequencies(plain), 10_000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    assert xing_serving.divisor(plain) == math.sqrt(192)


# -- the kind's reference against the program's ---------------------------------------------

def _tiny_model(seed=5, dtype="float32"):
    import jax.numpy as jnp

    from oryx_tpu.ops import xing

    ext = dict(xing_serving.extensions(TINY), dtype=dtype)
    enc = xing.XingEncoder.from_extensions(ext.get)
    tensors = xing.init_tensors(enc.cfg, seed, enc.dtype)
    return enc, xing.params_of(enc.cfg, tensors, enc.dtype), jnp


def test_the_kinds_reference_is_the_programs_reference():
    enc, params, jnp = _tiny_model()
    from oryx_tpu.ops import xing

    rng = np.random.default_rng(0)
    tokens = np.zeros((2, 28), np.int32)
    tokens[0, :13] = rng.choice(500, size=13, replace=False)
    tokens[1] = rng.choice(500, size=28, replace=False)
    z = np.asarray(xing_serving.ref_hidden(TINY, params, tokens))
    assert z.shape == (2, 28, 64)
    theirs = np.asarray(xing.reference_forward(enc.cfg, params, jnp.asarray(tokens[0, :13])))
    np.testing.assert_allclose(z[0, :13], theirs, atol=5e-6)  # causal: the padding behind changes nothing
    np.testing.assert_allclose(
        z[1], np.asarray(xing.reference_forward(enc.cfg, params, jnp.asarray(tokens[1]))), atol=5e-6
    )
    # the mix weighs: with the residual map's logits clamped to 0 (M uniform) it is another function
    uniform = dict(TINY, mhc_h_res_clamp_min=0, mhc_h_res_clamp_max=0)
    assert np.abs(np.asarray(xing_serving.ref_hidden(uniform, params, tokens)) - z).max() > 1e-4


def test_the_reference_with_the_stated_rounding_is_the_served_arithmetic():
    """bfloat16 weights, activations and cache: the float32 reference differs
    from the served prefill by the rounding; the same plain pass with every
    product's inputs at bfloat16's values (the maps float32, as stated)
    differs from it by the order of accumulation alone."""
    import jax.numpy as jnp

    enc, params, _ = _tiny_model(dtype="bfloat16")
    rng = np.random.default_rng(2)
    session = rng.choice(500, size=13, replace=False).astype(np.int32)
    state = enc.init_state(enc.step_rows)
    _, served, _ = enc.prefill(params, state, *enc.pack([session], 24, [0], enc.step_rows))
    from oryx_tpu.ops.decoder import rms_norm

    served = np.asarray(rms_norm(served, params["final_norm"], enc.cfg.eps)[0])
    exact = np.asarray(xing_serving.ref_hidden(TINY, params, session[None, :-1]))[0, -1]
    stated = np.asarray(xing_serving.ref_hidden(TINY, params, session[None, :-1], act=jnp.bfloat16))[0, -1]
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
    out = xing_serving.compare(TINY, entries, session, logits, 10)
    assert xing_serving.holds(xing_serving.summarise([out] * 16, "float32")) == []
    off = json.loads(json.dumps(entries))
    for e in off:
        e["next"] = [[i, s * (1 + 2 * xing_serving.SCORE_TIGHT["bfloat16"])] for i, s in e["next"]]
    bad = xing_serving.compare(TINY, off, session, logits, 10)
    assert "score_err_quartile" in xing_serving.holds(xing_serving.summarise([bad] * 16, "bfloat16"))
    low = xing_serving.compare(TINY, entries, session, logits, 10, rounded=logits * (1 + 2 * xing_serving.STATED_TIGHT))
    assert "stated_err_quartile" in xing_serving.holds(xing_serving.summarise([low] * 16, "bfloat16"))
    # one request in four off: a routing step, which the quartile lets through
    assert xing_serving.holds(xing_serving.summarise([bad] * 4 + [out] * 12, "bfloat16")) == []
    # the worst position is read in the readings and not compared: routing steps reach as far as faults
    far = json.loads(json.dumps(entries))
    far[0]["next"] = [[i, s + 2 * float(np.abs(logits[0]).max())] for i, s in far[0]["next"]]
    worst = xing_serving.summarise([xing_serving.compare(TINY, far, session, logits, 10)] + [out] * 15, "bfloat16")
    assert xing_serving.holds(worst) == [] and not set(xing_serving.WORST) & set(worst)
    assert xing_serving.compare(TINY, far, session, logits, 10)[0]["score_err"] > 1.5
    # the limits lie between the chip's sound readings and its controls' (PERF.md has both)
    assert xing_serving.SCORE_TIGHT["float32"] < xing_serving.STATED_TIGHT < xing_serving.SCORE_TIGHT["bfloat16"]
    # the Sinkhorn's gauge: above what 20 iterations leave, below what 5 leave
    assert 0.07 < xing_serving.HC_ERROR_LIMIT < 0.3
    hc = xing_serving.invariants(TINY, {"oryx_seq_hc_sinkhorn_error": 0.3}, {}, [], 0)["hc_sinkhorn_error"]
    assert xing_serving.holds({"hc_sinkhorn_error": hc}) == ["hc_sinkhorn_error"]
    absent = xing_serving.invariants(TINY, {}, {}, [], 0)["hc_sinkhorn_error"]
    assert xing_serving.holds({"hc_sinkhorn_error": absent}) == ["hc_sinkhorn_error"]  # a number not read breaks it
    # the unconverged share: of the real tokens' matrices, one a sublayer (3 layers, 2 sublayers)
    assert 0.2 < xing_serving.HC_UNCONVERGED_LIMIT < 0.9
    tokens = {'oryx_seq_step_tokens_total{kind="prefill",tokens="real"}': 80.0,
              'oryx_seq_step_tokens_total{kind="decode",tokens="real"}': 20.0}
    for unconverged, broken in ((120.0, []), (540.0, ["hc_unconverged_share"])):
        got = xing_serving.invariants(TINY, dict(tokens, oryx_seq_hc_unconverged_total=unconverged), {}, [], 0)
        assert got["hc_unconverged_share"][0] == unconverged / 600
        assert xing_serving.holds({"s": got["hc_unconverged_share"]}) == (["s"] if broken else [])
    absent = xing_serving.invariants(TINY, tokens, {}, [], 0)["hc_unconverged_share"]
    assert xing_serving.holds({"hc_unconverged_share": absent}) == ["hc_unconverged_share"]


# -- the kind's whole run on the CPU, sound and with each control ----------------------------------

@pytest.mark.parametrize(
    "control,dtype,failing",
    [
        (None, "float32", set()),
        (None, "bfloat16", set()),
        ("maps_in_bfloat16", "float32", {"score_err_quartile", "hc_unconverged_share"}),
        ("maps_in_bfloat16", "bfloat16", {"hc_unconverged_share"}),
        ("streams_collapsed_to_one", "float32", {"score_err_quartile"}),
        ("streams_collapsed_to_one", "bfloat16", {"score_err_quartile", "stated_err_quartile"}),
        ("latent_cache_in_8_bits", "float32", {"score_err_quartile"}),
    ],
    ids=["sound", "sound_bfloat16", "maps_in_bfloat16", "maps_in_bfloat16_bfloat16", "streams_collapsed_to_one",
         "streams_collapsed_to_one_bfloat16", "latent_cache_in_8_bits"],
)
def test_a_fault_under_the_timed_path_reads_not_correct(control, dtype, failing, tmp_path, monkeypatch):
    """The kind's whole run in this process (run.py's look for a chip is
    skipped), the program broken underneath by each control: `correct` is
    false exactly when a compared number breaks its limit, and the tight
    limit on the scores, or for the maps the unconverged share, is among
    them. (At this hidden size the maps' logits
    spread by 0.3 and 5 Sinkhorn iterations already converge: that control is
    tests/test_xing.py's, at the published spread, and the chip's.)"""
    import jax

    if control:
        CONTROLS[control](monkeypatch.setattr)
    cell = {"name": "xing-tiny.next-long-tiny", "config": dict(TINY, dtype=dtype), "traffic": TINY_TRAFFIC,
            "chips": 1, "scratch": str(tmp_path)}
    try:
        out = xing_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # the next test traces the sound program again
    # on the CPU the batcher pads rows to powers of two: a burst may meet a
    # row count the warm-up never saw, so shapes and compiles are the chip's to hold
    host_side = {"compiles_in_window", "topk_shapes"}
    broken = set(xing_serving.holds(out["compared"])) - host_side
    assert broken >= failing and (failing or not broken), out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert out["compared"]["requests_compared"][:2] == [10, "=="]  # every request of a 1 s window
    assert out["compared"]["steps_per_basket"][0] == 4 and out["compared"]["dropped_events"][0] == 0
    assert out["compared"]["dropped_pairs"][0] == 0
    assert out["correct"] is (not xing_serving.holds(out["compared"]))
    assert ("stated_err_quartile" in out["compared"]) is (dtype == "bfloat16")
    # the gauge was read after the sample's dispatches: a number, and none with one stream
    gauge = out["compared"]["hc_sinkhorn_error"][0]
    assert gauge is not None and (gauge == 0.0) is (control == "streams_collapsed_to_one")
    unconverged = out["compared"]["hc_unconverged_share"][0]
    assert unconverged is not None and (unconverged == 0.0) is (control != "maps_in_bfloat16")
    counters = out["sources"]["counters"]
    assert counters['oryx_seq_steps_total{kind="decode"}'] > 0 and counters["oryx_moe_experts_touched_total"] > 0


def test_a_precision_below_the_stated_float32_fails_the_tolerance(tmp_path):
    """The program run in bfloat16 while the configuration states float32 (the
    reference then has no stated rounding to share): not `correct`, by the
    float32 limit on the scores."""
    import jax

    cell = {"name": "xing-tiny.next-long-tiny", "config": dict(TINY, dtype="bfloat16"), "traffic": TINY_TRAFFIC,
            "chips": 1, "scratch": str(tmp_path)}
    try:
        out = xing_serving.run(cell, 2**31 + 13, 1.0, False, time.time(), lambda **kv: None)
    finally:
        jax.clear_caches()
    readings = out["compared"]["score_err_quartile"][0]
    assert readings > 10 * xing_serving.SCORE_TIGHT["float32"]
    held_as_float32 = dict(out["compared"], score_err_quartile=[readings, "<=", xing_serving.SCORE_TIGHT["float32"]])
    assert "score_err_quartile" in xing_serving.holds(held_as_float32)


def test_cpu_rehearsal_prints_the_shared_layers_metrics(tmp_path):
    """run.py end to end on the test-only cell xing-tiny.next-long-tiny, found
    by name alone: the counters' and spans' metrics of the shared layers print
    (`listed.cpu_names` is the rule), and none of the kind's own (the cell is
    on no metric's list)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", "xing-tiny.next-long-tiny",
         "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] == 20
    listed.printed_on_the_cpu_holds(BENCH, "xing-tiny.next-long-tiny", last["metrics"])
    assert not [name for name in last["metrics"] if name.startswith("xing_")]
    assert last["compared"]["steps_per_basket"] == [4.0, "==", 4]
    assert last["compared"]["dropped_pairs"] == [0.0, "==", 0]
    assert 0.0 < last["compared"]["hc_sinkhorn_error"][0] <= xing_serving.HC_ERROR_LIMIT
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")
    notes = [json.loads(ln)["info"] for ln in lines[:-1]]
    states = [n["slot_state_bytes"] for n in notes if "slot_state_bytes" in n]
    # a slot holds what a JoyAI slot holds: a float32 latent of 32 and a rotated key of 8 a position a layer
    assert states == [{"latent": 3 * 33 * 28 * 32 * 4.0, "rope_key": 3 * 33 * 28 * 8 * 4.0}]


def test_the_kind_fails_at_once_on_a_tree_without_the_decoder(tmp_path, monkeypatch):
    """A tree without ops/xing.py (the parent of this kind's first cell): the
    kind raises before any set-up (run.py then exits 1 with no result line)."""
    import builtins

    real_import = builtins.__import__

    def no_xing(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "oryx_tpu.ops" and "xing" in (fromlist or ()):
            raise ImportError("cannot import name 'xing' from 'oryx_tpu.ops'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_xing)
    cell = {"name": "xing-tiny.next-long-tiny", "config": TINY, "traffic": TINY_TRAFFIC, "chips": 1,
            "scratch": str(tmp_path)}
    t0 = time.monotonic()
    with pytest.raises(ImportError):
        xing_serving.run(cell, 1, 1.0, False, time.time(), lambda **kv: None)
    assert time.monotonic() - t0 < 5.0
