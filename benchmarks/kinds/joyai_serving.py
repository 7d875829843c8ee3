"""Configuration kind `joyai-serving`: the session app's `/recommend-next`
through ServingLayer over HTTP with a latent-attention mixture-of-experts
decoder (`joyai`: MLA in every layer, a leading dense layer, then 256
sigmoid-routed experts beside a shared one) that generates a next basket token
by token; one process holding the chip, load from a generator process
(benchmarks/seqgen.py).

The model is synthetic, from --seed: the layers' tensors and the input
embedding made on the device (`ops/joyai.py init_tensors`: normal x 0.02, the
router's correction bias normal x 0.1), the UNTIED head drawn on the host at
bfloat16's values and served as the item catalog, adopted as an artifact's
tensors would be. The server is the program as it ships: default
reference.conf plus what a read-only server on mem:// brokers with port 0
needs.

Also here, because later PRs may not change them: the kind's own copy of the
plain float32 reference a layer at a time (`ref_layer`: the attention as
written, never absorbed; every expert in turn on every token, upcast one at
a time), the comparison that decides `correct` (`compare` is kind
ssm-serving's, `summarise` and its limits this kind's), and the functions that
compute the operations and bytes of a dispatch, of its expert layer and of
its attention (`step_work`, `step_bytes`, `moe_work`, `attn_work`).
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

import numpy as np

from benchmarks import latency, seqgen, seqtrace, timeline, xplane
from benchmarks.kinds.als_serving import _get, _sleep_until, queued_ahead_share, scrape
from benchmarks.kinds.seq_serving import draw_catalog, holds, ref_logits
from benchmarks.kinds.ssm_serving import _as, _norm, basket_tokens, compare

# What `correct` holds the served answers to, as kind ssm-serving does: for a
# sample of the window's own requests the reference runs ONE full forward pass
# over [session + the basket the system chose] and its hidden rows at the four
# positions, scored over the catalog, are held against what the timed path
# returned (`compare`: distances in units of the position's largest |logit|, a
# position's `score_err` the root mean square over its candidates).
#
# Two kinds of distance, as kind seq-serving found for its experts. ROUNDING
# reaches every position of every request alike. ROUTING: where a token's 8th
# and 9th biased scores lie closer than the rounding, the served path and the
# reference reach different experts: a step, not a rounding, in SOME positions,
# and no fault (the two experts' scores are then equal to within the rounding).
# So the tight limits are held by the QUARTILE over the sampled requests at the
# worst basket position, and a loose one by the worst reading of all.
#
# The float32 reference lies a rounding away from a bfloat16 program, and what
# a fault adds can hide inside that. So the reference is computed a second time
# WITH the configuration's stated rounding (every product's inputs, and the
# latent and the rotated key as the cache keeps them, at bfloat16's values;
# compiled without XLA's excess precision) and the served scores are held to
# THAT too: `stated_err`. What is left there on a sound program is the chip's
# order of accumulation and the absorbed form's own rounding points (the
# reference is never absorbed).
#
# The limits, each above every sound reading on the chip and below the reading
# of the control it is there to catch (my chip runs, PR 41; PERF.md has every
# reading). A routing step is no rarity here: the routed experts' weights sum
# to 2.5, so one expert swapped for its equal moves a position's scores by
# 0.03-0.14 of the largest logit, and a tenth of all positions carry one
# (1,024 positions of eight sound runs: `score_err` 2.4e-3 at the median, 2.8e-2
# at the 90th percentile, 7.7e-2 at the worst). The quartiles do not see them.
CHECK_REQUESTS = 32
REFERENCE_BATCH = 16   # sessions a reference dispatch
# against the float32 reference, the quartile: float32 leaves the order of
# accumulation alone (1e-7 on the CPU); bfloat16 sound 1.87e-3 to 2.34e-3 over twenty-nine seeds, the
# latent cache in 8 bits 8.8e-3, the bias weighing 9.7e-3, the scale left out
# 5.7e-2, the key not rotated 9.9e-2, the shared expert left out 0.143
SCORE_TIGHT = {"float32": 2.0e-5, "bfloat16": 4.5e-3}
# against the reference with the stated rounding, the same quartile: sound
# 1.34e-3 to 1.58e-3 over twenty-nine seeds (the absorbed step rounds the query's and the output's
# halves of W_kvb where the reference, never absorbed, rounds keys and values;
# and the chip's order of accumulation), the latent cache in 8 bits 9.4e-3
# (the nearest precision below the stated one), the bias weighing 8.9e-3
STATED_TIGHT = 3.5e-3
# the worst position of all, its scores, the item fed back and the last
# candidate: sound at most 0.094, 0.127 and 0.140 over twenty-nine seeds (routing steps); the key not
# rotated 0.41 and 0.33, the shared expert left out 0.47 and 0.43 on the last two
SCORE_LOOSE = 3.0e-1
MIN_OVERLAP = 6        # of 10 candidates the reference's, by the same quartile (sound 8-9; the scale left out 4-5, the key not rotated 3, the shared expert left out 1.75)
MIN_OVERLAP_WORST = 1  # and in the worst position of all (sound 2-6: a routing step reorders near-equal logits; the shared expert left out 0)
WARM_MIN_S = 5.0
WARM_CYCLES = 5
TRACE_MAX_S = 12.0
# an op counts under the first scope its op_name holds. Every instruction the
# program writes lies under one of these; what a traced window reads as
# `unscoped` are the compiler's own instructions, chiefly the asynchronous
# copies that bring a dispatch's dense weights from HBM into VMEM ahead of use
SCOPES = ("joyai.moe", "joyai.shared", "joyai.attn", "joyai.dense", "joyai.head", "joyai.embed")
PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


# -- the algorithm's operations and bytes ------------------------------------------

def _sizes(cfg: dict) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dense = cfg["first_k_dense_replace"]
    return {
        "h": h, "heads": heads, "qk": qk, "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"], "c": cfg["kv_lora_rank"],
        "f": cfg["moe_intermediate_size"], "dense_f": cfg["intermediate_size"], "e": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"], "v": cfg["vocab_size"],
        "dense": dense, "moe": cfg["num_hidden_layers"] - dense, "layers": cfg["num_hidden_layers"],
        # the five projections of one layer's attention, in parameters
        "proj": h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) + heads * cfg["v_head_dim"] * h,
    }


def attn_work(tokens: float, context: float, rows: float, absorbed: bool, cfg: dict, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE layer's attention needs for `tokens` real tokens of
    `rows` sequences, each attending over `context` positions on average. The
    projections cost the same in both forms (absorbed, W_kvb's two halves
    multiply the query and the summed latent where written they multiply each
    position's latent once). Written (a prefill): scores and values over keys
    of nope + rope and values of v_dim a head; the tokens' (c, k_rope) written
    to the cache. Absorbed (a step): scores over the latent and the rotated
    key, the latent summed, a head; each sequence's cache read once over its
    context. The projections' weights once, the stream read and written in
    float32."""
    s = _sizes(cfg)
    kept = (s["c"] + s["rope"]) * itemsize  # a position's row of the cache
    if absorbed:
        attend = 2.0 * s["heads"] * (2 * s["c"] + s["rope"]) * context
        cache = rows * context * kept + tokens * kept
    else:
        attend = 2.0 * s["heads"] * (s["qk"] + s["v_dim"]) * context
        cache = tokens * kept
    flops = tokens * (2.0 * s["proj"] + attend)
    return flops, s["proj"] * itemsize + cache + tokens * s["h"] * 8.0


def moe_work(tokens: float, touched: float, cfg: dict, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE expert layer needs for `tokens` real tokens that
    reach `touched` distinct routed experts: the router, experts_per_token
    routed experts' and the shared expert's three products a token; each
    touched expert's three matrices, the shared expert's, the router and its
    bias read once, the tokens' hidden states read and written in float32.
    Padding tokens, padded row tiles and untouched experts are the
    implementation's, not the algorithm's."""
    s = _sizes(cfg)
    expert = 3.0 * s["h"] * s["f"]
    flops = tokens * (2.0 * s["h"] * s["e"] + (s["k"] + s["shared"]) * 2.0 * expert)
    moved = (touched + s["shared"]) * expert * itemsize + s["h"] * s["e"] * itemsize + s["e"] * 4.0
    return flops, moved + tokens * s["h"] * 8.0


def step_work(tokens: float, context: float, head_tokens: float, absorbed: bool, cfg: dict) -> float:
    """FLOPs the MODEL needs for one dispatch of `tokens` real tokens that
    each attend over `context` positions on average, `head_tokens` of which
    also take logits over the catalog: every layer's attention, the leading
    dense layers' SwiGLU, the expert layers' router, routed and shared
    experts, and the head."""
    s = _sizes(cfg)
    attn = attn_work(1.0, context, 0.0, absorbed, cfg)[0]
    dense = 3 * 2.0 * s["h"] * s["dense_f"]
    moe = moe_work(1.0, 0.0, cfg)[0]
    return tokens * (s["layers"] * attn + s["dense"] * dense + s["moe"] * moe) + head_tokens * 2.0 * s["h"] * s["v"]


def step_bytes(tokens: float, rows: float, context: float, touched: float, head: bool, cfg: dict, itemsize: int = 2) -> float:
    """Bytes one dispatch has to move: every layer's attention (its weights,
    its cache traffic), the dense layers' weights, the expert layers' router
    and shared expert, the `touched` routed experts' matrices (summed over
    the expert layers: the experts TOUCHED, not all of them), the tokens'
    input embeddings and, for a step, the head's view of the catalog once."""
    s = _sizes(cfg)
    moved = s["layers"] * attn_work(tokens, context, rows, absorbed=head, cfg=cfg, itemsize=itemsize)[1]
    moved += s["dense"] * (3.0 * s["h"] * s["dense_f"] * itemsize + tokens * s["h"] * 8.0)
    moved += s["moe"] * moe_work(tokens, 0.0, cfg, itemsize)[1] + touched * 3.0 * s["h"] * s["f"] * itemsize
    moved += tokens * s["h"] * itemsize
    if head:
        moved += s["v"] * s["h"] * itemsize
    return moved


# -- the plain reference, a layer at a time: float32, `highest`, no cache -------------

def _turn_pairs(x, pos, theta):
    """x [..., T, d] or [..., T, heads, d] with pos [T]: the pairs (2i, 2i+1)
    turned by pos x theta^(-2i/d)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if x.ndim == 4:
        ang = ang[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(u, wg, wu, wd, act):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    ua = _as(u, act)
    return _as(jax.nn.silu(ua @ wg.astype(f32)) * (ua @ wu.astype(f32)), act) @ wd.astype(f32)


def _ref_experts(cfg: dict, p: dict, u, act):
    """u [N,H] float32 -> the routed experts' output: s = sigmoid(u W_r) on
    the float32 `u`, the k largest of s + b, weights scale x s / their sum;
    every expert in turn on every token (upcast one at a time), weighted by
    the token's weight for it."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = jax.nn.sigmoid(u @ p["router"].astype(f32))
    _, which = jax.lax.top_k(s + p["router_bias"].astype(f32), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, which, axis=-1)
    top = cfg["routed_scaling_factor"] * top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], which].add(top)

    def expert(acc, xs):
        wg, wu, wd, col = xs
        return acc + col[:, None] * _swiglu(u, wg, wu, wd, act), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u), (p["wg"], p["wu"], p["wd"], weight.T))
    return out


def ref_layer(cfg: dict, p: dict, x, act=None):
    """x [B,T,H] float32 -> the layer's output: latent attention as written
    (keys and values decompressed for every position, full causal softmax),
    then the layer's feed-forward (dense, or routed experts + the shared
    one). With `act` the inputs of every product, and the latent and rotated
    key (which the cache keeps at that dtype), are at that dtype's values; the
    stream, the norms, the softmax, the router and the rotation stay float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = _sizes(cfg)
    heads, nope, eps, theta = s["heads"], s["nope"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        b, t, h = x.shape
        pos = jnp.arange(t)
        u = _as(_norm(x, p["ln1"], eps), act)
        cq = _as(_norm(u @ p["wq_a"].astype(f32), p["q_norm"], eps), act)
        q = (cq @ p["wq_b"].astype(f32)).reshape(b, t, heads, s["qk"])
        q_nope, q_rope = _as(q[..., :nope], act), _as(_turn_pairs(q[..., nope:], pos, theta), act)
        ckv = u @ p["wkv_a"].astype(f32)
        c = _as(_norm(ckv[..., : s["c"]], p["kv_norm"], eps), act)
        k_rope = _as(_turn_pairs(ckv[..., s["c"]:], pos, theta), act)
        kv = (c @ p["wkv_b"].astype(f32)).reshape(b, t, heads, nope + s["v_dim"])
        k_nope, v = _as(kv[..., :nope], act), _as(kv[..., nope:], act)
        sc = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope) + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)
        sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], sc / math.sqrt(s["qk"]), -jnp.inf)
        prob = _as(jax.nn.softmax(sc, axis=-1), act)
        o = jnp.einsum("bhts,bshd->bthd", prob, v).reshape(b, t, heads * s["v_dim"])
        x = x + _as(o, act) @ p["wo"].astype(f32)
        u = _norm(x, p["ln2"], eps)
        if "router" not in p:
            return x + _swiglu(u, p["wg"], p["wu"], p["wd"], act)
        flat = u.reshape(b * t, h)
        y = _ref_experts(cfg, p, flat, act) + _swiglu(flat, p["shared_wg"], p["shared_wu"], p["shared_wd"], act)
        return x + y.reshape(b, t, h)


def ref_hidden(config: dict, params: dict, tokens: np.ndarray, act=None, compiled: dict | None = None):
    """tokens [B,T] int32 -> final-normed hidden [B,T,H] float32 by the plain
    form, ONE layer's program at a time over the model's own tensors (an
    expert's float32 copy lives only inside its turn): the model is never
    held twice. Compiled without XLA's excess precision, so a stated rounding
    is computed as stated. `compiled` keeps the two layer programs between
    calls of one shape and one `act`."""
    import jax
    import jax.numpy as jnp

    x = params["E_in"][jnp.asarray(tokens)].astype(jnp.float32)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    compiled = {} if compiled is None else compiled
    for p in params["layers"]:
        kind = "experts" if "router" in p else "dense"
        if kind not in compiled:
            compiled[kind] = jax.jit(partial(ref_layer, config, act=act)).lower(
                jax.tree.map(shape, p), shape(x)
            ).compile(compiler_options={"xla_allow_excess_precision": False})
        x = compiled[kind](p, x)
    return _norm(x, params["final_norm"], config["rms_norm_eps"])


# -- the comparison that decides `correct` --------------------------------------------

def summarise(per_request: list[list[dict]], dtype: str = "bfloat16") -> dict:
    """The compared numbers of `compare`'s readings over the sampled
    requests: by basket position the quartile over the requests (the worst
    position's is reported), and the worst reading of all."""
    flat = [o for req in per_request for o in req]
    basket = len(per_request[0]) if per_request else 0

    def by_position(key, q, pick):
        read = []
        for b in range(basket):
            values = [req[b][key] for req in per_request if req[b][key] is not None]
            if values:
                read.append(float(np.percentile(values, q)))
        return pick(read) if read else None

    def worst(key, pick):
        values = [o[key] for o in flat if o[key] is not None]
        return pick(values) if values else None

    out = {"malformed_answers": [sum(1 for o in flat if o["fault"]), "==", 0]}
    if worst("stated_err", max) is not None:  # a configuration that states a rounding
        out["stated_err_quartile"] = [by_position("stated_err", 25, max), "<=", STATED_TIGHT]
    out.update(
        score_err_quartile=[by_position("score_err", 25, max), "<=", SCORE_TIGHT[dtype]],
        score_err_worst=[worst("score_err", max), "<=", SCORE_LOOSE],
        fixed_gap_worst=[worst("fixed_gap", max), "<=", SCORE_LOOSE],
        candidate_gap_worst=[worst("candidate_gap", max), "<=", SCORE_LOOSE],
        overlap_quartile=[by_position("overlap", 25, min), ">=", MIN_OVERLAP],
        overlap_worst=[worst("overlap", min), ">=", MIN_OVERLAP_WORST],
    )
    return out


# -- the model from the seed ----------------------------------------------------------------

EXTENSION_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "intermediate_size", "n_routed_experts",
    "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "num_hidden_layers", "vocab_size", "rope_theta", "rms_norm_eps", "routed_scaling_factor",
    # the forms the source names, which the program checks it computes
    "scoring_func", "topk_method", "n_group", "topk_group", "norm_topk_prob", "rope_interleave",
    "rope_scaling", "attention_bias", "tie_word_embeddings", "hidden_act", "moe_layer_freq",
    "basket", "max_len", "dtype",
)


def extensions(config: dict) -> dict:
    """The artifact's extensions: the source's own keys, as strings."""
    return dict({k: str(config[k]) for k in EXTENSION_KEYS if k in config}, encoder="joyai")


def build(cell: dict, seed: int, info):
    """The model from the seed and the server around it, started:
    (serving, manager, state, e_host). The caller closes `serving`."""
    # a tree without the decoder fails here, at once, before any set-up
    from oryx_tpu.ops import joyai

    import jax

    from oryx_tpu.apps.seq.serving import SeqServingModel, SeqServingModelManager
    from oryx_tpu.apps.seq.state import adopt_model
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    config = cell["config"]
    n_items = config["vocab_size"]  # every id is an item
    t_build = time.monotonic()
    ext = extensions(config)
    enc = joyai.JoyaiEncoder.from_extensions(ext.get)
    tensors = joyai.init_tensors(enc.cfg, seed, enc.dtype)
    # the untied head: the catalog's rows are its own draw
    e_host = draw_catalog(seed, n_items, config["hidden_size"])
    tensors["E"] = e_host
    state = adopt_model(None, ext.get, tensors, [f"i{j}" for j in range(n_items)])
    jax.block_until_ready(state.params)
    info(phase="model_built", seconds=time.monotonic() - t_build,
         parameters=joyai.param_count(enc.cfg) + n_items * config["hidden_size"])

    broker = "mem://bench"
    overlay = {
        "oryx.id": "bench",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.seq",
        ],
        "oryx.monitoring.flight.dir": str(Path(cell["scratch"]) / "flight"),
    }
    if jax.devices()[0].platform == "tpu":
        overlay["oryx.compute.platform"] = "tpu"
    cfg = load_config(overlay=overlay)
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = SeqServingModelManager(cfg)
    manager.model = SeqServingModel(state, sync=manager.sync)
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    return serving, manager, state, e_host


def reference_logits(config: dict, params: dict, e_dev, asked: list[np.ndarray], act) -> list[np.ndarray]:
    """The reference's logits [basket, items] at the last `basket` positions
    of every token array in `asked`, REFERENCE_BATCH forward passes a
    dispatch (right-padded: the pass is causal, so padding changes nothing
    before it)."""
    basket, width = config["basket"], config["max_len"] + config["basket"]
    out, compiled = [], {}
    for lo in range(0, len(asked), REFERENCE_BATCH):
        group = asked[lo:lo + REFERENCE_BATCH]
        padded = np.zeros((REFERENCE_BATCH, width), dtype=np.int32)
        for j, tokens in enumerate(group):
            padded[j, : len(tokens)] = tokens
        z = ref_hidden(config, params, padded, act, compiled)
        rows = np.stack([np.arange(len(t) - basket, len(t)) for t in group])
        zb = z[np.arange(len(group))[:, None], rows]                              # [G, basket, H]
        logits = ref_logits(zb.reshape(len(group) * basket, -1), e_dev)
        out += [logits[j * basket:(j + 1) * basket] for j in range(len(group))]
    return out


def check(base: str, config: dict, traffic: dict, state, e_host, sessions: list, sample: list[int], info):
    """The sampled sessions asked again, together, and each answer against
    the reference's one full pass over it: (readings of `compare`, faults)."""
    import jax.numpy as jnp

    with ThreadPoolExecutor(len(sample)) as pool:
        answers = list(pool.map(
            lambda i: _get(f"{base}{seqgen.session_path(traffic, sessions[i])}"), sample
        ))
    faults, served = [], []
    for i, (status, body) in zip(sample, answers):
        if status != 200:
            faults.append(f"request {i}: status {status}")
        else:
            served.append((json.loads(body), sessions[i]))
    t_ref = time.monotonic()
    asked = [basket_tokens(config, answer, session) for answer, session in served]
    sound = [j for j, t in enumerate(asked) if t is not None]
    e_dev = jnp.asarray(e_host, dtype=jnp.bfloat16)  # bf16 holds the catalog's values exactly
    tokens = [asked[j] for j in sound]
    exact = dict(zip(sound, reference_logits(config, state.params, e_dev, tokens, None)))
    # the configuration's stated rounding, where it states one below float32
    act = None if config["dtype"] == "float32" else jnp.dtype(config["dtype"])
    rounded = (
        dict(zip(sound, reference_logits(config, state.params, e_dev, tokens, act))) if act is not None else {}
    )
    readings = [
        compare(config, answer, session, exact.get(j), int(traffic["how_many"]), rounded.get(j))
        for j, (answer, session) in enumerate(served)
    ]
    keys = ("score_err", "rounding", "stated_err", "fixed_gap", "overlap", "candidate_gap")
    info(phase="reference", seconds=time.monotonic() - t_ref, forwards=len(tokens), readings=[
        [[None if o[k] is None else round(o[k], 6) for k in keys] for o in req] for req in readings
    ])
    for req in readings:
        faults += [f"a basket position: {o['fault']}" for o in req if o["fault"]]
    return readings, faults


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {config, traffic, chips, scratch}."""
    import jax

    from oryx_tpu.common.perfstats import get_perfstats

    config, traffic = cell["config"], cell["traffic"]
    n_items = config["vocab_size"]
    # the generator's names for the basket and its steps (benchmarks/seqgen.py)
    for key in ("block_length", "denoise_steps"):
        if traffic[key] != config["basket"]:
            raise ValueError(f"traffic's {key} and the configuration's basket disagree")
    serving, manager, state, e_host = build(cell, seed, info)

    # the cyclic collector stops every thread of the server while it runs:
    # time each collection (gc_pause_share)
    collections: list[tuple[float, float]] = []  # (monotonic start, seconds)

    def on_gc(phase: str, _info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            collections.append((now, 0.0))
        else:
            collections[-1] = (collections[-1][0], now - collections[-1][0])

    gc.callbacks.append(on_gc)
    base = f"http://127.0.0.1:{serving.port}"
    gen = None
    try:
        started = scrape(base)  # before this run's first request
        # -- warm-up, part 1: one request uploads the view and compiles (or
        # loads) every shape of the decoder and the scan's; a second, alone,
        # times one request
        t_prime = time.monotonic()
        probe = seqgen.draw_sessions(seed + 1, n_items, traffic, 2)
        for attempt, session in zip(("first", "cycle"), probe):
            t_req = time.monotonic()
            status, body = _get(f"{base}{seqgen.session_path(traffic, session)}")
            if status != 200:
                raise RuntimeError(f"priming request -> {status}: {body[:200]!r}")
            cycle_s = time.monotonic() - t_req
            info(phase=f"prime_{attempt}", seconds=cycle_s)
        warm_s = float(math.ceil(max(WARM_MIN_S, WARM_CYCLES * cycle_s)))
        spec = {
            "port": serving.port, "seed": seed, "traffic": traffic, "items": n_items,
            "seconds": seconds, "warm_s": warm_s,
        }
        gen = subprocess.Popen(
            [sys.executable, seqgen.__file__, json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "JAX_PLATFORMS")},
        )
        if gen.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not start")

        # -- warm-up, part 2: the cell's own traffic, then the window
        t0 = time.monotonic() + 0.25
        gen.stdin.write(json.dumps({"t0": t0}) + "\n")
        gen.stdin.flush()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        _sleep_until(t_open)
        setup_s = time.time() - t_process
        before = scrape(base)
        trace_out = timeline_out = found = None
        if trace:
            trace_dir = Path(cell["scratch"]) / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            _sleep_until(t_open + 0.25)
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            _sleep_until(min(time.monotonic() + TRACE_MAX_S, t_close - 0.5))
            jax.profiler.stop_trace()
            found = xplane.find_xplane(trace_dir)
            if found:
                trace_out = xplane.reduce_trace(found, prefer=timeline.REGION_PREFIX)
                timeline_out = timeline.parse(found)
        _sleep_until(t_close)
        after = scrape(base)
        ring = get_perfstats().records_since(t_open - 1.0)
        records = [r for r in ring if t_open <= r.t_start < t_close]
        pauses = [s for t, s in collections if t_open <= t < t_close]
        out, _ = gen.communicate(timeout=seconds + 240)
        result = json.loads(out.strip().splitlines()[-1])
        gen = None

        # -- correctness, outside the timing: sampled requests of the window,
        # asked again together, against the reference's pass over each
        good, attempted, failed = latency.window_latencies(result)
        n_requests = len(result["due"])
        sessions = seqgen.draw_sessions(seed, n_items, traffic, n_requests)
        in_window = np.flatnonzero(np.asarray(result["in_window"], dtype=bool))
        rng = np.random.default_rng([int(seed), 3])
        sample = rng.choice(in_window, size=min(CHECK_REQUESTS, len(in_window)), replace=False)
        readings, faults = check(base, config, traffic, state, e_host, sessions, sample.tolist(), info)
        final = scrape(base)
        wrong_bodies = sum(
            n for kind, n in result["errors"].items()
            if kind in ("unparsable", "wrong_block", "wrong_count", "known_item")
        )
        delta = {s: after[s] - before.get(s, 0.0) for s in after}
        compiles = sum(v for s, v in delta.items() if s.startswith("oryx_xla_compiles_total"))
        # every event of every session sent (the probes, the generator's, the
        # sample asked again) but its last has to have run through a prefill,
        # and every token of a prefill or a step through every expert layer
        sent = list(probe) + sessions + [sessions[i] for i in sample.tolist()]
        whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
        answered = whole("oryx_seq_blocks_total")
        prefilled = whole('oryx_seq_step_tokens_total{kind="prefill",tokens="real"}')
        stepped = whole('oryx_seq_step_tokens_total{kind="decode",tokens="real"}')
        pairs = (prefilled + stepped) * config["num_experts_per_tok"] * _sizes(config)["moe"]
        timed_out = sum(n for kind, n in result["errors"].items() if kind == "timeout")
        compared = dict(
            {"requests_compared": [len(readings), "==", len(sample)]},
            **summarise(readings, config["dtype"]),
            wrong_bodies_in_window=[wrong_bodies, "==", 0],
            compiles_in_window=[compiles, "==", 0],
            # over the whole run, read when nothing is in flight
            steps_per_basket=[whole("oryx_seq_denoise_steps_total") / answered if answered else None,
                              "==", config["basket"]],
            dropped_events=[sum(len(s) - 1 for s in sent) - prefilled if not timed_out else None, "==", 0],
            dropped_pairs=[pairs - whole("oryx_moe_routed_total"), "==", 0],
            host_fallbacks=[delta.get("oryx_topk_host_fallbacks", 0.0), "==", 0],
            topk_shapes=[len({(r.padded_rows, r.k_bucket) for r in records}), "==", 1],
            dispatches_not_exact=[sum(1 for r in records if r.score_mode != "exact"), "==", 0],
            good_in_window=[len(good), ">=", 1],
        )
        faults += [f"{name} = {compared[name][0]} breaks its limit" for name in holds(compared)]
        for f in faults:
            print(f"joyai_serving: {f}", file=sys.stderr)

        steps_out = None
        if found:
            steps_out = seqtrace.split(seqtrace.parse(found), _compiled_texts(manager.model), SCOPES)
        late = [ms for ms, w in zip(result["late_ms"], result["in_window"]) if w and ms is not None]
        if steps_out:
            info(phase="steps", steps=steps_out)
        info(
            generator_processes=1, connections_opened=result["connections_opened"],
            errors=result["errors"], warm_s=warm_s,
            in_flight_at_window_end=latency.in_flight_at(result, warm_s + seconds),
            prime_s=t_open - t_prime,
            gen_late_p95_ms=latency.percentile(late, 95) if late else None,
            latency_p95_ms=latency.percentile(good, 95) if good else None,
            collector_pauses_s=[round(s, 4) for s in pauses if s > 0.05],
            dispatches=len(records),
            rows_per_dispatch=sum(r.rows for r in records) / len(records) if records else None,
            shapes=sorted({(r.padded_rows, r.k_bucket) for r in records}),
            queued_ahead_share=queued_ahead_share(records),
            encoder_steps=sum(delta.get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0) for kind in PROGRAMS),
            prefill_tokens_per_step=_ratio(delta, "prefill"), decode_tokens_per_step=_ratio(delta, "decode"),
            slot_state_bytes={
                kind: final.get(f'oryx_seq_slot_state_bytes{{state="{kind}"}}') for kind in ("latent", "rope_key")
            },
        )
    finally:
        gc.callbacks.remove(on_gc)
        if gen is not None:
            gen.kill()
            gen.wait()
        serving.close()

    return {
        "correct": not faults and bool(good),
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "end_to_end": {"p50_ms": latency.percentile(good, 50) if good else None},
        "sources": {
            "config": config,
            "traffic": traffic,
            "counters": delta,
            "dispatch_records": [
                {"rows": r.rows, "padded_rows": r.padded_rows, "k_bucket": r.k_bucket}
                for r in records
            ],
            "generator": {"late_ms": late, "latency_ms": good},
            "collector": {"window_s": seconds, "pauses_s": pauses},
            "trace": trace_out,
            "timeline": timeline_out,
            # the traced window's device time by decoder program and scope
            "steps": steps_out,
        },
        "compared": compared,
    }


def _ratio(delta: dict, kind: str) -> float | None:
    n = delta.get(f'oryx_seq_steps_total{{kind="{kind}"}}', 0.0)
    real = delta.get(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}', 0.0)
    return real / n if n else None


def _compiled_texts(model) -> dict[str, list[str]]:
    """The compiled text of every decoder program the engine runs, by the
    program's name on the device trace: lowered again from the live arrays'
    shapes (a persistent compile cache makes it a load)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import joyai

    engine = model._engine()
    enc = engine.encoder
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params, state = jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.state)
    view, _n_valid, row_token = engine.head()
    rows = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    texts = {PROGRAMS["prefill"]: [], PROGRAMS["decode"]: []}
    for bucket in enc.length_buckets:
        p = rows(enc.prefill_rows, jnp.int32)
        lowered = joyai.prefill.lower(
            enc.cfg, params, state, jax.ShapeDtypeStruct((enc.prefill_rows, bucket), jnp.int32), p, p, p
        )
        texts[PROGRAMS["prefill"]].append(lowered.compile().as_text())
    d = enc.step_rows
    lowered = joyai.decode_step.lower(
        enc.cfg, params, state, shape(view), jax.ShapeDtypeStruct((), jnp.int32), shape(row_token),
        rows(d, jnp.int32), rows(d, jnp.int32), rows(d, jnp.bool_), rows(d, jnp.int32),
    )
    texts[PROGRAMS["decode"]].append(lowered.compile().as_text())
    return texts
