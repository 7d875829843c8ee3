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
ssm-serving's, and so is `check_baskets` over this kind's `ref_hidden`; the
limits are this kind's), and the functions that
compute the operations and bytes of a dispatch, of its expert layer and of
its attention (`step_work`, `step_bytes`, `moe_work`, `attn_work`).
"""

from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from benchmarks.kinds import _encoder
from benchmarks.kinds._encoder import holds  # noqa: F401 - the kind's tests read it here
from benchmarks.kinds.seq_serving import draw_catalog
from benchmarks.kinds.ssm_serving import _as, _norm, basket_invariants, check_baskets, compare  # noqa: F401

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
    requests, under this kind's limits."""
    return _encoder.summarise(
        per_request, SCORE_TIGHT[dtype], STATED_TIGHT, SCORE_LOOSE, MIN_OVERLAP, MIN_OVERLAP_WORST
    )


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

    from oryx_tpu.apps.seq.state import adopt_model

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
    return (*_encoder.serve(cell, state), state, e_host)


def invariants(config: dict, final: dict, started: dict, sent: list, timed_out: int) -> dict:
    """This kind's own entries of `compared`: a basket's, and every real
    token of a prefill or a step through every expert layer's k experts."""
    whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
    tokens = sum(whole(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}') for kind in PROGRAMS)
    pairs = tokens * config["num_experts_per_tok"] * _sizes(config)["moe"]
    return dict(
        basket_invariants(config, final, started, sent, timed_out),
        dropped_pairs=[pairs - whole("oryx_moe_routed_total"), "==", 0],
    )


def compiled_texts(model) -> dict[str, list[str]]:
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


KIND = _encoder.Kind(
    name="joyai_serving", programs=PROGRAMS, scopes=SCOPES, build=build, check=partial(check_baskets, ref_hidden),
    summarise=summarise, invariants=invariants, compiled_texts=compiled_texts, position="basket",
    slot_states=("latent", "rope_key"),
)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    return _encoder.run(KIND, cell, seed, seconds, trace, t_process, info)
