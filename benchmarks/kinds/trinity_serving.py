"""Configuration kind `trinity-serving`: the session app's `/recommend-next`
through ServingLayer over HTTP with a gated-attention mixture-of-experts
decoder (`trinity`: grouped-query attention with a gate on its output, sliding
and position-free full layers mixed, four norms a layer, a leading dense layer,
then sigmoid-routed experts of which this chip HOLDS A SHARE, beside a shared
one) that generates a next basket token by token; one process holding the
chip, load from a generator process (benchmarks/seqgen.py).

The model is synthetic, from --seed: the layers' tensors and the input
embedding made on the device (`ops/trinity.py init_tensors`: normal x 0.02,
norm gains 1, the router's selecting bias normal x 0.1), the UNTIED head drawn
on the host at bfloat16's values and served as the item catalog, adopted as an
artifact's tensors would be. The server is the program as it ships: default
reference.conf plus what a read-only server on mem:// brokers with port 0
needs.

Also here, because later PRs may not change them: the kind's own copy of the
plain float32 reference a layer at a time (`ref_layer`: one full causal pass,
the window's mask, rotation on the sliding layers alone; every HELD expert in
turn on every token, upcast one at a time; what the experts held elsewhere
would add is left out, as in the program), the limits of the comparison that
decides `correct` (`compare` and `check_baskets` are kind ssm-serving's, over
this kind's `ref_hidden`), and the functions that compute the operations and
bytes of a dispatch, of its expert layer and of its attention (`step_work`,
`step_bytes`, `moe_work`, `attn_work`).
"""

from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from benchmarks.kinds import _encoder
from benchmarks.kinds._encoder import holds  # noqa: F401 - the kind's tests read it here
from benchmarks.kinds.joyai_serving import _swiglu
from benchmarks.kinds.seq_serving import draw_catalog
from benchmarks.kinds.ssm_serving import _as, _norm, basket_invariants, check_baskets, compare  # noqa: F401

# What `correct` holds the served answers to, as kinds ssm-serving and
# joyai-serving do: for a sample of the window's own requests the reference runs
# ONE full forward pass over [session + the basket the system chose] and its
# hidden rows at the four positions, scored over the catalog, are held against
# what the timed path returned (`compare`: distances in units of the position's
# largest |logit|, a position's `score_err` the root mean square over its
# candidates), against the float32 reference (`score_err`) and against the same
# pass WITH the configuration's stated rounding (`stated_err`: every product's
# inputs, and the keys and values as the cache keeps them, at bfloat16's
# values; compiled without XLA's excess precision).
#
# Two kinds of distance, as every expert kind found. ROUNDING reaches every
# position of every request alike. ROUTING: where a token's 4th and 5th biased
# scores lie closer than the rounding, two computations reach different experts:
# a step, not a rounding, in SOME positions, and no fault. Here a step is rarer
# than in joyai-flash-5l and LARGER: two or three positions in a hundred carry
# one (3,840 positions of thirty sound runs: `score_err` 1.6e-3 at the median,
# 4.5e-3 at the 90th percentile, 4.5e-2 at the 99th, 0.112 at the worst), because an expert that crosses the held share's edge brings or takes a
# whole expert's output at a weight of 0.6 beside the shared expert's 1, and the
# norm after the feed-forward spreads that over the branch. In three of four (62
# of the 82 positions over 0.03) it is the FLOAT32 REFERENCE that steps away
# from the two bfloat16 computations (`stated_err` stays 1e-3 where `score_err`
# reads 0.09), so the readings against the float32 reference have a long tail on
# a sound program. The tight limits are therefore held by the QUARTILE over the
# sampled requests at the worst basket position, and a loose one by the worst
# reading of all.
#
# The limits, each above every sound reading on the chip and below the reading
# of the control it is there to catch (my chip runs, PR 44: thirty sound runs,
# each its own seed, and one run of each control at seed 4400003001;
# PERF.md has every reading; the controls are tests/benchmarks/trinity_controls.py).
# against the float32 reference, the quartile: float32 leaves the order of
# accumulation alone (1.5e-7 on the CPU; bfloat16 where float32 is stated reads
# 1.6e-3 there); bfloat16 sound 1.20e-3 to 1.50e-3; the cache in 8 bits 4.45e-3,
# the scale left out 3.15e-2, another chip's share 9.6e-2, the gate left out
# 0.168, the keys not rotated 0.207, the shared expert left out 0.236
SCORE_TIGHT = {"float32": 2.0e-5, "bfloat16": 2.5e-3}
# against the reference with the stated rounding, the same quartile: sound
# 6.17e-4 to 7.54e-4 (the chip's order of accumulation, and values a rounding
# apart that round apart, layer after layer); the cache in 8 bits 4.47e-3 (the
# nearest precision below the stated one: not `correct` by both quartiles)
STATED_TIGHT = 1.8e-3
# the worst position of all, its scores, the item fed back and the last
# candidate: sound at most 0.112, 0.237 and 0.188 (routing steps; the next
# largest 0.106, 0.163, 0.159), with the more room above since fresh seeds read
# higher; the keys not rotated 0.35, 0.66, 0.66, the shared expert left out
# 0.46, 0.73, 0.73
SCORE_LOOSE = 5.0e-1
MIN_OVERLAP = 7        # of 10 candidates the reference's, by the same quartile (sound 9-10; the scale left out 5.75, another chip's share 2.75, the other three under 1; the cache in 8 bits 9: the scores catch it)
MIN_OVERLAP_WORST = 1  # and in the worst position of all (sound 3-8: a routing step reorders the candidates; four of the controls 0)
# an op counts under the first scope its op_name holds. Every instruction the
# program writes lies under one of these; what a traced window reads as
# `unscoped` are the compiler's own instructions, chiefly the asynchronous
# copies that bring a dispatch's dense weights from HBM into VMEM ahead of use
SCOPES = ("trinity.moe", "trinity.shared", "trinity.attn", "trinity.dense", "trinity.head", "trinity.embed")
PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


# -- the algorithm's operations and bytes ------------------------------------------

def _sizes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    dense = cfg["num_dense_layers"]
    types = cfg["layer_types"]
    return {
        "h": h, "q": q, "kv": kv, "f": cfg["moe_intermediate_size"], "dense_f": cfg["intermediate_size"],
        "e": cfg.get("num_experts_routed", cfg["num_experts"]), "held": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "shared": cfg["num_shared_experts"], "v": cfg["vocab_size"],
        "dense": dense, "moe": cfg["num_hidden_layers"] - dense, "layers": cfg["num_hidden_layers"],
        "sliding": sum(1 for t in types if t == "sliding_attention"), "window": cfg["sliding_window"],
        # the five projections of one layer's attention (q, k, v, the output gate, o), in parameters
        "proj": 2 * h * q + 2 * h * kv + q * h,
    }


def attn_work(tokens: float, context: float, rows: float, step: bool, cfg: dict, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE layer's attention needs for `tokens` real tokens of
    `rows` sequences, each attending over `context` positions on average (a
    sliding layer's context is the window at most; the caller clips it): the
    five projections, scores and values a query head over the context; the
    projections' weights once, the tokens' keys and values written to the
    cache, a step's sequences' cache read once over the context, the stream
    read and written in float32."""
    s = _sizes(cfg)
    flops = tokens * (2.0 * s["proj"] + 2.0 * 2.0 * s["q"] * context)
    kept = 2.0 * s["kv"] * itemsize  # a position's row of the cache: its key and its value
    cache = tokens * kept + (rows * context * kept if step else 0.0)
    return flops, s["proj"] * itemsize + cache + tokens * s["h"] * 8.0


def moe_work(tokens: float, pairs: float, touched: float, cfg: dict, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE expert layer needs HERE for `tokens` real tokens of
    whose (token, expert) pairs `pairs` reach an expert this chip holds,
    `touched` distinct ones: the router over every expert of the model and the
    shared expert's three products a token, a routed expert's three products a
    pair computed here; each touched HELD expert's three matrices, the shared
    expert's, the router and its bias read once, the tokens' hidden states
    read and written in float32. Pairs sent elsewhere, padding tokens, padded
    row tiles and untouched experts are not this chip's algorithm's."""
    s = _sizes(cfg)
    expert = 3.0 * s["h"] * s["f"]
    flops = tokens * (2.0 * s["h"] * s["e"] + s["shared"] * 2.0 * expert) + pairs * 2.0 * expert
    moved = (touched + s["shared"]) * expert * itemsize + s["h"] * s["e"] * itemsize + s["e"] * 4.0
    return flops, moved + tokens * s["h"] * 8.0


def _contexts(context: float, s: dict) -> list[tuple[int, float]]:
    """[(layers, positions a token attends over)] by kind of layer."""
    return [(s["sliding"], min(context, s["window"])), (s["layers"] - s["sliding"], context)]


def step_work(tokens: float, context: float, head_tokens: float, pairs: float, cfg: dict) -> float:
    """FLOPs the MODEL needs here for one dispatch of `tokens` real tokens that
    each attend over `context` positions on average, `head_tokens` of which
    also take logits over the catalog, `pairs` of their (token, expert) pairs
    (summed over the expert layers) computed by experts held here: every
    layer's attention, the leading dense layers' SwiGLU, the expert layers'
    router, shared expert and held routed experts, and the head."""
    s = _sizes(cfg)
    attn = sum(n * attn_work(1.0, c, 0.0, False, cfg)[0] for n, c in _contexts(context, s))
    dense = 3 * 2.0 * s["h"] * s["dense_f"]
    moe = moe_work(1.0, 0.0, 0.0, cfg)[0]
    routed = pairs * 2.0 * 3.0 * s["h"] * s["f"]
    return tokens * (attn + s["dense"] * dense + s["moe"] * moe) + routed + head_tokens * 2.0 * s["h"] * s["v"]


def step_bytes(tokens: float, rows: float, context: float, touched: float, head: bool, cfg: dict, itemsize: int = 2) -> float:
    """Bytes one dispatch has to move: every layer's attention (its weights,
    its cache traffic), the dense layers' weights, the expert layers' router
    and shared expert, the `touched` HELD experts' matrices (summed over the
    expert layers: those TOUCHED, not all that are held), the tokens' input
    embeddings and, for a step, the head's rows of the catalog once."""
    s = _sizes(cfg)
    moved = sum(n * attn_work(tokens, c, rows, head, cfg, itemsize)[1] for n, c in _contexts(context, s))
    moved += s["dense"] * (3.0 * s["h"] * s["dense_f"] * itemsize + tokens * s["h"] * 8.0)
    moved += s["moe"] * moe_work(tokens, 0.0, 0.0, cfg, itemsize)[1] + touched * 3.0 * s["h"] * s["f"] * itemsize
    moved += tokens * s["h"] * itemsize
    if head:
        moved += s["v"] * s["h"] * itemsize
    return moved


# -- the plain reference, a layer at a time: float32, `highest`, no cache -------------

def _turn_halves(x, pos, theta):
    """x [B,T,heads,d] with pos [T]: dimension i pairs with i + d/2, the pair
    turned by pos x theta^(-2i/d) (the rotate-half form)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _ref_experts(cfg: dict, p: dict, u, act):
    """u [N,H] float32 -> the HELD experts' part of the routed output: s =
    sigmoid(u W_r) on the float32 `u` over every expert of the model, the k
    largest of s + b, weights route_scale x s / (their sum + 1e-20); every
    held expert in turn on every token (upcast one at a time), weighted by the
    token's weight for it. What the other chips' experts would add is left
    out, as in the program."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = jax.nn.sigmoid(u @ p["router"].astype(f32))
    _, which = jax.lax.top_k(s + p["router_bias"].astype(f32), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, which, axis=-1)
    top = cfg["route_scale"] * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    weight = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], which].add(top)
    first = cfg.get("first_expert", 0)
    weight = weight[:, first:first + cfg["num_experts"]]

    def expert(acc, xs):
        wg, wu, wd, col = xs
        return acc + col[:, None] * _swiglu(u, wg, wu, wd, act), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u), (p["wg"], p["wu"], p["wd"], weight.T))
    return out


def ref_layer(cfg: dict, p: dict, x, sliding: bool = True, act=None):
    """x [B,T,H] float32 -> the layer's output: gated grouped-query attention
    over the whole sequence (causal, and on a sliding layer inside the window
    and with q and k turned by their position), then the layer's feed-forward
    (dense, or held routed experts + the shared one), each between its two
    norms. With `act` the inputs of every product, and keys and values (which
    the cache keeps at that dtype), are at that dtype's values; the stream, the
    norms, the softmax, the gate's sigmoid, the router and the rotation stay
    float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        b, t, h = x.shape
        pos = jnp.arange(t)
        a = _as(_norm(x, p["ln1"], eps), act)
        q = _norm((a @ p["wq"].astype(f32)).reshape(b, t, heads, d), p["q_norm"], eps)
        k = _norm((a @ p["wk"].astype(f32)).reshape(b, t, kv_heads, d), p["k_norm"], eps)
        v = (a @ p["wv"].astype(f32)).reshape(b, t, kv_heads, d)
        allowed = pos[None, :] <= pos[:, None]
        if sliding:
            q, k = _turn_halves(q, pos, theta), _turn_halves(k, pos, theta)
            allowed = allowed & (pos[:, None] - pos[None, :] < cfg["sliding_window"])
        q, k, v = _as(q, act), _as(k, act), _as(v, act)
        k, v = jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)
        sc = jnp.where(allowed[None, None], jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d), -jnp.inf)
        prob = _as(jax.nn.softmax(sc, axis=-1), act)
        o = jnp.einsum("bhts,bshd->bthd", prob, v).reshape(b, t, heads * d)
        gated = o * jax.nn.sigmoid(a @ p["wgate"].astype(f32))
        x = x + _norm(_as(gated, act) @ p["wo"].astype(f32), p["ln1_post"], eps)
        m = _norm(x, p["ln2"], eps)
        if "router" not in p:
            return x + _norm(_swiglu(m, p["wg"], p["wu"], p["wd"], act), p["ln2_post"], eps)
        flat = m.reshape(b * t, h)
        f = _ref_experts(cfg, p, flat, act) + _swiglu(flat, p["shared_wg"], p["shared_wu"], p["shared_wd"], act)
        return x + _norm(f.reshape(b, t, h), p["ln2_post"], eps)


def ref_hidden(config: dict, params: dict, tokens: np.ndarray, act=None, compiled: dict | None = None):
    """tokens [B,T] int32 -> final-normed hidden [B,T,H] float32 by the plain
    form, ONE layer's program at a time over the model's own tensors (an
    expert's float32 copy lives only inside its turn): the model is never
    held twice. Compiled without XLA's excess precision, so a stated rounding
    is computed as stated. `compiled` keeps the layer programs (dense or
    experts, sliding or full) between calls of one shape and one `act`."""
    import jax
    import jax.numpy as jnp

    x = params["E_in"][jnp.asarray(tokens)].astype(jnp.float32) * math.sqrt(config["hidden_size"])
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    compiled = {} if compiled is None else compiled
    for p, layer_type in zip(params["layers"], config["layer_types"]):
        kind = ("experts" if "router" in p else "dense", layer_type)
        if kind not in compiled:
            layer = partial(ref_layer, config, sliding=layer_type == "sliding_attention", act=act)
            compiled[kind] = jax.jit(layer).lower(jax.tree.map(shape, p), shape(x)).compile(
                compiler_options={"xla_allow_excess_precision": False}
            )
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
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
    "moe_intermediate_size", "num_experts", "num_experts_routed", "first_expert", "num_experts_per_tok",
    "num_shared_experts", "num_dense_layers", "num_hidden_layers", "layer_types", "global_attn_every_n_layers",
    "sliding_window", "vocab_size", "rope_theta", "rms_norm_eps", "route_scale",
    # the forms the source names, which the program checks it computes
    "score_func", "route_norm", "n_group", "topk_group", "num_expert_groups", "num_limited_groups",
    "rope_scaling", "tie_word_embeddings", "hidden_act", "mup_enabled",
    "basket", "max_len", "dtype",
)


def extensions(config: dict) -> dict:
    """The artifact's extensions: the source's own keys, as strings."""
    return dict({k: str(config[k]) for k in EXTENSION_KEYS if k in config}, encoder="trinity")


def build(cell: dict, seed: int, info):
    """The model from the seed and the server around it, started:
    (serving, manager, state, e_host). The caller closes `serving`."""
    # a tree without the decoder fails here, at once, before any set-up
    from oryx_tpu.ops import trinity

    import jax

    from oryx_tpu.apps.seq.state import adopt_model

    config = cell["config"]
    n_items = config["vocab_size"]  # every id is an item
    t_build = time.monotonic()
    ext = extensions(config)
    enc = trinity.TrinityEncoder.from_extensions(ext.get)
    tensors = trinity.init_tensors(enc.cfg, seed, enc.dtype)
    # the untied head: the catalog's rows are its own draw
    e_host = draw_catalog(seed, n_items, config["hidden_size"])
    tensors["E"] = e_host
    state = adopt_model(None, ext.get, tensors, [f"i{j}" for j in range(n_items)])
    jax.block_until_ready(state.params)
    info(phase="model_built", seconds=time.monotonic() - t_build,
         parameters=trinity.param_count(enc.cfg) + n_items * config["hidden_size"])
    return (*_encoder.serve(cell, state), state, e_host)


def invariants(config: dict, final: dict, started: dict, sent: list, timed_out: int) -> dict:
    """This kind's own entries of `compared`: a basket's, and every real
    token of a prefill or a step through every expert layer's k experts, each
    pair computed by an expert held here or counted as sent elsewhere: a pair
    sent elsewhere is not a dropped pair."""
    whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
    tokens = sum(whole(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}') for kind in PROGRAMS)
    pairs = tokens * config["num_experts_per_tok"] * _sizes(config)["moe"]
    accounted = whole("oryx_moe_routed_total") + whole("oryx_moe_routed_elsewhere_total")
    return dict(
        basket_invariants(config, final, started, sent, timed_out),
        dropped_pairs=[pairs - accounted, "==", 0],
    )


def compiled_texts(model) -> dict[str, list[str]]:
    """The compiled text of every decoder program the engine runs, by the
    program's name on the device trace: lowered again from the live arrays'
    shapes (a persistent compile cache makes it a load)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity

    engine = model._engine()
    enc = engine.encoder
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params, state = jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.state)
    view, _n_valid, row_token = engine.head()
    rows = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    texts = {PROGRAMS["prefill"]: [], PROGRAMS["decode"]: []}
    for bucket in enc.length_buckets:
        p = rows(enc.prefill_rows, jnp.int32)
        lowered = trinity.prefill.lower(
            enc.cfg, params, state, jax.ShapeDtypeStruct((enc.prefill_rows, bucket), jnp.int32), p, p, p
        )
        texts[PROGRAMS["prefill"]].append(lowered.compile().as_text())
    d = enc.step_rows
    lowered = trinity.decode_step.lower(
        enc.cfg, params, state, shape(view), jax.ShapeDtypeStruct((), jnp.int32), shape(row_token),
        rows(d, jnp.int32), rows(d, jnp.int32), rows(d, jnp.bool_), rows(d, jnp.int32),
    )
    texts[PROGRAMS["decode"]].append(lowered.compile().as_text())
    return texts


KIND = _encoder.Kind(
    name="trinity_serving", programs=PROGRAMS, scopes=SCOPES, build=build, check=partial(check_baskets, ref_hidden),
    summarise=summarise, invariants=invariants, compiled_texts=compiled_texts, position="basket",
    slot_states=("window_kv", "full_kv"),
)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    return _encoder.run(KIND, cell, seed, seconds, trace, t_process, info)
