"""Configuration kind `xing-serving`: the session app's `/recommend-next`
through ServingLayer over HTTP with a latent-attention mixture-of-experts
decoder whose residual path is four streams (`xing`: manifold-constrained
hyper-connections around every sublayer, MLA with YaRN's rotation, a leading
dense layer, then 64 sigmoid-routed experts beside a shared one) that
generates a next basket token by token; one process holding the chip, load
from a generator process (benchmarks/seqgen.py).

The model is synthetic, from --seed: the layers' tensors and the input
embedding made on the device (`ops/xing.py init_tensors`: normal x 0.02, norm
gains 1; the maps' phi normal x 0.02, alpha 1 and biases normal x 0.1, all
float32; the router's selecting bias normal x 0.1), the UNTIED head drawn on
the host at bfloat16's values and served as the item catalog, adopted as an
artifact's tensors would be. The server is the program as it ships: default
reference.conf plus what a read-only server on mem:// brokers with port 0
needs.

Also here, because later PRs may not change them: the kind's own copy of the
plain float32 reference a layer at a time (`ref_layer`: the four streams and
each sublayer's maps written token by token, the Sinkhorn as stated; the
attention as written, never absorbed, at YaRN's frequencies; every expert in
turn on every token, upcast one at a time), the limits of the comparison that
decides `correct` (`compare` and `check_baskets` are kind ssm-serving's, over
this kind's `ref_hidden`), and the functions that compute the operations and
bytes of a dispatch, of its expert layer, of its attention and of its
hyper-connections (`step_work`, `step_bytes`, `moe_work`, `attn_work`,
`hc_work`).
"""

from __future__ import annotations

import json
import math
import time
from functools import partial

import numpy as np

from benchmarks.kinds import _encoder, joyai_serving
from benchmarks.kinds._encoder import holds  # noqa: F401 - the kind's tests read it here
from benchmarks.kinds.joyai_serving import _ref_experts, _sizes, _swiglu, attn_work, moe_work  # noqa: F401
from benchmarks.kinds.seq_serving import draw_catalog
from benchmarks.kinds.ssm_serving import _as, _norm, basket_invariants, check_baskets, compare  # noqa: F401

# What `correct` holds the served answers to, as kinds ssm-serving,
# joyai-serving and trinity-serving do: for a sample of the window's own
# requests the reference runs ONE full forward pass over [session + the basket
# the system chose] and its hidden rows at the four positions, scored over the
# catalog, are held against what the timed path returned (`compare`: distances
# in units of the position's largest |logit|, a position's `score_err` the root
# mean square over its candidates), against the float32 reference (`score_err`)
# and against the same pass WITH the configuration's stated rounding
# (`stated_err`: every product's inputs, and the latent and the rotated key as
# the cache keeps them, at bfloat16's values; the streams and the maps float32
# as stated; compiled without XLA's excess precision).
#
# Two kinds of distance, as every expert kind found. ROUNDING reaches every
# position of every request alike. ROUTING: where a token's 4th and 5th biased
# scores lie closer than the rounding, two computations reach different
# experts: a step, not a rounding, in SOME positions, and no fault. So the
# limits on the scores are held by the QUARTILE over the sampled requests at
# the worst basket position. The worst reading of all is not held here: a
# routing step moves it as far as any fault the controls plant (below).
#
# The limits, each above every sound reading on the chip and below the reading
# of the control it is there to catch (fifteen sound runs at the published
# widths, each its own seed, and one or two runs of each control; PERF.md has
# every reading; the controls are tests/benchmarks/xing_controls.py).
# ROUTING steps are LARGE here: 4 of 64 experts a token at weights summing to 2
# beside one shared expert, so one expert swapped for its near-equal moves a
# position's logits by a fifth to four fifths of the largest.
# against the float32 reference, the quartile: float32 leaves the order of
# accumulation alone (1e-7 on the CPU, where the maps in bfloat16 read 1e-3);
# bfloat16 sound 2.71e-3 to 3.66e-3; the Sinkhorn at 5 iterations 4.44e-3
# (caught by the gauge and the stated quartile), the maps in bfloat16 2.92e-3
# and 3.36e-3 (caught by the unconverged share), the streams collapsed to one
# 0.165
SCORE_TIGHT = {"float32": 2.0e-5, "bfloat16": 4.5e-3}
# against the reference with the stated rounding, the same quartile: sound
# 2.06e-3 to 2.62e-3 (the absorbed step's own rounding points and the chip's
# order of accumulation, layer after layer through four streams); the latent
# cache in 8 bits 1.49e-2 (the nearest precision below the stated one: not
# `correct` by both quartiles, 1.50e-2 against the float32 reference); the
# Sinkhorn at 5 iterations 4.41e-3; the streams collapsed to one 0.165; the
# maps in bfloat16 2.88e-3 and 2.55e-3, inside the rounding of the bfloat16
# model around them: the scores cannot hold the maps' stated float32 at these
# widths (the float32 limit does on the CPU), the unconverged share below does
STATED_TIGHT = 3.5e-3
MIN_OVERLAP = 5        # of 10 candidates the reference's, by the same quartile (sound 6.75-8)
# the worst position of all (its scores, the item fed back and the last
# candidate, whose gaps are at most 2 by construction, and the overlap) is
# read and not held: sound up to 0.48, 0.62 and 0.79 and an overlap of 0 in six
# of ten runs (routing steps), the controls 0.20-0.44, so no limit lies between
WORST = ("score_err_worst", "fixed_gap_worst", "candidate_gap_worst", "overlap_worst")
# `oryx_seq_hc_sinkhorn_error` after the window, over the dispatches of the
# sample asked again: the largest |row or column sum - 1| of any mixing matrix.
# 20 iterations leave 1.3e-5 at the median of matrices whose logits spread as
# these do (2.4) and the worst of a run's some 50,000 at 0.039-0.060 on the
# chip (sixteen readings); 5 iterations leave 0.449
HC_ERROR_LIMIT = 0.15
# `oryx_seq_hc_unconverged_total` over the run, a share of the real tokens'
# mixing matrices (one a sublayer): those whose row or column sums miss 1 by
# more than 1e-3 after the iterations. The maps alone, whatever the model's
# rounding around them: 20 iterations in float32 leave the slow tail of
# matrices whose logits spread by 2.4, a fifth of 200,000 drawn so on the CPU
# and 0.1968 to 0.1998 in five sound runs on the chip; the maps in bfloat16
# read 0.793 there (0.999 on the CPU; why the chip reads less is not known),
# 5 iterations leave 97 in 100 on the CPU, and one stream mixes nothing and
# reads 0 (the scores catch it)
HC_UNCONVERGED_LIMIT = 0.5
# an op counts under the first scope its op_name holds. Every instruction the
# program writes lies under one of these; what a traced window reads as
# `unscoped` are the compiler's own instructions, chiefly the asynchronous
# copies that bring a dispatch's dense weights from HBM into VMEM ahead of use
SCOPES = ("xing.moe", "xing.shared", "xing.hc", "xing.attn", "xing.dense", "xing.head", "xing.embed")
PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


# -- the algorithm's operations and bytes ------------------------------------------

def _streams(cfg: dict) -> tuple[int, int]:
    """(n, the columns of a sublayer's phi: Hpre's n, Hpost's n, R's n x n)."""
    n = cfg["hc_mult"]
    return n, 2 * n + n * n


def hc_work(tokens: float, cfg: dict) -> tuple[float, float]:
    """(FLOPs, bytes) ONE sublayer's hyper-connection needs for `tokens` real
    tokens: the streams' norm (n H squares and sums, n H scalings), the maps'
    product (2 n H (2n + n^2)), the maps themselves, exp and the Sinkhorn's
    2 x iterations normalisations of n x n (a sum and a division an entry
    each), the weighted sum (2 n H), the mix and the write (2 n^2 H + 3 n H);
    phi, alpha and b read once, and a token's streams read once and written
    once in float32 with the sublayer's output read once: the least a fused
    sublayer moves."""
    n, w = _streams(cfg)
    h, iters = cfg["hidden_size"], cfg["hc_sinkhorn_iters"]
    per_token = 3 * n * h + 2.0 * n * h * w + 4 * w + n * n * (1 + 4 * iters) + 2 * n * h + 2 * n * n * h + 3 * n * h
    moved = (n * h * w + 3 + w) * 4.0 + tokens * (2 * n * h + h) * 4.0
    return tokens * per_token, moved


def step_work(tokens: float, context: float, head_tokens: float, absorbed: bool, cfg: dict) -> float:
    """FLOPs the MODEL needs for one dispatch of `tokens` real tokens that
    each attend over `context` positions on average, `head_tokens` of which
    also take logits over the catalog: kind joyai-serving's (every layer's
    attention, the leading dense layers' SwiGLU, the expert layers' router,
    routed and shared experts, the head) and every sublayer's
    hyper-connection."""
    s = _sizes(cfg)
    return joyai_serving.step_work(tokens, context, head_tokens, absorbed, cfg) + 2 * s["layers"] * hc_work(tokens, cfg)[0]


def step_bytes(tokens: float, rows: float, context: float, touched: float, head: bool, cfg: dict, itemsize: int = 2) -> float:
    """Bytes one dispatch has to move: kind joyai-serving's (every layer's
    attention, the dense layers' weights, the expert layers' router and shared
    expert, the `touched` routed experts' matrices, the tokens' input
    embeddings and, for a step, the head's rows of the catalog once) and every
    sublayer's hyper-connection (its maps' tensors, the streams)."""
    s = _sizes(cfg)
    own = joyai_serving.step_bytes(tokens, rows, context, touched, head, cfg, itemsize)
    return own + 2 * s["layers"] * hc_work(tokens, cfg)[1]


# -- the plain reference, a layer at a time: float32, `highest`, no cache -------------

def frequencies(cfg: dict) -> np.ndarray:
    """The rotation's inverse frequencies [rope / 2]: theta^(-2i/d), and under
    `rope_scaling` of type yarn (the DeepSeek-V3 form) pairs past the
    correction range divided by `factor`, a linear ramp between."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / d)
    y = cfg.get("rope_scaling")
    if not y:
        return base.astype(np.float32)
    dim = lambda turns: d * math.log(y["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(dim(y["beta_fast"])), 0), min(math.ceil(dim(y["beta_slow"])), d - 1)
    kept = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (base * kept + base / y["factor"] * (1.0 - kept)).astype(np.float32)


def divisor(cfg: dict) -> float:
    """The scores' divisor: sqrt(nope + rope), over (0.1 mscale_all_dim ln
    factor + 1)^2 under YaRN."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    y = cfg.get("rope_scaling")
    m = 0.1 * y.get("mscale_all_dim", 0) * math.log(y["factor"]) + 1.0 if y else 1.0
    return math.sqrt(qk) / (m * m)


def _turn(x, pos, inv):
    """x [B,T,d] or [B,T,heads,d] with pos [T]: the pairs (2i, 2i+1) turned by
    pos x inv_i."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    if x.ndim == 4:
        ang = ang[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def _ref_maps(cfg: dict, p: dict, sub: str, streams):
    """streams [B,T,n,H] float32 -> (Hpre [B,T,n], Hpost [B,T,n], M
    [B,T,n,n]) as written, a token at a time: the maps from the streams'
    vector over its RMS, the residual map projected by the stated Sinkhorn."""
    import jax
    import jax.numpy as jnp

    n, _ = _streams(cfg)
    b, t = streams.shape[:2]
    flat = streams.reshape(b, t, -1)
    v = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    a = v @ p[f"hc_{sub}_phi"]
    alpha, bias = p[f"hc_{sub}_alpha"], p[f"hc_{sub}_bias"]
    pre = jax.nn.sigmoid(alpha[0] * a[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[..., n:2 * n] + bias[n:2 * n])
    r = jnp.clip(alpha[2] * a[..., 2 * n:] + bias[2 * n:], cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    m = jnp.exp(r).reshape(b, t, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])   # each column j: over the rows i
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])   # each row i
    return pre, post, m


def _ref_attention(cfg: dict, p: dict, h, act):
    """h [B,T,H] float32 -> latent attention's output as written (keys and
    values decompressed for every position, full causal softmax), through
    W_o. With `act` the inputs of every product, and the latent and rotated
    key (which the cache keeps at that dtype), are at that dtype's values."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = _sizes(cfg)
    heads, nope, eps, inv = s["heads"], s["nope"], cfg["rms_norm_eps"], frequencies(cfg)
    b, t, _ = h.shape
    pos = jnp.arange(t)
    u = _as(_norm(h, p["ln1"], eps), act)
    cq = _as(_norm(u @ p["wq_a"].astype(f32), p["q_norm"], eps), act)
    q = (cq @ p["wq_b"].astype(f32)).reshape(b, t, heads, s["qk"])
    q_nope, q_rope = _as(q[..., :nope], act), _as(_turn(q[..., nope:], pos, inv), act)
    ckv = u @ p["wkv_a"].astype(f32)
    c = _as(_norm(ckv[..., : s["c"]], p["kv_norm"], eps), act)
    k_rope = _as(_turn(ckv[..., s["c"]:], pos, inv), act)
    kv = (c @ p["wkv_b"].astype(f32)).reshape(b, t, heads, nope + s["v_dim"])
    k_nope, v = _as(kv[..., :nope], act), _as(kv[..., nope:], act)
    sc = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope) + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], sc / divisor(cfg), -jnp.inf)
    prob = _as(jax.nn.softmax(sc, axis=-1), act)
    o = jnp.einsum("bhts,bshd->bthd", prob, v).reshape(b, t, heads * s["v_dim"])
    return _as(o, act) @ p["wo"].astype(f32)


def ref_layer(cfg: dict, p: dict, streams, act=None):
    """streams [B,T,n,H] float32 -> the layer's: around each sublayer (the
    attention, then the feed-forward: dense, or routed experts + the shared
    one) the maps from the streams, the sublayer of their Hpre-weighted sum,
    then X_i <- sum_j M_ij X_j + Hpost_i f(h). With `act` the inputs of the
    sublayers' products are at that dtype's values; the streams, the maps, the
    norms, the softmax, the router and the rotation stay float32."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        for sub in ("attn", "ffn"):
            pre, post, m = _ref_maps(cfg, p, sub, streams)
            h = jnp.einsum("btn,btnh->bth", pre, streams)
            if sub == "attn":
                y = _ref_attention(cfg, p, h, act)
            else:
                u = _norm(h, p["ln2"], cfg["rms_norm_eps"])
                if "router" not in p:
                    y = _swiglu(u, p["wg"], p["wu"], p["wd"], act)
                else:
                    b, t, hidden = u.shape
                    flat = u.reshape(b * t, hidden)
                    routed = _ref_experts(cfg, p, flat, act)
                    y = (routed + _swiglu(flat, p["shared_wg"], p["shared_wu"], p["shared_wd"], act)).reshape(b, t, hidden)
            streams = jnp.einsum("btij,btjh->btih", m, streams) + post[..., None] * y[:, :, None, :]
        return streams


def ref_hidden(config: dict, params: dict, tokens: np.ndarray, act=None, compiled: dict | None = None):
    """tokens [B,T] int32 -> final-normed hidden [B,T,H] float32 by the plain
    form: n copies of the input embedding in, ONE layer's program at a time
    over the model's own tensors (an expert's float32 copy lives only inside
    its turn): the model is never held twice; the streams summed before the
    final norm. Compiled without XLA's excess precision, so a stated rounding
    is computed as stated. `compiled` keeps the two layer programs between
    calls of one shape and one `act`."""
    import jax
    import jax.numpy as jnp

    e = params["E_in"][jnp.asarray(tokens)].astype(jnp.float32)
    streams = jnp.broadcast_to(e[:, :, None, :], (*e.shape[:2], config["hc_mult"], e.shape[-1]))
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    compiled = {} if compiled is None else compiled
    for p in params["layers"]:
        kind = "experts" if "router" in p else "dense"
        if kind not in compiled:
            compiled[kind] = jax.jit(partial(ref_layer, config, act=act)).lower(
                jax.tree.map(shape, p), shape(streams)
            ).compile(compiler_options={"xla_allow_excess_precision": False})
        streams = compiled[kind](p, streams)
    return _norm(jnp.sum(streams, axis=2), params["final_norm"], config["rms_norm_eps"])


# -- the comparison that decides `correct` --------------------------------------------

def summarise(per_request: list[list[dict]], dtype: str = "bfloat16") -> dict:
    """The compared numbers of `compare`'s readings over the sampled
    requests, under this kind's limits; the worst position's are not held
    (`WORST`) and not compared."""
    out = _encoder.summarise(per_request, SCORE_TIGHT[dtype], STATED_TIGHT, math.inf, MIN_OVERLAP, 0)
    return {name: held for name, held in out.items() if name not in WORST}


# -- the model from the seed ----------------------------------------------------------------

EXTENSION_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "intermediate_size", "n_routed_experts",
    "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "num_hidden_layers", "vocab_size", "rope_theta", "rms_norm_eps", "routed_scaling_factor",
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
    # the forms the source names, which the program checks it computes
    "scoring_func", "topk_method", "n_group", "topk_group", "norm_topk_prob", "rope_interleave",
    "attention_bias", "tie_word_embeddings", "hidden_act", "moe_layer_freq",
    "basket", "max_len", "dtype",
)


def extensions(config: dict) -> dict:
    """The artifact's extensions: the source's own keys, as strings (the
    rotation's scaling as JSON)."""
    out = {k: str(config[k]) for k in EXTENSION_KEYS if k in config}
    return dict(out, rope_scaling=json.dumps(config.get("rope_scaling")), encoder="xing")


def build(cell: dict, seed: int, info):
    """The model from the seed and the server around it, started:
    (serving, manager, state, e_host). The caller closes `serving`."""
    # a tree without the decoder fails here, at once, before any set-up
    from oryx_tpu.ops import xing

    import jax

    from oryx_tpu.apps.seq.state import adopt_model

    config = cell["config"]
    n_items = config["vocab_size"]  # every id is an item
    t_build = time.monotonic()
    ext = extensions(config)
    enc = xing.XingEncoder.from_extensions(ext.get)
    tensors = xing.init_tensors(enc.cfg, seed, enc.dtype)
    # the untied head: the catalog's rows are its own draw
    e_host = draw_catalog(seed, n_items, config["hidden_size"])
    tensors["E"] = e_host
    state = adopt_model(None, ext.get, tensors, [f"i{j}" for j in range(n_items)])
    jax.block_until_ready(state.params)
    info(phase="model_built", seconds=time.monotonic() - t_build,
         parameters=xing.param_count(enc.cfg) + n_items * config["hidden_size"])
    return (*_encoder.serve(cell, state), state, e_host)


def invariants(config: dict, final: dict, started: dict, sent: list, timed_out: int) -> dict:
    """This kind's own entries of `compared`: a basket's, every real token of
    a prefill or a step through every expert layer's k experts, the largest
    Sinkhorn error the program reported since the window's end, and the share
    of the run's mixing matrices it left unconverged (None: not reported)."""
    whole = lambda series: final.get(series, 0.0) - started.get(series, 0.0)  # noqa: E731
    tokens = sum(whole(f'oryx_seq_step_tokens_total{{kind="{kind}",tokens="real"}}') for kind in PROGRAMS)
    pairs = tokens * config["num_experts_per_tok"] * _sizes(config)["moe"]
    matrices = tokens * 2 * config["num_hidden_layers"]
    unconverged = "oryx_seq_hc_unconverged_total"
    share = whole(unconverged) / matrices if unconverged in final and matrices else None
    return dict(
        basket_invariants(config, final, started, sent, timed_out),
        dropped_pairs=[pairs - whole("oryx_moe_routed_total"), "==", 0],
        hc_sinkhorn_error=[final.get("oryx_seq_hc_sinkhorn_error"), "<=", HC_ERROR_LIMIT],
        hc_unconverged_share=[share, "<=", HC_UNCONVERGED_LIMIT],
    )


def compiled_texts(model) -> dict[str, list[str]]:
    """The compiled text of every decoder program the engine runs, by the
    program's name on the device trace: lowered again from the live arrays'
    shapes (a persistent compile cache makes it a load)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import xing

    engine = model._engine()
    enc = engine.encoder
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    params, state = jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.state)
    view, _n_valid, row_token = engine.head()
    rows = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    texts = {PROGRAMS["prefill"]: [], PROGRAMS["decode"]: []}
    for bucket in enc.length_buckets:
        p = rows(enc.prefill_rows, jnp.int32)
        lowered = xing.prefill.lower(
            enc.cfg, params, state, jax.ShapeDtypeStruct((enc.prefill_rows, bucket), jnp.int32), p, p, p
        )
        texts[PROGRAMS["prefill"]].append(lowered.compile().as_text())
    d = enc.step_rows
    lowered = xing.decode_step.lower(
        enc.cfg, params, state, shape(view), jax.ShapeDtypeStruct((), jnp.int32), shape(row_token),
        rows(d, jnp.int32), rows(d, jnp.int32), rows(d, jnp.bool_), rows(d, jnp.int32),
    )
    texts[PROGRAMS["decode"]].append(lowered.compile().as_text())
    return texts


KIND = _encoder.Kind(
    name="xing_serving", programs=PROGRAMS, scopes=SCOPES, build=build, check=partial(check_baskets, ref_hidden),
    summarise=summarise, invariants=invariants, compiled_texts=compiled_texts, position="basket",
    slot_states=("latent", "rope_key"),
)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch}."""
    return _encoder.run(KIND, cell, seed, seconds, trace, t_process, info)
