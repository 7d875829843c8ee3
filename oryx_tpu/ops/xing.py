"""A latent-attention mixture-of-experts decoder whose residual path is several
streams over the item catalog (`xing4_0`, Xing4.0-29B-A4B: the DeepSeek-V3
layer, multi-head latent attention with YaRN's rotation, a leading dense
layer, then sigmoid-routed experts beside a shared one, wrapped in
manifold-constrained hyper-connections, mHC; generation token by token).

    streams: X [n, H] a token, float32: X_0 = [e, ..., e], n = `hc_mult` copies
             of e = E_in[token]
    sublayer: each of a layer's two, f (the attention, then the feed-forward),
             with its own phi [n H, 2n + n^2], alpha [3], b [2n + n^2]:
               v = vec(X) / rms(vec(X))                 (over n H; no gain)
               a = v phi                                (float32, highest precision)
               Hpre  = sigmoid(alpha_0 a[:n] + b[:n])
               Hpost = 2 sigmoid(alpha_1 a[n:2n] + b[n:2n])
               R = clamp(alpha_2 mat(a[2n:]) + b[2n:], clamp_min, clamp_max)
               M = exp(R), then `hc_sinkhorn_iters` times: every column over
                   (its sum + hc_eps), then every row over (its sum + hc_eps)
               h = sum_i Hpre_i X_i;  X_i <- sum_j M_ij X_j + Hpost_i f(h)
    f_attn:  ops/mla.py's latent attention of RMSNorm(h), its rotation at
             YaRN's per-pair frequencies and its scores over sqrt(nope + rope)
             / mscale^2 (`XingConfig.frequencies`, `.divisor`)
    f_ffn:   RMSNorm(h), then layers before `first_k_dense_replace`: SwiGLU at
             `intermediate_size`; the others: s = sigmoid(u W_r), the k experts
             with the largest s + b, weights scale x s / sum of the chosen s
             (ops/moe.py), plus a shared expert's SwiGLU of the same input
    out:     z = final RMSNorm(sum_i X_L,i), logits = z E^T over the UNTIED head

The vocabulary is the item catalog: row i of the served view (the
FactorStore's "E") is item i's row of the head, row t of `E_in` the input
embedding of announced id t.

Generation, a basket of B items a request: `prefill` runs all but the last
of the session's events into a cache slot; then B `step`s, one token each:
step 0 feeds the last event, step i the item step i-1 chose (the argmax of
the head over the view's real rows, fed back on the device through the
row's input embedding). The hidden state of step i is what the catalog scan
ranks for position i.

The streams live inside a dispatch and are never cached: a slot holds what a
JoyAI slot holds, a layer and a position the normalised latent c and the
rotated key k_rope (ops/mla.py `cache`). A prefill computes the attention as
written, a one-token step in its absorbed form. Streams are laid out
[n, ..., H], the stream first, so each of the maps' small products and mixes
runs over whole rows of tokens. Every boundary between two sublayers (the
write of the one behind, the maps and h of the one ahead) is ONE Pallas
kernel (ops/pallas_hc.py) that reads and writes each token's streams once
and walks only the blocks of positions that hold a real token; inside it
run this module's `_write`, `_maps` (and so `sinkhorn`) and
`sinkhorn_error`, looked up as the program is traced.

Each dispatch also tallies the Sinkhorn over its real tokens and every
sublayer: `hc_error`, the largest |row or column sum of M - 1| after the
iterations, and `hc_unconverged`, the matrices whose error passes
`UNCONVERGED` (serving/stepper.py publishes them as
`oryx_seq_hc_sinkhorn_error` and `oryx_seq_hc_unconverged_total`).

Precision: weights in their stored dtype (bfloat16 as published), the
activations enter every product in that dtype and accumulate in float32;
the streams, the maps (phi, alpha, b in float32, `a` at highest precision,
the Sinkhorn, the mixes), the norms, the softmax, the router and the rotation
are float32; the cache holds c and k_rope in the weights' dtype.

`reference_forward` / `reference_generate` are the plain form: float32,
`highest` precision, the streams and the maps written token by token as
above, the attention as written (never absorbed), every expert in turn on
every token, no cache, no batching. Not brought: the published
multi-token-prediction module (`num_nextn_predict_layers`), a draft head for
speculative decoding that the next-token logits do not depend on.
"""

from __future__ import annotations

import ast
import json
import math
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops import mla, pallas_hc
from oryx_tpu.ops.decoder import (
    DecoderEncoder, Layout, advance, basket, dot, fed_back, reset, rms_norm, router_bias, swiglu, view_head,
)
from oryx_tpu.ops.moe import moe_apply, moe_reference

NORM_TENSORS = ("ln1", "ln2", "q_norm", "kv_norm")
SUBLAYERS = ("attn", "ffn")
# keys of the source that name a form, and the one form of each computed here
# (as an artifact's extensions spell them, lower case)
_COMPUTED = {
    "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",), "n_group": ("1",), "topk_group": ("1",),
    "norm_topk_prob": ("true",), "rope_interleave": ("true",), "attention_bias": ("false",),
    "tie_word_embeddings": ("false",), "hidden_act": ("silu",), "moe_layer_freq": ("1",),
}


def _rope_scaling(stated) -> tuple | None:
    """An artifact's `rope_scaling` (JSON, or a Python dict's text) -> (factor,
    original_max_position_embeddings, beta_fast, beta_slow, mscale_all_dim),
    or None where it states none. A scaling this program does not compute is
    refused: another type, or cos and sin scaled (mscale's attention factor
    other than mscale_all_dim's)."""
    text = str(stated).strip()
    if text.lower() in ("", "none", "null"):
        return None
    try:
        s = json.loads(text)
    except ValueError:
        s = ast.literal_eval(text)
    kind = str(s.get("type", s.get("rope_type", ""))).lower()
    if kind != "yarn":
        raise ValueError(f"Xing model states rope_scaling of type {kind!r}; this program computes yarn alone")
    factor, ms_all = float(s["factor"]), float(s.get("mscale_all_dim", 0.0))
    if mla.yarn_mscale(factor, float(s.get("mscale", 1.0))) != mla.yarn_mscale(factor, ms_all):
        raise ValueError("Xing model states rope_scaling with mscale other than mscale_all_dim; this program "
                         "turns cos and sin unscaled alone")
    return (factor, int(s["original_max_position_embeddings"]), float(s.get("beta_fast", 32)),
            float(s.get("beta_slow", 1)), ms_all)


class XingConfig(NamedTuple):
    hidden: int
    heads: int
    q_rank: int              # q_lora_rank
    kv_rank: int             # kv_lora_rank: the cached latent's width
    nope: int                # qk_nope_head_dim
    rope: int                # qk_rope_head_dim: the cached key's width
    v_dim: int               # v_head_dim
    intermediate: int        # the leading dense layers' SwiGLU
    experts: int             # n_routed_experts
    expert_width: int        # moe_intermediate_size
    experts_per_token: int
    shared_experts: int      # n_shared_experts: one SwiGLU of this many expert widths
    first_dense: int         # first_k_dense_replace
    layers: int
    vocab: int
    hc_mult: int = 4         # the residual streams
    hc_iters: int = 20       # hc_sinkhorn_iters
    hc_eps: float = 1e-6     # added to each Sinkhorn denominator
    hc_clamp: tuple = (-30.0, 30.0)   # mhc_h_res_clamp_min / _max
    rope_theta: float = 10_000.0
    # rope_scaling (YaRN): (factor, original window, beta_fast, beta_slow,
    # mscale_all_dim); None: the plain rotation
    yarn: tuple | None = None
    eps: float = 1e-6
    routed_scale: float = 2.0
    basket: int = 4          # items generated a request
    max_len: int = 100       # longest session a slot holds

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope

    @property
    def positions(self) -> int:
        return self.max_len + self.basket

    @property
    def maps_width(self) -> int:
        """Columns of a sublayer's phi: Hpre's n, Hpost's n, R's n x n."""
        return 2 * self.hc_mult + self.hc_mult * self.hc_mult

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense

    @property
    def routing(self) -> dict:
        """The model's routing rule, as ops/moe.py takes it."""
        return {"scoring": "sigmoid", "scale": self.routed_scale}

    @property
    def frequencies(self) -> np.ndarray:
        """The rotation's inverse frequencies, float32 [rope / 2]."""
        if self.yarn is None:
            i = np.arange(0, self.rope, 2, dtype=np.float64)
            return (self.rope_theta ** (-i / self.rope)).astype(np.float32)
        return mla.yarn_frequencies(self.rope_theta, self.rope, *self.yarn[:4])

    @property
    def divisor(self) -> float:
        """The scores' divisor: sqrt(nope + rope) over YaRN's attention factor squared."""
        m = 1.0 if self.yarn is None else mla.yarn_mscale(self.yarn[0], self.yarn[4])
        return math.sqrt(self.qk_dim) / (m * m)

    @staticmethod
    def from_extensions(ext) -> "XingConfig":
        """From an artifact's extensions: the source's own key names. What
        the source states and this program does not compute is refused."""
        g = ext
        for key, computed in _COMPUTED.items():
            got = str(g(key, computed[0])).lower()
            if got not in computed:
                raise ValueError(f"Xing model states {key} = {got}; this program computes {computed[0]} alone")
        cfg = XingConfig(
            hidden=int(g("hidden_size")),
            heads=int(g("num_attention_heads")),
            q_rank=int(g("q_lora_rank")),
            kv_rank=int(g("kv_lora_rank")),
            nope=int(g("qk_nope_head_dim")),
            rope=int(g("qk_rope_head_dim")),
            v_dim=int(g("v_head_dim")),
            intermediate=int(g("intermediate_size")),
            experts=int(g("n_routed_experts")),
            expert_width=int(g("moe_intermediate_size")),
            experts_per_token=int(g("num_experts_per_tok")),
            shared_experts=int(g("n_shared_experts", 1)),
            first_dense=int(g("first_k_dense_replace", 1)),
            layers=int(g("num_hidden_layers")),
            vocab=int(g("vocab_size")),
            hc_mult=int(g("hc_mult", 4)),
            hc_iters=int(g("hc_sinkhorn_iters", 20)),
            hc_eps=float(g("hc_eps", 1e-6)),
            hc_clamp=(float(g("mhc_h_res_clamp_min", -30)), float(g("mhc_h_res_clamp_max", 30))),
            rope_theta=float(g("rope_theta", 10_000.0)),
            yarn=_rope_scaling(g("rope_scaling", "null")),
            eps=float(g("rms_norm_eps", 1e-6)),
            routed_scale=float(g("routed_scaling_factor", 2.0)),
            basket=int(g("basket", 4)),
            max_len=int(g("max_len", 100)),
        )
        if int(g("qk_head_dim", cfg.qk_dim)) != cfg.qk_dim:
            raise ValueError("Xing model's qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
        return cfg

    def to_extensions(self) -> dict:
        scaling = None if self.yarn is None else {
            "type": "yarn", "factor": self.yarn[0], "original_max_position_embeddings": self.yarn[1],
            "beta_fast": self.yarn[2], "beta_slow": self.yarn[3], "mscale": self.yarn[4],
            "mscale_all_dim": self.yarn[4],
        }
        return {
            "hidden_size": self.hidden, "num_attention_heads": self.heads,
            "q_lora_rank": self.q_rank, "kv_lora_rank": self.kv_rank,
            "qk_nope_head_dim": self.nope, "qk_rope_head_dim": self.rope, "v_head_dim": self.v_dim,
            "intermediate_size": self.intermediate, "n_routed_experts": self.experts,
            "moe_intermediate_size": self.expert_width, "num_experts_per_tok": self.experts_per_token,
            "n_shared_experts": self.shared_experts, "first_k_dense_replace": self.first_dense,
            "num_hidden_layers": self.layers, "vocab_size": self.vocab, "hc_mult": self.hc_mult,
            "hc_sinkhorn_iters": self.hc_iters, "hc_eps": self.hc_eps,
            "mhc_h_res_clamp_min": self.hc_clamp[0], "mhc_h_res_clamp_max": self.hc_clamp[1],
            "rope_theta": self.rope_theta, "rope_scaling": json.dumps(scaling),
            "rms_norm_eps": self.eps, "routed_scaling_factor": self.routed_scale,
            "basket": self.basket, "max_len": self.max_len,
        }


def layer_shapes(cfg: XingConfig, layer: int) -> dict[str, tuple]:
    H, E, F, n = cfg.hidden, cfg.experts, cfg.expert_width, cfg.hc_mult
    out = {
        "ln1": (H,), "ln2": (H,),
        "wq_a": (H, cfg.q_rank), "q_norm": (cfg.q_rank,), "wq_b": (cfg.q_rank, cfg.heads * cfg.qk_dim),
        "wkv_a": (H, cfg.kv_rank + cfg.rope), "kv_norm": (cfg.kv_rank,),
        "wkv_b": (cfg.kv_rank, cfg.heads * (cfg.nope + cfg.v_dim)),
        "wo": (cfg.heads * cfg.v_dim, H),
    }
    for sub in SUBLAYERS:
        out.update({
            f"hc_{sub}_phi": (n * H, cfg.maps_width), f"hc_{sub}_alpha": (3,), f"hc_{sub}_bias": (cfg.maps_width,),
        })
    if cfg.is_dense(layer):
        out.update(wg=(H, cfg.intermediate), wu=(H, cfg.intermediate), wd=(cfg.intermediate, H))
    else:
        S = cfg.shared_experts * F
        out.update(
            router=(H, E), router_bias=(E,), wg=(E, H, F), wu=(E, H, F), wd=(E, F, H),
            shared_wg=(H, S), shared_wu=(H, S), shared_wd=(S, H),
        )
    return out


# the maps' tensors are float32 whatever the weights' dtype: phi drawn normal x
# 0.02 (so `a` spreads by 0.02 x sqrt(n H) and the maps vary from token to
# token), alpha 1, the biases normal x 0.1 as a router's selecting bias is
# drawn (ops/decoder.py `router_bias`)
_MAPS = tuple(f"hc_{sub}_{kind}" for sub in SUBLAYERS for kind in ("phi", "alpha", "bias"))
LAYOUT = Layout(
    "Xing", layer_shapes, NORM_TENSORS + ("hc_attn_alpha", "hc_ffn_alpha"),
    special={"router_bias": router_bias, "hc_attn_bias": router_bias, "hc_ffn_bias": router_bias},
    float32=("router_bias",) + _MAPS,
)
tensor_shapes, param_count, init_tensors = LAYOUT.tensor_shapes, LAYOUT.param_count, LAYOUT.init_tensors
params_of, init_params = LAYOUT.params_of, LAYOUT.init_params


# -- the hyper-connections: the streams, a sublayer's maps, its mix --------------

def sinkhorn(r, iters: int, eps: float):
    """r [n, n, ...] float32 (row i, column j, then the tokens) -> M = exp(r)
    made doubly stochastic by `iters` alternating normalisations, columns
    first, each sum + eps."""
    m = jnp.exp(r)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def sinkhorn_error(m):
    """M [n, n, ...] -> the largest |row or column sum - 1| of each token's M."""
    rows = jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0), axis=0)
    cols = jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0), axis=0)
    return jnp.maximum(rows, cols)


def _maps(cfg: XingConfig, p: dict, sub: str, x):
    """A sublayer's three maps from the streams x [n, ..., H] float32: (Hpre
    [n, ...], Hpost [n, ...], M [n, n, ...]), float32; `a` at highest
    precision, one product a stream. phi is [n H, w] as the artifact holds
    it, or its transpose [w, n H] as the boundary kernel reads it
    (ops/pallas_hc.py)."""
    n, hidden, w = cfg.hc_mult, cfg.hidden, cfg.maps_width
    v = x * jax.lax.rsqrt(jnp.mean(x * x, axis=(0, -1)) + cfg.eps)[None, ..., None]
    phi = p[f"hc_{sub}_phi"]
    phi_t = phi if phi.shape[0] == w else phi.T                                   # [w, n H]
    a = sum(
        jax.lax.dot_general(
            phi_t[:, i * hidden:(i + 1) * hidden], v[i], (((1,), (v.ndim - 2,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )
        for i in range(n)
    )                                                                             # [2n + n^2, ...]
    alpha = p[f"hc_{sub}_alpha"]
    b = p[f"hc_{sub}_bias"].reshape(-1, *([1] * (a.ndim - 1)))
    pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
    r = jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:], cfg.hc_clamp[0], cfg.hc_clamp[1])
    return pre, post, sinkhorn(r.reshape(n, n, *a.shape[1:]), cfg.hc_iters, cfg.hc_eps)


# a matrix whose row or column sums miss 1 by more than this after the
# iterations is counted unconverged: at 20 iterations in float32 a fifth of the
# matrices whose logits spread by 2.4 (the median's error is 1e-5, the tail's
# some 1e-2); rounded to bfloat16 on the way, or cut to 5 iterations, nearly all
UNCONVERGED = 1e-3


def _boundary(cfg: XingConfig, hc, x, y=None, maps=None, p=None, sub=None, h_over=None):
    """A sublayer boundary over the streams x [n, R, T, H], one kernel
    (ops/pallas_hc.py `boundary`; the dispatch's live tokens planned once,
    `hc`): the sublayer behind closed where its `maps` and output y [R, T, H]
    are given (`_write`), `sub` of layer `p` opened where `p` is given
    (`_maps`, its h, the Sinkhorn's tally over the live tokens: the largest
    error and the matrices left unconverged). The model's functions are
    looked up here, as the program is traced."""
    with jax.named_scope("xing.hc"):
        return pallas_hc.boundary(
            cfg, hc, x, y, maps, p, sub, h_over=h_over, fns=(_maps, _write, sinkhorn_error, UNCONVERGED),
            interpret=jax.default_backend() != "tpu",
        )


def _no_tallies() -> dict:
    """A dispatch's tallies before its first layer."""
    return {
        "counts": jnp.zeros((3,), jnp.int32), "hc_error": jnp.zeros((), jnp.float32),
        "hc_unconverged": jnp.zeros((), jnp.int32),
    }


def _tallied(tallies: dict, counts, *hc) -> dict:
    """The dispatch's tallies with a layer's: its expert counts and its
    sublayers' Sinkhorn tallies `hc`."""
    return {
        "counts": tallies["counts"] + counts,
        "hc_error": jnp.maximum(tallies["hc_error"], jnp.max(jnp.stack([e for e, _ in hc]))),
        "hc_unconverged": tallies["hc_unconverged"] + sum(n for _, n in hc),
    }


def _write(x, maps, y):
    """A sublayer's exit: X_i <- sum_j M_ij X_j + Hpost_i y, y [..., H]."""
    post, m = maps
    with jax.named_scope("xing.hc"):
        return jnp.sum(m[..., None] * x[None], axis=1) + post[..., None] * y[None]


def _streams(cfg: XingConfig, e):
    """The streams a token enters with: `hc_mult` copies of e [..., H]."""
    return jnp.broadcast_to(e[None], (cfg.hc_mult, *e.shape))


# -- the sublayers' own pieces (ops/decoder.py `dot`: the dtype of the weights
# decides the precision of a product's inputs) ----------------------------------

def _latent(cfg: XingConfig, p: dict, u, pos):
    """ops/mla.py `latent` at this model's frequencies: (c, k_rope)."""
    return mla.latent(cfg, p, u, pos, cfg.frequencies)


def _shared_expert(p: dict, u):
    """The shared expert's SwiGLU of every token's `u` [N,H] float32."""
    with jax.named_scope("xing.shared"):
        return swiglu(u, p["shared_wg"], p["shared_wu"], p["shared_wd"])


def _ffn(cfg: XingConfig, p: dict, h, live):
    """The feed-forward sublayer of h [..., H] float32: (its output, the
    expert layer's counts int32[3]; zeros from a dense one)."""
    if "router" not in p:
        with jax.named_scope("xing.dense"):
            return swiglu(rms_norm(h, p["ln2"], cfg.eps), p["wg"], p["wu"], p["wd"]), jnp.zeros((3,), jnp.int32)
    with jax.named_scope("xing.moe"):
        flat = rms_norm(h, p["ln2"], cfg.eps).reshape(-1, cfg.hidden)
        y, counts = moe_apply(
            flat, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
            live.reshape(-1), bias=p["router_bias"], **cfg.routing,
        )
    shared = _shared_expert(p, flat)
    with jax.named_scope("xing.moe"):
        return (y + shared).reshape(h.shape), counts


def _hyper_connected(cfg: XingConfig, layers: list, e, live, attention):
    """The layers over tokens laid out [R, T] (a prefill's rows and
    positions; a step's tokens, one row) entering with embeddings e [R, T, H]
    float32, `live` [R, T] the real ones; `attention(l, p, h)` is layer l's
    attention sublayer of h [R, T, H]. Every boundary between sublayers is
    one kernel (`_boundary`) over the blocks that hold a live token. ->
    (the streams' sum [R, T, H] after the last layer, the tallies over the
    live tokens)."""
    with jax.named_scope("xing.embed"):
        x = _streams(cfg, e)                                                      # [n,R,T,H]
    with jax.named_scope("xing.hc"):
        hc = pallas_hc.plan(live)
    tallies = _no_tallies()
    x, h, maps, opened = _boundary(cfg, hc, x, p=layers[0], sub="attn", h_over=e)
    for l, p in enumerate(layers):
        y = attention(l, p, h)
        x, h, maps, ffn_tally = _boundary(cfg, hc, x, y, maps, p, "ffn")
        y, counts = _ffn(cfg, p, h, live)
        tallies = _tallied(tallies, counts, opened, ffn_tally)
        if l + 1 < len(layers):
            x, h, maps, opened = _boundary(cfg, hc, x, y, maps, layers[l + 1], "attn")
    return _boundary(cfg, hc, x, y, maps), tallies


# -- the served form: a slot cache of latents, fixed shapes --------------------

def init_state(cfg: XingConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """Per-request state for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there). latent, rope_key: a layer's
    cache, one row a position (ops/mla.py `cache`); the streams are never
    kept. x_in: the next step's input embedding; z / row / step: the basket
    (ops/decoder.py `basket`)."""
    return {**mla.cache(cfg, slots, dtype), **basket(cfg, slots, dtype)}


state_bytes = mla.cache_bytes


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: XingConfig, params: dict, state: dict, tokens, lengths, slots, last):
    """tokens [P,T] int32 (right-padded) = each session WITHOUT its last
    event, lengths [P], slots [P] (the scratch slot for a padding row, whose
    length is 0), last [P] the last event's token -> (state, the streams'
    sum [P,H] at each row's last position, the tallies over the real tokens:
    {"counts": int32[3] summed over the expert layers, "hc_error": float32[]
    the largest Sinkhorn error, "hc_unconverged": int32[] the matrices left
    unconverged}). A slot taken starts empty: its whole row of the cache
    is written, (c, k_rope) at the real positions and zeros elsewhere (a
    padded position writes nothing), then the last event as the first step's
    input and an empty basket."""
    p_rows, t = tokens.shape
    f32 = jnp.float32
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (p_rows, t))
    live = pos < lengths[:, None]
    allowed = (pos[:, None, :] <= pos[:, :, None]) & live[:, None, :]
    with jax.named_scope("xing.embed"):
        e = params["E_in"][tokens].astype(f32)                                   # [P,T,H]
    latent, rope_key = list(state["latent"]), list(state["rope_key"])
    behind = ((0, 0), (0, cfg.positions - t), (0, 0))

    def attention(l, p, h):
        with jax.named_scope("xing.attn"):
            u = rms_norm(h, p["ln1"], cfg.eps)
            q_nope, q_rope = mla.queries(cfg, p, u, pos, cfg.frequencies)
            c, k_rope = _latent(cfg, p, u, pos)
            kept = jnp.where(live[:, :, None], c, 0.0).astype(latent[l].dtype)
            latent[l] = latent[l].at[slots].set(jnp.pad(kept, behind))
            kept = jnp.where(live[:, :, None], k_rope, 0.0).astype(rope_key[l].dtype)
            rope_key[l] = rope_key[l].at[slots].set(jnp.pad(kept, behind))
            o = mla.attend_written(cfg, p, q_nope, q_rope, c, k_rope, allowed, cfg.divisor)
            return dot(o, p["wo"])

    summed, tallies = _hyper_connected(cfg, params["layers"], e, live, attention)
    with jax.named_scope("xing.embed"):
        hidden = summed[jnp.arange(p_rows), jnp.maximum(lengths - 1, 0)]
        state = reset(state, slots, params["E_in"][last], latent=latent, rope_key=rope_key)
    return state, hidden, tallies


def _token_hidden(cfg: XingConfig, params: dict, state: dict, slots, pos, live):
    """The layers over ONE token of each of `slots` [D] (its input embedding
    is the slot's `x_in`, its position `pos` [D]): the final-normed hidden
    state [D,H] float32, the caches with the token's (c, k_rope) written at
    `pos`, and the tallies over the `live` rows (as `prefill`'s)."""
    with jax.named_scope("xing.embed"):
        e = state["x_in"][slots].astype(jnp.float32)[None]                       # [1,D,H]: one row
    latent, rope_key = list(state["latent"]), list(state["rope_key"])
    allowed = jnp.arange(cfg.positions, dtype=jnp.int32)[None, :] <= pos[:, None]

    def attention(l, p, h):
        with jax.named_scope("xing.attn"):
            u = rms_norm(h[0], p["ln1"], cfg.eps)
            q_nope, q_rope = mla.queries(cfg, p, u, pos, cfg.frequencies)
            c, k_rope = _latent(cfg, p, u, pos)
            latent[l] = latent[l].at[slots, pos].set(c.astype(latent[l].dtype))
            rope_key[l] = rope_key[l].at[slots, pos].set(k_rope.astype(rope_key[l].dtype))
            o = mla.attend_absorbed(
                cfg, p, q_nope, q_rope, latent[l][slots], rope_key[l][slots], allowed, cfg.divisor
            )
            return dot(o, p["wo"])[None]

    summed, tallies = _hyper_connected(cfg, params["layers"], e, live[None], attention)
    with jax.named_scope("xing.head"):
        return rms_norm(summed[0], params["final_norm"], cfg.eps), latent, rope_key, tallies


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(
    cfg: XingConfig, params: dict, state: dict, view, n_valid, row_token,
    slots, lengths, live, step,
):
    """One token of every sequence in `slots` [D] (the scratch slot and live
    False for a padding row): the layers over each slot's pending input at
    position lengths + step, the head over the `n_valid` real rows of `view`
    [rows, H], and the argmax fed back: `row_token` [rows] maps the view row
    to its E_in row, the slot's next input (a row with no input embedding
    yet, `row_token` < 0, feeds zeros). `step` [D] is each sequence's own
    step number, the basket position it fills.

    -> (state, out) with out = {"z": [D,B,H] float32 hidden of each position
    generated so far, "row": [D,B] the view rows chosen, "step": [D,B] the
    steps that chose them, and `prefill`'s tallies}: what a finished request
    needs, and every row's, so one fetch serves whichever finished."""
    z, latent, rope_key, tallies = _token_hidden(cfg, params, state, slots, lengths + step, live)
    with jax.named_scope("xing.head"):
        _top, arg, _conf = view_head(z, view, n_valid)
    with jax.named_scope("xing.embed"):
        fed = fed_back(params, row_token, arg)
        state, out = advance(state, slots, step, live, z, arg, fed, latent=latent, rope_key=rope_key)
    return state, dict(out, **tallies)


# -- behind the encoder seam (ops/seq.py) ------------------------------------

class XingEncoder(DecoderEncoder):
    """A Xing decoder behind the seam (ops/decoder.py DecoderEncoder)."""

    name, config, layout = "xing", XingConfig, LAYOUT
    programs, slot_state = (prefill, decode_step), (init_state, state_bytes)
    # a prefill's time is the experts its tokens reach: 4 sessions' tokens
    # already touch most of a layer's 64
    prefill_rows = 4

    def prefill(self, params, state, *packed):
        state, hidden, tallies = self.programs[0](self.cfg, params, state, *packed)  # a dict already
        tokens, lengths = packed[0], packed[1]
        live = np.arange(tokens.shape[1])[None, :] < np.asarray(lengths)[:, None]
        return state, hidden, dict(tallies, hc_tokens=self.hc_tokens(live))

    def step(self, params, state, head, slots, lengths, live, step):
        state, out = super().step(params, state, head, slots, lengths, live, step)
        out["hc_tokens"] = self.hc_tokens(np.asarray(live)[None, :])
        return state, out

    def hc_tokens(self, live) -> tuple[int, int]:
        """(token slots walked, skipped) by a dispatch's sublayer boundaries
        over its `live` [R, T] tokens (host booleans), summed over the
        sublayers (ops/pallas_hc.py `hc_tokens`): what the stepper publishes
        as `oryx_seq_hc_tokens_total`."""
        walked, skipped = pallas_hc.hc_tokens(live)
        sublayers = len(SUBLAYERS) * self.cfg.layers
        return walked * sublayers, skipped * sublayers


# -- the plain reference: float32, highest precision, no cache ---------------

def _reference_maps(cfg: XingConfig, w: dict, sub: str, streams):
    """streams [T, n, H] -> (Hpre [T, n], Hpost [T, n], M [T, n, n]) as the
    maps are written, token by token."""
    t, n = streams.shape[0], cfg.hc_mult
    flat = streams.reshape(t, n * cfg.hidden)
    v = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.eps)
    a = v @ w[f"hc_{sub}_phi"]
    alpha, b = w[f"hc_{sub}_alpha"], w[f"hc_{sub}_bias"]
    pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * a[:, 2 * n:] + b[2 * n:], *cfg.hc_clamp)).reshape(t, n, n)
    for _ in range(cfg.hc_iters):
        m = m / (m.sum(axis=1, keepdims=True) + cfg.hc_eps)   # each column j: its sum over the rows i
        m = m / (m.sum(axis=2, keepdims=True) + cfg.hc_eps)   # each row i
    return pre, post, m


def reference_forward(cfg: XingConfig, params: dict, tokens):
    """tokens [T] int32 -> final-normed hidden [T,H] float32: one full causal
    forward pass as published, the streams and every sublayer's maps as
    written, nothing cached, nothing padded, nothing absorbed; every expert
    in turn on every token (`moe_reference`)."""
    f32 = jnp.float32
    inv = jnp.asarray(cfg.frequencies)
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        pos = jnp.arange(t)
        causal = jnp.tril(jnp.ones((t, t), bool))
        e = params["E_in"][tokens].astype(f32)
        streams = jnp.stack([e] * cfg.hc_mult, axis=1)                                 # [T, n, H]
        for p in params["layers"]:
            w = {k: v.astype(f32) for k, v in p.items() if v.ndim < 3}
            for sub in SUBLAYERS:
                pre, post, m = _reference_maps(cfg, w, sub, streams)
                h = jnp.einsum("ti,tih->th", pre, streams)
                if sub == "attn":
                    u = rms_norm(h, w["ln1"], cfg.eps)
                    q = (rms_norm(u @ w["wq_a"], w["q_norm"], cfg.eps) @ w["wq_b"]).reshape(t, cfg.heads, cfg.qk_dim)
                    q_nope = q[..., : cfg.nope]
                    q_rope = mla.rope_interleaved(q[..., cfg.nope:], pos[:, None], inv)
                    ckv = u @ w["wkv_a"]
                    c = rms_norm(ckv[:, : cfg.kv_rank], w["kv_norm"], cfg.eps)
                    k_rope = mla.rope_interleaved(ckv[:, cfg.kv_rank:], pos, inv)
                    kv = (c @ w["wkv_b"]).reshape(t, cfg.heads, cfg.nope + cfg.v_dim)
                    k_nope, v = kv[..., : cfg.nope], kv[..., cfg.nope:]
                    s = jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_rope, k_rope)
                    s = jnp.where(causal[None], s / cfg.divisor, -jnp.inf)
                    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
                    y = o.reshape(t, cfg.heads * cfg.v_dim) @ w["wo"]
                else:
                    u = rms_norm(h, w["ln2"], cfg.eps)
                    if "router" in p:
                        y = moe_reference(
                            u, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
                            bias=p["router_bias"], **cfg.routing,
                        )
                        y = y + (jax.nn.silu(u @ w["shared_wg"]) * (u @ w["shared_wu"])) @ w["shared_wd"]
                    else:
                        y = (jax.nn.silu(u @ w["wg"]) * (u @ w["wu"])) @ w["wd"]
                streams = jnp.einsum("tij,tjh->tih", m, streams) + post[:, :, None] * y[:, None, :]
        return rms_norm(streams.sum(axis=1), params["final_norm"], cfg.eps)


def reference_generate(cfg: XingConfig, params: dict, e_out, session, row_token=None, n_valid=None):
    """A basket by the plain form: session [n] int32 tokens, e_out [rows, H]
    the head (row i is item i's; `row_token` [rows] its E_in row, absent:
    i) -> {"row": [B] catalog rows chosen, "logits": [B, rows] float32}. A
    full forward pass a position."""
    n_valid = int(e_out.shape[0]) if n_valid is None else int(n_valid)
    tokens = [int(t) for t in session]
    rows, all_logits = [], []
    for _ in range(cfg.basket):
        z = reference_forward(cfg, params, jnp.asarray(tokens, dtype=jnp.int32))[-1]
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(jnp.asarray(e_out, jnp.float32)[:n_valid] @ z)
        all_logits.append(logits)
        rows.append(int(np.argmax(logits)))
        tokens.append(rows[-1] if row_token is None else int(row_token[rows[-1]]))
    return {"row": np.asarray(rows), "logits": np.stack(all_logits)}
