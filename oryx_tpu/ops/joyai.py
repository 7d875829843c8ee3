"""A latent-attention mixture-of-experts decoder over the item catalog
(`joyai_llm_flash`, the DeepSeek-V3 layer: multi-head latent attention, a
leading dense layer, then sigmoid-routed experts beside a shared one;
generation token by token).

    layer:   h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA:     c_q = RMSNorm(u W_qa);  q = c_q W_qb, heads x (nope + rope)
             [c_kv | k_r] = u W_kva;  c = RMSNorm(c_kv);  k_rope = rope(k_r),
             ONE key for all heads;  [k_nope_h | v_h] = c W_kvb
             score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + rope(q_rope_h(t)) .
             k_rope(s)) / sqrt(nope + rope);  causal softmax;  o_h = sum p v_h;
             [o_1..o_heads] W_o.  No bias.
    rope:    the interleaved pairs (2i, 2i+1) turned by pos x theta^(-2i/d)
             (`rope_interleave`; no `rope_scaling`, so no length factor)
    FFN:     layers before `first_k_dense_replace`: SwiGLU at
             `intermediate_size`; the others: s = sigmoid(u W_r), the k experts
             with the largest s + b, weights scale x s / sum of the chosen s
             (ops/moe.py), plus a shared expert's SwiGLU of the same input
    out:     final RMSNorm, logits = z E^T over the UNTIED head

The vocabulary is the item catalog: row i of the served view (the
FactorStore's "E") is item i's row of the head, row t of `E_in` the input
embedding of announced id t.

Generation, a basket of B items a request: `prefill` runs all but the last
of the session's events into a cache slot; then B `step`s, one token each:
step 0 feeds the last event, step i the item step i-1 chose (the argmax of
the head over the view's real rows, fed back on the device through the
row's input embedding). The hidden state of step i is what the catalog scan
ranks for position i.

A slot holds, a layer and a position, the normalised latent c and the
rotated key k_rope: `kv_lora_rank + qk_rope_head_dim` numbers for all heads
where keys and values a head would be heads x (nope + rope + v).

The attention's pieces are ops/mla.py's, which ops/xing.py shares; this file
hands them its plain rotation and divisor. It has two forms, the same
function (tests/test_joyai.py):
`prefill` computes it as written, keys and values decompressed for the
bucket's positions; a step ABSORBS W_kvb: with W_kvb split a head into W_uk
and W_uv, q'_h = q_nope_h W_uk_h^T scores the cached latent itself and o_h =
(sum p c) W_uv_h, so nothing is decompressed a position.

Precision: weights in their stored dtype (bfloat16 as published), the
activations enter every product in that dtype and accumulate in float32; the
residual stream, the norms, the softmax, the router and the rotation are
float32; the cache holds c and k_rope in the weights' dtype.

`reference_forward` / `reference_generate` are the plain form: float32,
`highest` precision, the attention as written (never absorbed), every expert
in turn on every token, no cache, no batching. Not brought: the published
multi-token-prediction module (`num_nextn_predict_layers`), a draft head for
speculative decoding that the next-token logits do not depend on.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops import mla
from oryx_tpu.ops.decoder import (
    DecoderEncoder, Layout, advance, basket, dot, fed_back, reset, rms_norm, router_bias, swiglu, view_head,
)
from oryx_tpu.ops.moe import moe_apply, moe_reference

NORM_TENSORS = ("ln1", "ln2", "q_norm", "kv_norm")
# keys of the source that name a form, and the one form of each computed here
# (as an artifact's extensions spell them, lower case)
_COMPUTED = {
    "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",), "n_group": ("1",), "topk_group": ("1",),
    "norm_topk_prob": ("true",), "rope_interleave": ("true",), "rope_scaling": ("null", "none"),
    "attention_bias": ("false",), "tie_word_embeddings": ("false",), "hidden_act": ("silu",),
    "moe_layer_freq": ("1",),
}


class JoyaiConfig(NamedTuple):
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
    rope_theta: float = 32_000_000.0
    eps: float = 1e-6
    routed_scale: float = 2.5
    basket: int = 4          # items generated a request
    max_len: int = 100       # longest session a slot holds

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope

    @property
    def positions(self) -> int:
        return self.max_len + self.basket

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense

    @property
    def routing(self) -> dict:
        """The model's routing rule, as ops/moe.py takes it."""
        return {"scoring": "sigmoid", "scale": self.routed_scale}

    @staticmethod
    def from_extensions(ext) -> "JoyaiConfig":
        """From an artifact's extensions: the source's own key names. What
        the source states and this program does not compute is refused."""
        g = ext
        for key, computed in _COMPUTED.items():
            got = str(g(key, computed[0])).lower()
            if got not in computed:
                raise ValueError(f"JoyAI model states {key} = {got}; this program computes {computed[0]} alone")
        cfg = JoyaiConfig(
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
            rope_theta=float(g("rope_theta", 32_000_000.0)),
            eps=float(g("rms_norm_eps", 1e-6)),
            routed_scale=float(g("routed_scaling_factor", 2.5)),
            basket=int(g("basket", 4)),
            max_len=int(g("max_len", 100)),
        )
        if int(g("qk_head_dim", cfg.qk_dim)) != cfg.qk_dim:
            raise ValueError("JoyAI model's qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
        return cfg

    def to_extensions(self) -> dict:
        return {
            "hidden_size": self.hidden, "num_attention_heads": self.heads,
            "q_lora_rank": self.q_rank, "kv_lora_rank": self.kv_rank,
            "qk_nope_head_dim": self.nope, "qk_rope_head_dim": self.rope, "v_head_dim": self.v_dim,
            "intermediate_size": self.intermediate, "n_routed_experts": self.experts,
            "moe_intermediate_size": self.expert_width, "num_experts_per_tok": self.experts_per_token,
            "n_shared_experts": self.shared_experts, "first_k_dense_replace": self.first_dense,
            "num_hidden_layers": self.layers, "vocab_size": self.vocab, "rope_theta": self.rope_theta,
            "rms_norm_eps": self.eps, "routed_scaling_factor": self.routed_scale,
            "basket": self.basket, "max_len": self.max_len,
        }


def layer_shapes(cfg: JoyaiConfig, layer: int) -> dict[str, tuple]:
    H, E, F = cfg.hidden, cfg.experts, cfg.expert_width
    out = {
        "ln1": (H,), "ln2": (H,),
        "wq_a": (H, cfg.q_rank), "q_norm": (cfg.q_rank,), "wq_b": (cfg.q_rank, cfg.heads * cfg.qk_dim),
        "wkv_a": (H, cfg.kv_rank + cfg.rope), "kv_norm": (cfg.kv_rank,),
        "wkv_b": (cfg.kv_rank, cfg.heads * (cfg.nope + cfg.v_dim)),
        "wo": (cfg.heads * cfg.v_dim, H),
    }
    if cfg.is_dense(layer):
        out.update(wg=(H, cfg.intermediate), wu=(H, cfg.intermediate), wd=(cfg.intermediate, H))
    else:
        S = cfg.shared_experts * F
        out.update(
            router=(H, E), router_bias=(E,), wg=(E, H, F), wu=(E, H, F), wd=(E, F, H),
            shared_wg=(H, S), shared_wu=(H, S), shared_wd=(S, H),
        )
    return out


# the router's correction bias is float32 (ops/decoder.py `router_bias`)
LAYOUT = Layout(
    "JoyAI", layer_shapes, NORM_TENSORS, special={"router_bias": router_bias}, float32=("router_bias",),
)
tensor_shapes, param_count, init_tensors = LAYOUT.tensor_shapes, LAYOUT.param_count, LAYOUT.init_tensors
params_of, init_params = LAYOUT.params_of, LAYOUT.init_params


# -- pieces both served programs share: the latent attention of ops/mla.py at
# this model's plain rotation (theta^(-2i/d), no `rope_scaling`) and divisor
# sqrt(nope + rope) (ops/decoder.py `dot`: the dtype of the weights decides the
# precision of a product's inputs) ----------------------------------------------

def rope_interleaved(x, pos, theta):
    """x [..., d] float32, pos broadcastable to x's leading axes -> the pairs
    (2i, 2i+1) turned by pos x theta^(-2i/d), in place."""
    return mla.rope_interleaved(x, pos, mla.plain_frequencies(theta, x.shape[-1]))


def _queries(cfg: JoyaiConfig, p: dict, u, pos):
    """u [..., H] float32 (normalised), pos [...] -> (q_nope [..., heads,
    nope], q_rope [..., heads, rope] rotated), float32."""
    return mla.queries(cfg, p, u, pos, mla.plain_frequencies(cfg.rope_theta, cfg.rope))


def _latent(cfg: JoyaiConfig, p: dict, u, pos):
    """u [..., H] float32 (normalised), pos [...] -> what the cache keeps of
    each position: (c [..., kv_rank] normalised, k_rope [..., rope] rotated)."""
    return mla.latent(cfg, p, u, pos, mla.plain_frequencies(cfg.rope_theta, cfg.rope))


def _attend_written(cfg: JoyaiConfig, p: dict, q_nope, q_rope, c, k_rope, allowed):
    """ops/mla.py `attend_written` over a prefill's own positions."""
    return mla.attend_written(cfg, p, q_nope, q_rope, c, k_rope, allowed, math.sqrt(cfg.qk_dim))


def _attend_absorbed(cfg: JoyaiConfig, p: dict, q_nope, q_rope, c, k_rope, allowed):
    """ops/mla.py `attend_absorbed`: one query a row over its slot's cache."""
    return mla.attend_absorbed(cfg, p, q_nope, q_rope, c, k_rope, allowed, math.sqrt(cfg.qk_dim))


def _shared_expert(p: dict, u):
    """The shared expert's SwiGLU of every token's `u` [N,H] float32."""
    with jax.named_scope("joyai.shared"):
        return swiglu(u, p["shared_wg"], p["shared_wu"], p["shared_wd"])


def _ffn(cfg: JoyaiConfig, p: dict, x, live):
    """The layer's feed-forward over the tokens of x [..., H] float32: (x +
    its output, the expert layer's counts int32[3]; zeros from a dense one)."""
    if "router" not in p:
        with jax.named_scope("joyai.dense"):
            u = rms_norm(x, p["ln2"], cfg.eps)
            return x + swiglu(u, p["wg"], p["wu"], p["wd"]), jnp.zeros((3,), jnp.int32)
    with jax.named_scope("joyai.moe"):
        flat = rms_norm(x, p["ln2"], cfg.eps).reshape(-1, cfg.hidden)
        y, counts = moe_apply(
            flat, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
            live.reshape(-1), bias=p["router_bias"], **cfg.routing,
        )
    shared = _shared_expert(p, flat)
    with jax.named_scope("joyai.moe"):
        return x + (y + shared).reshape(x.shape), counts


# -- the served form: a slot cache of latents, fixed shapes --------------------

def init_state(cfg: JoyaiConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """Per-request state for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there). latent, rope_key: a layer's
    cache, one row a position (ops/mla.py `cache`). x_in: the next step's
    input embedding; z / row / step: the basket (for each position generated
    the hidden state, the view row chosen and the step that chose it:
    ops/decoder.py `basket`)."""
    return {**mla.cache(cfg, slots, dtype), **basket(cfg, slots, dtype)}


state_bytes = mla.cache_bytes


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: JoyaiConfig, params: dict, state: dict, tokens, lengths, slots, last):
    """tokens [P,T] int32 (right-padded) = each session WITHOUT its last
    event, lengths [P], slots [P] (the scratch slot for a padding row, whose
    length is 0), last [P] the last event's token -> (state, the stream
    [P,H] at each row's last position, counts int32[3] summed over the expert
    layers). A slot taken starts empty: its whole row of the cache is
    written, (c, k_rope) at the real positions and zeros elsewhere (a padded
    position writes nothing), then the last event as the first step's input
    and an empty basket."""
    p_rows, t = tokens.shape
    f32 = jnp.float32
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (p_rows, t))
    live = pos < lengths[:, None]
    allowed = (pos[:, None, :] <= pos[:, :, None]) & live[:, None, :]
    with jax.named_scope("joyai.embed"):
        x = params["E_in"][tokens].astype(f32)
    latent, rope_key = list(state["latent"]), list(state["rope_key"])
    counts = jnp.zeros((3,), jnp.int32)
    behind = ((0, 0), (0, cfg.positions - t), (0, 0))
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("joyai.attn"):
            u = rms_norm(x, p["ln1"], cfg.eps)
            q_nope, q_rope = _queries(cfg, p, u, pos)
            c, k_rope = _latent(cfg, p, u, pos)
            kept = jnp.where(live[:, :, None], c, 0.0).astype(latent[l].dtype)
            latent[l] = latent[l].at[slots].set(jnp.pad(kept, behind))
            kept = jnp.where(live[:, :, None], k_rope, 0.0).astype(rope_key[l].dtype)
            rope_key[l] = rope_key[l].at[slots].set(jnp.pad(kept, behind))
            x = x + dot(_attend_written(cfg, p, q_nope, q_rope, c, k_rope, allowed), p["wo"])
        x, n = _ffn(cfg, p, x, live)
        counts = counts + n
    with jax.named_scope("joyai.embed"):
        hidden = x[jnp.arange(p_rows), jnp.maximum(lengths - 1, 0)]
        state = reset(state, slots, params["E_in"][last], latent=latent, rope_key=rope_key)
    return state, hidden, counts


def _token_hidden(cfg: JoyaiConfig, params: dict, state: dict, slots, pos, live):
    """The layers over ONE token of each of `slots` [D] (its input embedding
    is the slot's `x_in`, its position `pos` [D]): the final-normed hidden
    state [D,H] float32, the caches with the token's (c, k_rope) written at
    `pos`, and the expert layers' counts."""
    with jax.named_scope("joyai.embed"):
        x = state["x_in"][slots].astype(jnp.float32)                            # [D,H]
    latent, rope_key = list(state["latent"]), list(state["rope_key"])
    allowed = jnp.arange(cfg.positions, dtype=jnp.int32)[None, :] <= pos[:, None]
    counts = jnp.zeros((3,), jnp.int32)
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("joyai.attn"):
            u = rms_norm(x, p["ln1"], cfg.eps)
            q_nope, q_rope = _queries(cfg, p, u, pos)
            c, k_rope = _latent(cfg, p, u, pos)
            latent[l] = latent[l].at[slots, pos].set(c.astype(latent[l].dtype))
            rope_key[l] = rope_key[l].at[slots, pos].set(k_rope.astype(rope_key[l].dtype))
            o = _attend_absorbed(cfg, p, q_nope, q_rope, latent[l][slots], rope_key[l][slots], allowed)
            x = x + dot(o, p["wo"])
        x, n = _ffn(cfg, p, x, live)
        counts = counts + n
    with jax.named_scope("joyai.head"):
        return rms_norm(x, params["final_norm"], cfg.eps), latent, rope_key, counts


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(
    cfg: JoyaiConfig, params: dict, state: dict, view, n_valid, row_token,
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
    steps that chose them, "counts": int32[3]}: what a finished request
    needs, and every row's, so one fetch serves whichever finished."""
    z, latent, rope_key, counts = _token_hidden(cfg, params, state, slots, lengths + step, live)
    with jax.named_scope("joyai.head"):
        _top, arg, _conf = view_head(z, view, n_valid)
    with jax.named_scope("joyai.embed"):
        fed = fed_back(params, row_token, arg)
        state, out = advance(state, slots, step, live, z, arg, fed, latent=latent, rope_key=rope_key)
    return state, dict(out, counts=counts)


# -- behind the encoder seam (ops/seq.py) ------------------------------------

class JoyaiEncoder(DecoderEncoder):
    """A JoyAI decoder behind the seam (ops/decoder.py DecoderEncoder)."""

    name, config, layout = "joyai", JoyaiConfig, LAYOUT
    programs, slot_state = (prefill, decode_step), (init_state, state_bytes)
    # a prefill's time is the experts its tokens reach: 4 sessions' 100-odd
    # real tokens already touch most of a layer's 256
    prefill_rows = 4


# -- the plain reference: float32, highest precision, no cache ---------------

def reference_forward(cfg: JoyaiConfig, params: dict, tokens):
    """tokens [T] int32 -> final-normed hidden [T,H] float32: one full causal
    forward pass as published, nothing cached, nothing padded, nothing
    absorbed; every expert in turn on every token (`moe_reference`)."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        pos = jnp.arange(t)
        causal = jnp.tril(jnp.ones((t, t), bool))
        x = params["E_in"][tokens].astype(f32)
        for p in params["layers"]:
            w = {k: v.astype(f32) for k, v in p.items() if v.ndim < 3}
            u = rms_norm(x, w["ln1"], cfg.eps)
            q = (rms_norm(u @ w["wq_a"], w["q_norm"], cfg.eps) @ w["wq_b"]).reshape(t, cfg.heads, cfg.qk_dim)
            q_nope = q[..., : cfg.nope]
            q_rope = rope_interleaved(q[..., cfg.nope:], pos[:, None], cfg.rope_theta)
            ckv = u @ w["wkv_a"]
            c = rms_norm(ckv[:, : cfg.kv_rank], w["kv_norm"], cfg.eps)
            k_rope = rope_interleaved(ckv[:, cfg.kv_rank:], pos, cfg.rope_theta)
            kv = (c @ w["wkv_b"]).reshape(t, cfg.heads, cfg.nope + cfg.v_dim)
            k_nope, v = kv[..., : cfg.nope], kv[..., cfg.nope:]
            s = jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_rope, k_rope)
            s = jnp.where(causal[None], s / math.sqrt(cfg.qk_dim), -jnp.inf)
            o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
            x = x + o.reshape(t, cfg.heads * cfg.v_dim) @ w["wo"]
            u = rms_norm(x, w["ln2"], cfg.eps)
            if "router" in p:
                y = moe_reference(
                    u, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
                    bias=p["router_bias"], **cfg.routing,
                )
                y = y + (jax.nn.silu(u @ w["shared_wg"]) * (u @ w["shared_wu"])) @ w["shared_wd"]
            else:
                y = (jax.nn.silu(u @ w["wg"]) * (u @ w["wu"])) @ w["wd"]
            x = x + y
        return rms_norm(x, params["final_norm"], cfg.eps)


def reference_generate(cfg: JoyaiConfig, params: dict, e_out, session, row_token=None, n_valid=None):
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
