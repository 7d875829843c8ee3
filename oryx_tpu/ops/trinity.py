"""A gated-attention mixture-of-experts decoder over the item catalog (`afmoe`,
Trinity-Large: grouped-query attention with a gate on its output, sliding
and position-free full layers mixed, four norms a layer, a leading dense
layer, then sigmoid-routed experts beside a shared one; generation token by
token). One chip may hold a SHARE of each layer's experts.

    embed:   x_0 = E_in[token] * sqrt(hidden)                   (`mup_enabled`)
    layer:   a = N_in(x);  q = qnorm(a W_q), k = knorm(a W_k) per head, v = a W_v
             a sliding layer turns q and k by their position (rope over the
             whole head, rotate-half); a full layer uses no positions
             key j is visible to query i when j <= i, and on a sliding layer
             also i - j < sliding_window
             o = softmax(q k^T / sqrt(d)) v  (heads / kv_heads queries a key-
             value head)  *  sigmoid(a W_gate), elementwise
             x = x + N_post_attn(o W_o);  m = N_pre_mlp(x);  x = x + N_post_mlp(f(m))
    f:       layers before `num_dense_layers`: SwiGLU at `intermediate_size`;
             the others: SwiGLU_shared(m) + the routed experts (ops/moe.py:
             s = sigmoid(m W_r) in float32, the k largest of s + b, weights
             route_scale x s / sum of the chosen s; b selects, never weighs)
    out:     final RMSNorm, logits = z E^T over the UNTIED head

The vocabulary is the item catalog: row i of the served view (the
FactorStore's "E") is item i's row of the head, row t of `E_in` the input
embedding of announced id t.

The held share. `num_experts` experts of each layer are HERE, the
`first_expert`-th onwards; the router has `num_experts_routed` outputs (the
model's whole count; absent: every expert is held) and keeps its
`num_experts_per_tok`. A pair routed to an expert held elsewhere adds
nothing, here and in the reference alike, and that partial result goes on to
the next layer; attention, the shared expert, the router, the embedding and
the head are whole. Nothing stands in for the other chips or their exchange.

Generation, a basket of B items a request: `prefill` runs all but the last
of the session's events into a cache slot; then B `step`s, one token each:
step 0 feeds the last event, step i the item step i-1 chose (the argmax of
the head over the view's real rows, fed back on the device through the
row's input embedding). The hidden state of step i is what the catalog scan
ranks for position i.

A slot holds TWO kinds of key-value state: a full layer keeps a row a
position (`positions` of them), a sliding layer `min(sliding_window,
positions)` rows written modulo that length: position p lies in row p mod
rows, and what a row held `rows` positions earlier has left the window by
then. Keys are kept rotated.

Precision: weights in their stored dtype (bfloat16 as published), the
activations enter every product in that dtype and accumulate in float32; the
residual stream, the norms, the softmax, the gate's sigmoid, the router and
the rotation are float32; the cache holds keys and values in the weights'
dtype.

`reference_forward` / `reference_generate` are the plain form: float32,
`highest` precision, one full forward pass, every held expert in turn on
every token, no cache, no batching.
"""

from __future__ import annotations

import math
import re
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops.decoder import (
    DecoderEncoder, Layout, advance, attend, basket, dot, fed_back, reset, rms_norm, rope,
    router_bias, swiglu, view_head,
)
from oryx_tpu.ops.moe import moe_apply, moe_reference

NORM_TENSORS = ("ln1", "ln1_post", "ln2", "ln2_post", "q_norm", "k_norm")
LAYER_TYPES = ("sliding_attention", "full_attention")
# keys of the source that name a form, and the one form of each computed here
# (as an artifact's extensions spell them, lower case)
_COMPUTED = {
    "score_func": ("sigmoid",), "route_norm": ("true",), "n_group": ("1",), "topk_group": ("1",),
    "num_expert_groups": ("1",), "num_limited_groups": ("1",), "rope_scaling": ("null", "none"),
    "tie_word_embeddings": ("false",), "hidden_act": ("silu",), "mup_enabled": ("true",),
}


class TrinityConfig(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    intermediate: int        # the leading dense layers' SwiGLU
    experts: int             # the router's outputs: every expert of the model's layer
    held: int                # num_experts: the experts of a layer that are HERE
    expert_width: int        # moe_intermediate_size
    experts_per_token: int
    shared_experts: int      # num_shared_experts: one SwiGLU of this many expert widths
    dense_layers: int        # num_dense_layers
    layer_types: tuple       # "sliding_attention" | "full_attention", one a layer
    vocab: int
    sliding_window: int = 4096
    first_expert: int = 0    # the first of the held experts
    rope_theta: float = 10_000.0
    eps: float = 1e-5
    route_scale: float = 2.448
    basket: int = 4          # items generated a request
    max_len: int = 100       # longest session a slot holds

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def positions(self) -> int:
        return self.max_len + self.basket

    def is_dense(self, layer: int) -> bool:
        return layer < self.dense_layers

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def cache_rows(self, layer: int) -> int:
        """Rows of a slot's keys (and values) in this layer."""
        return min(self.sliding_window, self.positions) if self.is_sliding(layer) else self.positions

    @property
    def routing(self) -> dict:
        """The model's routing rule and this chip's share, as ops/moe.py takes them."""
        return {"scoring": "sigmoid", "scale": self.route_scale, "held": (self.first_expert, self.held)}

    @staticmethod
    def from_extensions(ext) -> "TrinityConfig":
        """From an artifact's extensions: the source's own key names. What
        the source states and this program does not compute is refused."""
        g = ext
        for key, computed in _COMPUTED.items():
            got = str(g(key, computed[0])).lower()
            if got not in computed:
                raise ValueError(f"Trinity model states {key} = {got}; this program computes {computed[0]} alone")
        layers = int(g("num_hidden_layers"))
        stated = g("layer_types", None)
        if stated is None:
            every = int(g("global_attn_every_n_layers", 4))
            types = tuple(LAYER_TYPES[(l + 1) % every == 0] for l in range(layers))
        else:
            # a JSON list, a Python list's text or names with anything between them
            types = tuple(re.findall("|".join(LAYER_TYPES), str(stated)))
        if len(types) != layers:
            raise ValueError(f"Trinity model states {len(types)} layer_types for {layers} layers")
        held = int(g("num_experts"))
        cfg = TrinityConfig(
            hidden=int(g("hidden_size")),
            heads=int(g("num_attention_heads")),
            kv_heads=int(g("num_key_value_heads")),
            head_dim=int(g("head_dim")),
            intermediate=int(g("intermediate_size")),
            experts=int(g("num_experts_routed", held)),
            held=held,
            expert_width=int(g("moe_intermediate_size")),
            experts_per_token=int(g("num_experts_per_tok")),
            shared_experts=int(g("num_shared_experts", 1)),
            dense_layers=int(g("num_dense_layers", 1)),
            layer_types=types,
            vocab=int(g("vocab_size")),
            sliding_window=int(g("sliding_window", 4096)),
            first_expert=int(g("first_expert", 0)),
            rope_theta=float(g("rope_theta", 10_000.0)),
            eps=float(g("rms_norm_eps", 1e-5)),
            route_scale=float(g("route_scale", 2.448)),
            basket=int(g("basket", 4)),
            max_len=int(g("max_len", 100)),
        )
        if not 0 <= cfg.first_expert <= cfg.experts - cfg.held:
            raise ValueError(
                f"Trinity model holds experts {cfg.first_expert}..{cfg.first_expert + cfg.held - 1} of {cfg.experts}"
            )
        if cfg.heads % cfg.kv_heads or cfg.head_dim % 2:
            raise ValueError("Trinity model's heads do not divide over its key-value heads, or head_dim is odd")
        return cfg

    def to_extensions(self) -> dict:
        return {
            "hidden_size": self.hidden, "num_attention_heads": self.heads,
            "num_key_value_heads": self.kv_heads, "head_dim": self.head_dim,
            "intermediate_size": self.intermediate, "num_experts_routed": self.experts,
            "num_experts": self.held, "first_expert": self.first_expert,
            "moe_intermediate_size": self.expert_width, "num_experts_per_tok": self.experts_per_token,
            "num_shared_experts": self.shared_experts, "num_dense_layers": self.dense_layers,
            "num_hidden_layers": self.layers, "layer_types": list(self.layer_types),
            "vocab_size": self.vocab, "sliding_window": self.sliding_window, "rope_theta": self.rope_theta,
            "rms_norm_eps": self.eps, "route_scale": self.route_scale,
            "basket": self.basket, "max_len": self.max_len,
        }


def layer_shapes(cfg: TrinityConfig, layer: int) -> dict[str, tuple]:
    H, F = cfg.hidden, cfg.expert_width
    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    out = {
        "ln1": (H,), "ln1_post": (H,), "ln2": (H,), "ln2_post": (H,),
        "wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wgate": (H, q), "wo": (q, H),
        "q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,),
    }
    if cfg.is_dense(layer):
        out.update(wg=(H, cfg.intermediate), wu=(H, cfg.intermediate), wd=(cfg.intermediate, H))
    else:
        S = cfg.shared_experts * F
        out.update(
            router=(H, cfg.experts), router_bias=(cfg.experts,),
            wg=(cfg.held, H, F), wu=(cfg.held, H, F), wd=(cfg.held, F, H),
            shared_wg=(H, S), shared_wu=(H, S), shared_wd=(S, H),
        )
    return out


# norm weights 1 (the published depth scaling of the sandwich norms is an
# initialisation, not a form); the router's selecting bias float32
# (ops/decoder.py `router_bias`)
LAYOUT = Layout(
    "Trinity", layer_shapes, NORM_TENSORS, special={"router_bias": router_bias}, float32=("router_bias",),
)
tensor_shapes, param_count, init_tensors = LAYOUT.tensor_shapes, LAYOUT.param_count, LAYOUT.init_tensors
params_of, init_params = LAYOUT.params_of, LAYOUT.init_params


# -- pieces both served programs share (ops/decoder.py `dot`: the dtype of the
# weights decides the precision of a product's inputs) ----------------------

def _qkv(cfg: TrinityConfig, p: dict, a, pos, sliding: bool):
    """a [R,T,H] float32 (normalised), pos [R,T] -> q [R,T,heads,d], k, v
    [R,T,kv,d] float32; q and k normalised per head and, on a sliding layer
    alone, turned by their position."""
    r, t = a.shape[0], a.shape[1]
    q = rms_norm(dot(a, p["wq"]).reshape(r, t, cfg.heads, cfg.head_dim), p["q_norm"], cfg.eps)
    k = rms_norm(dot(a, p["wk"]).reshape(r, t, cfg.kv_heads, cfg.head_dim), p["k_norm"], cfg.eps)
    v = dot(a, p["wv"]).reshape(r, t, cfg.kv_heads, cfg.head_dim)
    if sliding:
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    return q, k, v


def _attn_out(cfg: TrinityConfig, p: dict, x, a, attended):
    """The attention's end: `attended` [..., heads * d] float32 gated by
    sigmoid(a W_gate), through W_o and the norm after it, added to x."""
    gated = attended * jax.nn.sigmoid(dot(a, p["wgate"]))
    return x + rms_norm(dot(gated, p["wo"]), p["ln1_post"], cfg.eps)


def _shared_expert(p: dict, u):
    """The shared expert's SwiGLU of every token's `u` [N,H] float32."""
    with jax.named_scope("trinity.shared"):
        return swiglu(u, p["shared_wg"], p["shared_wu"], p["shared_wd"])


def _ffn(cfg: TrinityConfig, p: dict, x, live):
    """The layer's feed-forward over the tokens of x [..., H] float32: (x +
    its normalised output, the expert layer's counts int32[4]; zeros from a
    dense one)."""
    if "router" not in p:
        with jax.named_scope("trinity.dense"):
            m = rms_norm(x, p["ln2"], cfg.eps)
            f = swiglu(m, p["wg"], p["wu"], p["wd"])
            return x + rms_norm(f, p["ln2_post"], cfg.eps), jnp.zeros((4,), jnp.int32)
    with jax.named_scope("trinity.moe"):
        flat = rms_norm(x, p["ln2"], cfg.eps).reshape(-1, cfg.hidden)
        y, counts = moe_apply(
            flat, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
            live.reshape(-1), bias=p["router_bias"], **cfg.routing,
        )
    shared = _shared_expert(p, flat)
    with jax.named_scope("trinity.moe"):
        return x + rms_norm((y + shared).reshape(x.shape), p["ln2_post"], cfg.eps), counts


# -- the served form: a slot cache of keys and values, fixed shapes --------------

def init_state(cfg: TrinityConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """Per-request state for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there). k, v: a layer's cache, `positions`
    rows a slot in a full layer and `cache_rows` (the window, where it is
    shorter) in a sliding one. x_in: the next step's input embedding; z / row
    / step: the basket (for each position generated the hidden state, the view
    row chosen and the step that chose it: ops/decoder.py `basket`)."""
    kv = [(slots + 1, cfg.cache_rows(l), cfg.kv_heads, cfg.head_dim) for l in range(cfg.layers)]
    return {
        "k": [jnp.zeros(shape, dtype) for shape in kv],
        "v": [jnp.zeros(shape, dtype) for shape in kv],
        **basket(cfg, slots, dtype),
    }


def state_bytes(cfg: TrinityConfig, slots: int, itemsize: int = 2) -> dict[str, int]:
    """Bytes of the slots' keys and values by the kind of layer that keeps them."""
    row = (slots + 1) * 2 * cfg.kv_heads * cfg.head_dim * itemsize
    rows = {"window_kv": 0, "full_kv": 0}
    for l in range(cfg.layers):
        rows["window_kv" if cfg.is_sliding(l) else "full_kv"] += cfg.cache_rows(l)
    return {kind: n * row for kind, n in rows.items()}


def _kept(x, lengths, rows: int):
    """What a slot's `rows` rows hold after a prefill: x [R,T,kv,d] at the
    bucket's positions -> [R,rows,kv,d], row c the newest position p <
    length with p mod rows == c, zeros where there is none. With rows >= T
    that is the positions themselves, the padded ones zeroed."""
    c = jnp.arange(rows, dtype=jnp.int32)[None, :]
    newest = c + rows * ((lengths[:, None] - 1 - c) // rows)                       # [R,rows]
    at = jnp.clip(newest, 0, x.shape[1] - 1)
    taken = jnp.take_along_axis(x, at[:, :, None, None], axis=1)
    return jnp.where((c < lengths[:, None])[:, :, None, None], taken, 0.0)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: TrinityConfig, params: dict, state: dict, tokens, lengths, slots, last):
    """tokens [P,T] int32 (right-padded) = each session WITHOUT its last
    event, lengths [P], slots [P] (the scratch slot for a padding row, whose
    length is 0), last [P] the last event's token -> (state, the stream
    [P,H] at each row's last position, counts int32[4] summed over the expert
    layers). A slot taken starts empty: every row of its cache is written,
    keys and values where a real position lies and zeros elsewhere (a padded
    position writes nothing), then the last event as the first step's input
    and an empty basket."""
    p_rows, t = tokens.shape
    f32 = jnp.float32
    dt = params["layers"][0]["wq"].dtype
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (p_rows, t))
    live = pos < lengths[:, None]
    causal = (pos[:, None, :] <= pos[:, :, None]) & live[:, None, :]
    in_window = pos[:, :, None] - pos[:, None, :] < cfg.sliding_window
    with jax.named_scope("trinity.embed"):
        x = params["E_in"][tokens].astype(f32) * math.sqrt(cfg.hidden)
    k_cache, v_cache = list(state["k"]), list(state["v"])
    counts = jnp.zeros((4,), jnp.int32)
    for l, p in enumerate(params["layers"]):
        sliding = cfg.is_sliding(l)
        with jax.named_scope("trinity.attn"):
            a = rms_norm(x, p["ln1"], cfg.eps)
            q, k, v = _qkv(cfg, p, a, pos, sliding)
            rows = cfg.cache_rows(l)
            k_cache[l] = k_cache[l].at[slots].set(_kept(k, lengths, rows).astype(k_cache[l].dtype))
            v_cache[l] = v_cache[l].at[slots].set(_kept(v, lengths, rows).astype(v_cache[l].dtype))
            x = _attn_out(cfg, p, x, a, attend(cfg, q, k, v, causal & in_window if sliding else causal, dt))
        x, n = _ffn(cfg, p, x, live)
        counts = counts + n
    with jax.named_scope("trinity.embed"):
        hidden = x[jnp.arange(p_rows), jnp.maximum(lengths - 1, 0)]
        state = reset(state, slots, params["E_in"][last], k=k_cache, v=v_cache)
    return state, hidden, counts


def _token_hidden(cfg: TrinityConfig, params: dict, state: dict, slots, pos, live):
    """The layers over ONE token of each of `slots` [D] (its input embedding
    is the slot's `x_in`, its position `pos` [D]): the final-normed hidden
    state [D,H] float32, the caches with the token's key and value written
    (row `pos` mod the layer's rows), and the expert layers' counts."""
    dt = params["layers"][0]["wq"].dtype
    with jax.named_scope("trinity.embed"):
        x = state["x_in"][slots].astype(jnp.float32) * math.sqrt(cfg.hidden)         # [D,H]
    k_cache, v_cache = list(state["k"]), list(state["v"])
    counts = jnp.zeros((4,), jnp.int32)
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("trinity.attn"):
            rows = cfg.cache_rows(l)
            a = rms_norm(x, p["ln1"], cfg.eps)
            q, k, v = _qkv(cfg, p, a[:, None, :], pos[:, None], cfg.is_sliding(l))
            at = pos % rows
            k_cache[l] = k_cache[l].at[slots, at].set(k[:, 0].astype(k_cache[l].dtype))
            v_cache[l] = v_cache[l].at[slots, at].set(v[:, 0].astype(v_cache[l].dtype))
            # row c holds position pos - ((pos - c) mod rows), the newest one
            # that lies there: written (by the prefill or a step before) when
            # it is not negative, and never a whole window behind
            c = jnp.arange(rows, dtype=jnp.int32)[None, :]
            allowed = (pos[:, None] - (pos[:, None] - c) % rows >= 0)[:, None, :]
            o = attend(cfg, q, k_cache[l][slots], v_cache[l][slots], allowed, dt)
            x = _attn_out(cfg, p, x, a, o[:, 0])
        x, n = _ffn(cfg, p, x, live)
        counts = counts + n
    with jax.named_scope("trinity.head"):
        return rms_norm(x, params["final_norm"], cfg.eps), k_cache, v_cache, counts


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(
    cfg: TrinityConfig, params: dict, state: dict, view, n_valid, row_token,
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
    steps that chose them, "counts": int32[4]}: what a finished request
    needs, and every row's, so one fetch serves whichever finished."""
    z, k_cache, v_cache, counts = _token_hidden(cfg, params, state, slots, lengths + step, live)
    with jax.named_scope("trinity.head"):
        _top, arg, _conf = view_head(z, view, n_valid)
    with jax.named_scope("trinity.embed"):
        fed = fed_back(params, row_token, arg)
        state, out = advance(state, slots, step, live, z, arg, fed, k=k_cache, v=v_cache)
    return state, dict(out, counts=counts)


# -- behind the encoder seam (ops/seq.py) ------------------------------------

class TrinityEncoder(DecoderEncoder):
    """A Trinity decoder behind the seam (ops/decoder.py DecoderEncoder); its
    `window` is the events of a session kept, not the attention's window."""

    name, config, layout = "trinity", TrinityConfig, LAYOUT
    programs, slot_state = (prefill, decode_step), (init_state, state_bytes)
    # a prefill's time is the held experts its tokens reach, 56.6 MB each at
    # the published widths: 4 sessions' 100-odd tokens reach most of a share
    prefill_rows = 4


# -- the plain reference: float32, highest precision, no cache ---------------

def reference_forward(cfg: TrinityConfig, params: dict, tokens, pos=None):
    """tokens [T] int32 -> final-normed hidden [T,H] float32: one full causal
    forward pass as published, nothing cached, nothing padded; every held
    expert in turn on every token (`moe_reference`, given the same share).
    `pos` [T]: the positions, where they are not 0..T-1 (a full layer reads
    none)."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        order = jnp.arange(t)
        pos = order if pos is None else pos
        causal = order[None, :] <= order[:, None]
        in_window = order[:, None] - order[None, :] < cfg.sliding_window
        group = cfg.heads // cfg.kv_heads
        x = params["E_in"][tokens].astype(f32) * math.sqrt(cfg.hidden)
        for l, p in enumerate(params["layers"]):
            w = {k: v.astype(f32) for k, v in p.items() if v.ndim < 3}
            a = rms_norm(x, w["ln1"], cfg.eps)
            q = rms_norm((a @ w["wq"]).reshape(t, cfg.heads, cfg.head_dim), w["q_norm"], cfg.eps)
            k = rms_norm((a @ w["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim), w["k_norm"], cfg.eps)
            v = (a @ w["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            allowed = causal
            if cfg.is_sliding(l):
                q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
                allowed = causal & in_window
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(cfg.head_dim)
            s = jnp.where(allowed[None], s, -jnp.inf)
            o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(t, cfg.heads * cfg.head_dim)
            x = x + rms_norm((o * jax.nn.sigmoid(a @ w["wgate"])) @ w["wo"], w["ln1_post"], cfg.eps)
            m = rms_norm(x, w["ln2"], cfg.eps)
            if "router" in p:
                f = moe_reference(
                    m, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
                    bias=p["router_bias"], **cfg.routing,
                )
                f = f + (jax.nn.silu(m @ w["shared_wg"]) * (m @ w["shared_wu"])) @ w["shared_wd"]
            else:
                f = (jax.nn.silu(m @ w["wg"]) * (m @ w["wu"])) @ w["wd"]
            x = x + rms_norm(f, w["ln2_post"], cfg.eps)
        return rms_norm(x, params["final_norm"], cfg.eps)


def reference_generate(cfg: TrinityConfig, params: dict, e_out, session, row_token=None, n_valid=None):
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
