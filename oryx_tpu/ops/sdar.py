"""A block-diffusion mixture-of-experts transformer over the item catalog
(`sdar_moe`: the Qwen3-MoE layer, generation by diffusion over blocks).

    layer:  h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    Attn:   q = u Wq [heads x d], k = u Wk, v = u Wv [kv_heads x d]; q and k
            RMS-normalised per head (a learned weight of d each); RoPE over
            the whole head by absolute position; query head j reads
            key-value head j // (heads / kv_heads);
            softmax(q k^T / sqrt(d) + M) v; Wo; no biases
    M:      the session's history is the prefix, causal within itself; the
            block of B positions appended after it sees the whole prefix
            and ALL of the block
    MoE:    ops/moe.py
    out:    final RMSNorm, logits = z E_out^T over the item catalog

The vocabulary is the item catalog: row i of `E_out` is item i's row of
the served view (the FactorStore, as for the GRU), row t of `E_in` the
input embedding of announced id t, and the last row of `E_in` is [MASK].

Generation, one block a request: append B [MASK] positions; for T steps
run the layers over the block against the prefix's cached keys and
values, take logits at the still-masked positions and fix the one whose
largest softmax probability is highest to its argmax (static
low-confidence remasking, one position a step). The hidden state of a
position at the step that fixed it is what the catalog scan ranks.

Precision: weights in their stored dtype (bfloat16 as published), the
activations enter every product in that dtype and accumulate in float32;
the residual stream, the norms, the softmaxes and the router are float32.

Two forms live here. `prefill` / `denoise_step` are the served ones: a
slot cache on the device, fixed shapes, per-request block state that never
visits the host between steps. `reference_*` is the plain one: float32,
`highest` precision, full forward passes over prefix + block, no cache.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops.decoder import DecoderEncoder, Layout, rms_norm, rope, view_head
from oryx_tpu.ops.decoder import attend as _attend  # `sdar._attend`: the causal_block control patches it
from oryx_tpu.ops.moe import moe_apply, moe_reference

NORM_TENSORS = ("ln1", "ln2", "q_norm", "k_norm")


class SdarConfig(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    expert_width: int
    experts_per_token: int
    layers: int
    vocab: int               # rows of E_in: the items and, last, [MASK]
    rope_theta: float = 1_000_000.0
    eps: float = 1e-6
    block_length: int = 4
    denoise_steps: int = 4
    max_len: int = 100       # longest prefix a slot holds

    @property
    def mask_id(self) -> int:
        return self.vocab - 1

    @property
    def positions(self) -> int:
        return self.max_len + self.block_length

    @staticmethod
    def from_extensions(ext) -> "SdarConfig":
        """From an artifact's extensions: the source's own key names."""
        g = ext
        return SdarConfig(
            hidden=int(g("hidden_size")),
            heads=int(g("num_attention_heads")),
            kv_heads=int(g("num_key_value_heads")),
            head_dim=int(g("head_dim")),
            experts=int(g("num_experts")),
            expert_width=int(g("moe_intermediate_size")),
            experts_per_token=int(g("num_experts_per_tok")),
            layers=int(g("num_hidden_layers")),
            vocab=int(g("vocab_size")),
            rope_theta=float(g("rope_theta", 1_000_000.0)),
            eps=float(g("rms_norm_eps", 1e-6)),
            block_length=int(g("block_length", 4)),
            denoise_steps=int(g("denoise_steps", 4)),
            max_len=int(g("max_len", 100)),
        )

    def to_extensions(self) -> dict:
        return {
            "hidden_size": self.hidden, "num_attention_heads": self.heads,
            "num_key_value_heads": self.kv_heads, "head_dim": self.head_dim,
            "num_experts": self.experts, "moe_intermediate_size": self.expert_width,
            "num_experts_per_tok": self.experts_per_token,
            "num_hidden_layers": self.layers, "vocab_size": self.vocab,
            "rope_theta": self.rope_theta, "rms_norm_eps": self.eps,
            "block_length": self.block_length, "denoise_steps": self.denoise_steps,
            "max_len": self.max_len,
        }


def layer_shapes(cfg: SdarConfig, layer: int = 0) -> dict[str, tuple]:
    """A layer's tensors (ops/decoder.py Layout); every layer's are alike."""
    H, E, F = cfg.hidden, cfg.experts, cfg.expert_width
    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return {
        "ln1": (H,), "ln2": (H,),
        "wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H),
        "q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,),
        "router": (H, E), "wg": (E, H, F), "wu": (E, H, F), "wd": (E, F, H),
    }


LAYOUT = Layout("SDAR", layer_shapes, NORM_TENSORS)
tensor_shapes, param_count, init_tensors = LAYOUT.tensor_shapes, LAYOUT.param_count, LAYOUT.init_tensors
params_of, init_params = LAYOUT.params_of, LAYOUT.init_params


# -- pieces both forms share (the dtype of the inputs decides the precision) --

def _qkv(cfg: SdarConfig, p: dict, u, pos):
    """u [R,T,H] float32 -> q [R,T,heads,d], k, v [R,T,kv,d] float32, q and k
    normalised per head and rotated."""
    dt = p["wq"].dtype
    ub = u.astype(dt)
    f32 = jnp.float32
    r, t = u.shape[0], u.shape[1]
    q = jnp.dot(ub, p["wq"], preferred_element_type=f32).reshape(r, t, cfg.heads, cfg.head_dim)
    k = jnp.dot(ub, p["wk"], preferred_element_type=f32).reshape(r, t, cfg.kv_heads, cfg.head_dim)
    v = jnp.dot(ub, p["wv"], preferred_element_type=f32).reshape(r, t, cfg.kv_heads, cfg.head_dim)
    q = rope(rms_norm(q, p["q_norm"], cfg.eps), pos, cfg.rope_theta)
    k = rope(rms_norm(k, p["k_norm"], cfg.eps), pos, cfg.rope_theta)
    return q, k, v


def _moe(cfg: SdarConfig, p: dict, x, live):
    """The expert layer over the flattened tokens of x [R,T,H]."""
    r, t, h = x.shape
    with jax.named_scope("sdar.moe"):
        u = rms_norm(x, p["ln2"], cfg.eps).reshape(r * t, h)
        y, counts = moe_apply(
            u, p["router"], p["wg"], p["wu"], p["wd"],
            cfg.experts_per_token, live.reshape(r * t),
        )
    return x + y.reshape(r, t, h), counts


# -- the served form: a slot cache, fixed shapes -----------------------------

def init_state(cfg: SdarConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """Per-request state for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there). k, v: the prefix's keys and
    values, one array a layer; tok / masked / z / row / step: the block (its
    input tokens, which positions are still [MASK], and for each fixed
    position the hidden state, the view row and the step that fixed it)."""
    s, b = slots + 1, cfg.block_length
    kv = (s, cfg.max_len, cfg.kv_heads, cfg.head_dim)
    return {
        "k": [jnp.zeros(kv, dtype) for _ in range(cfg.layers)],
        "v": [jnp.zeros(kv, dtype) for _ in range(cfg.layers)],
        "tok": jnp.full((s, b), cfg.mask_id, jnp.int32),
        "masked": jnp.ones((s, b), bool),
        "z": jnp.zeros((s, b, cfg.hidden), jnp.float32),
        "row": jnp.full((s, b), -1, jnp.int32),
        "step": jnp.full((s, b), -1, jnp.int32),
    }


def state_bytes(cfg: SdarConfig, slots: int, itemsize: int = 2) -> dict[str, int]:
    """The slots' keys and values (the block's own state is a few KiB)."""
    return {"kv": 2 * cfg.layers * (slots + 1) * cfg.max_len * cfg.kv_heads * cfg.head_dim * itemsize}


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: SdarConfig, params: dict, state: dict, tokens, lengths, slots):
    """tokens [P,T] int32 (right-padded), lengths [P], slots [P] (the scratch
    slot for a padding row, whose length is 0) -> (state, hidden [P,H] at
    each session's last position, counts int32[3] summed over the layers).
    Writes the prefix's keys and values into the slots and resets their
    blocks to B [MASK] positions."""
    p_rows, t = tokens.shape
    dt = params["layers"][0]["wq"].dtype
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (p_rows, t))
    live = pos < lengths[:, None]
    allowed = (pos[:, None, :] <= pos[:, :, None]) & live[:, None, :]
    x = params["E_in"][tokens].astype(jnp.float32)
    k_cache, v_cache = list(state["k"]), list(state["v"])
    counts = jnp.zeros((3,), jnp.int32)
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("sdar.attn"):
            u = rms_norm(x, p["ln1"], cfg.eps)
            q, k, v = _qkv(cfg, p, u, pos)
            k_cache[l] = k_cache[l].at[slots, :t].set(k.astype(k_cache[l].dtype))
            v_cache[l] = v_cache[l].at[slots, :t].set(v.astype(v_cache[l].dtype))
            o = _attend(cfg, q, k, v, allowed, dt)
            x = x + jnp.dot(o.astype(dt), p["wo"], preferred_element_type=jnp.float32)
        x, c = _moe(cfg, p, x, live)
        counts = counts + c
    last = jnp.maximum(lengths - 1, 0)
    hidden = x[jnp.arange(p_rows), last]
    b = cfg.block_length
    state = dict(
        state, k=k_cache, v=v_cache,
        tok=state["tok"].at[slots].set(cfg.mask_id),
        masked=state["masked"].at[slots].set(True),
        row=state["row"].at[slots].set(-1),
        step=state["step"].at[slots].set(-1),
        z=state["z"].at[slots].set(jnp.zeros((b, cfg.hidden), jnp.float32)),
    )
    return state, hidden, counts


def _block_hidden(cfg: SdarConfig, params: dict, state: dict, slots, lengths, live):
    """The layers over the blocks of `slots` against their cached prefixes:
    final-normed z [D,B,H] float32 and the layers' counts."""
    d_rows, b = slots.shape[0], cfg.block_length
    dt = params["layers"][0]["wq"].dtype
    pos = lengths[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
    x = params["E_in"][state["tok"][slots]].astype(jnp.float32)
    t = cfg.max_len
    in_prefix = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]      # [D,T]
    # a block position sees its whole prefix and ALL of the block
    allowed = jnp.concatenate(
        [jnp.broadcast_to(in_prefix[:, None, :], (d_rows, b, t)),
         jnp.ones((d_rows, b, b), bool)], axis=-1,
    )
    live_tok = jnp.broadcast_to(live[:, None], (d_rows, b))
    counts = jnp.zeros((3,), jnp.int32)
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("sdar.attn"):
            u = rms_norm(x, p["ln1"], cfg.eps)
            q, k, v = _qkv(cfg, p, u, pos)
            keys = jnp.concatenate([state["k"][l][slots].astype(jnp.float32), k], axis=1)
            vals = jnp.concatenate([state["v"][l][slots].astype(jnp.float32), v], axis=1)
            o = _attend(cfg, q, keys, vals, allowed, dt)
            x = x + jnp.dot(o.astype(dt), p["wo"], preferred_element_type=jnp.float32)
        x, c = _moe(cfg, p, x, live_tok)
        counts = counts + c
    return rms_norm(x, params["final_norm"], cfg.eps), counts


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def denoise_step(
    cfg: SdarConfig, params: dict, state: dict, view, n_valid, row_token,
    slots, lengths, live, step,
):
    """One denoising step of every block in `slots` [D] (the scratch slot and
    live False for a padding row): logits of the still-masked positions over
    the `n_valid` real rows of `view` [rows, H]; the position whose largest
    softmax probability is highest is fixed to its argmax. `row_token` [rows]
    maps a view row to its E_in row (the [MASK] row where an item has no
    input embedding yet). `step` [D] is each block's own step number.

    -> (state, out) with out = {"z": [D,B,H] float32 hidden of each fixed
    position at its step, "row": [D,B] view rows fixed, "step": [D,B] the
    steps that fixed them, "counts": int32[3]}: what a finished request
    needs, and every row's, so one fetch serves whichever finished."""
    d_rows, b = slots.shape[0], cfg.block_length
    z, counts = _block_hidden(cfg, params, state, slots, lengths, live)
    with jax.named_scope("sdar.head"):
        _top, arg, conf = view_head(z.reshape(d_rows * b, cfg.hidden), view, n_valid)
        arg, conf = arg.reshape(d_rows, b), conf.reshape(d_rows, b)
    masked = state["masked"][slots]
    pick = jnp.argmax(jnp.where(masked, conf, -1.0), axis=-1)                  # [D]
    chosen_row = arg[jnp.arange(d_rows), pick]
    onehot = (jnp.arange(b)[None, :] == pick[:, None]) & masked & live[:, None]
    new_tok = jnp.where(onehot, row_token[chosen_row][:, None], state["tok"][slots])
    new_row = jnp.where(onehot, chosen_row[:, None], state["row"][slots])
    new_step = jnp.where(onehot, step[:, None], state["step"][slots])
    new_z = jnp.where(onehot[:, :, None], z, state["z"][slots])
    state = dict(
        state,
        tok=state["tok"].at[slots].set(new_tok),
        masked=state["masked"].at[slots].set(masked & ~onehot),
        row=state["row"].at[slots].set(new_row),
        step=state["step"].at[slots].set(new_step),
        z=state["z"].at[slots].set(new_z),
    )
    return state, {"z": new_z, "row": new_row, "step": new_step, "counts": counts}


# -- behind the encoder seam (ops/seq.py) ------------------------------------

class SdarEncoder(DecoderEncoder):
    """The block behind the seam (ops/decoder.py DecoderEncoder): `prefill`
    fills a request's cache slot with its whole session, `steps` denoising
    steps follow, and the request hands the catalog scan its block's
    positions, `block` rows."""

    name, config, layout = "sdar", SdarConfig, LAYOUT
    programs, slot_state = (prefill, denoise_step), (init_state, state_bytes)
    step_kind = "denoise"
    feeds_last = False  # the whole session is the prefix; a block of [MASK] follows it
    prefill_rows = 8

    @property
    def steps(self) -> int:
        return self.cfg.denoise_steps

    @property
    def block(self) -> int:
        return self.cfg.block_length

    step_tokens = block  # a step runs every position of a block

    @property
    def unknown_token(self) -> int:
        """What a step feeds for a view row with no input embedding yet."""
        return self.cfg.mask_id


# -- the plain reference: float32, highest precision, no cache ---------------

def reference_forward(cfg: SdarConfig, params: dict, tokens, n_prefix):
    """tokens [T] int32 = prefix, one block, then padding -> final-normed
    hidden [T,H] float32. Full forward pass, nothing cached; the mask is
    causal within the first `n_prefix` positions and lets the block (the
    next B) see the whole prefix and all of itself; the padding after it is
    seen by nobody. `n_prefix` may be traced: one program for every length."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        pos = jnp.arange(t)
        in_block = (pos >= n_prefix) & (pos < n_prefix + cfg.block_length)
        allowed = (pos[None, :] <= pos[:, None]) | (in_block[:, None] & in_block[None, :])
        x = params["E_in"][tokens].astype(f32)
        group = cfg.heads // cfg.kv_heads
        for p in params["layers"]:
            u = rms_norm(x, p["ln1"], cfg.eps)
            q = (u @ p["wq"].astype(f32)).reshape(t, cfg.heads, cfg.head_dim)
            k = (u @ p["wk"].astype(f32)).reshape(t, cfg.kv_heads, cfg.head_dim)
            v = (u @ p["wv"].astype(f32)).reshape(t, cfg.kv_heads, cfg.head_dim)
            q = rope(rms_norm(q, p["q_norm"], cfg.eps), pos, cfg.rope_theta)
            k = rope(rms_norm(k, p["k_norm"], cfg.eps), pos, cfg.rope_theta)
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
            s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(cfg.head_dim)
            s = jnp.where(allowed[None], s, -jnp.inf)
            o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
            x = x + o.reshape(t, cfg.heads * cfg.head_dim) @ p["wo"].astype(f32)
            u = rms_norm(x, p["ln2"], cfg.eps)
            x = x + moe_reference(
                u, p["router"], p["wg"], p["wu"], p["wd"], cfg.experts_per_token,
            )
        return rms_norm(x, params["final_norm"], cfg.eps)


@partial(jax.jit, static_argnums=(0,))
def reference_block_logits(cfg: SdarConfig, params: dict, e_out, tokens, n_prefix, n_valid):
    """Logits [B, rows] float32 of the block's positions over the catalog
    `e_out` [rows, H] (rows at or past `n_valid` read -inf), by the plain
    form: `tokens` [T] = prefix + block (its [MASK] and fixed positions as
    they stand) + padding."""
    z = reference_forward(cfg, params, tokens, n_prefix)
    zb = jax.lax.dynamic_slice_in_dim(z, n_prefix, cfg.block_length, axis=0)
    with jax.default_matmul_precision("highest"):
        logits = zb @ e_out.astype(jnp.float32).T
    return jnp.where(jnp.arange(e_out.shape[0])[None, :] < n_valid, logits, -jnp.inf)


def reference_generate(cfg: SdarConfig, params: dict, e_out, prefix, row_token=None, n_valid=None):
    """One block's generation by the plain form: prefix [n] int32 tokens,
    e_out [rows, H] -> {"row": [B] catalog rows fixed, "step": [B] the steps
    that fixed them, "logits": [steps, B, rows] float32}."""
    b = cfg.block_length
    n = len(prefix)
    n_valid = int(e_out.shape[0]) if n_valid is None else int(n_valid)
    tok = np.full(b, cfg.mask_id, dtype=np.int32)
    masked = np.ones(b, dtype=bool)
    row = np.full(b, -1, dtype=np.int64)
    step_of = np.full(b, -1, dtype=np.int64)
    all_logits = []
    for step in range(cfg.denoise_steps):
        tokens = np.zeros(cfg.positions, dtype=np.int32)
        tokens[:n], tokens[n:n + b] = prefix, tok
        logits = np.asarray(reference_block_logits(
            cfg, params, e_out, jnp.asarray(tokens), jnp.int32(n), jnp.int32(n_valid),
        ))
        all_logits.append(logits)
        top = logits.max(-1)
        conf = np.exp(top - np.asarray(jax.nn.logsumexp(jnp.asarray(logits), axis=-1)))
        pick = int(np.argmax(np.where(masked, conf, -1.0)))
        item = int(np.argmax(logits[pick]))
        masked[pick] = False
        row[pick], step_of[pick] = item, step
        tok[pick] = item if row_token is None else int(row_token[item])
    return {"row": row, "step": step_of, "logits": np.stack(all_logits)}
