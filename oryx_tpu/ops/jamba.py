"""A hybrid state-space / attention decoder over the item catalog (`jamba`:
Mamba-1 layers with an attention layer every `attn_layer_period`, a dense
SwiGLU feed-forward in every layer, generation token by token).

    layer:   h = x + Mixer(RMSNorm(x));  y = h + MLP(RMSNorm(h))
    Mixer:   attention where l % attn_layer_period == attn_layer_offset,
             else Mamba
    Mamba:   [x, z] = u W_in;  x = silu(conv1d_causal(x) + b_conv)  (depthwise,
             width d_conv);  [dt, B, C] = x W_x, each RMS-normalised (a learned
             weight each);  dt = softplus(dt W_dt + b_dt);  A = -exp(A_log);
             h_t = exp(dt_t A) h_{t-1} + (dt_t B_t) x_t;  y_t = h_t C_t + D x_t;
             out = (y silu(z)) W_out.  No biases but the conv's and b_dt.
    Attn:    q = u Wq [heads x d], k = u Wk, v = u Wv [kv_heads x d]; causal
             softmax(q k^T / sqrt(d)) v; Wo.  No positions, no biases.
    MLP:     (silu(u Wg) * (u Wu)) Wd
    out:     final RMSNorm, logits = z E^T over the TIED embedding

The vocabulary is the item catalog: the embedding is tied, so row i of the
served view (the FactorStore's "E") is item i's output row and row t of
`E_in` the same values as the input embedding of announced id t.

Generation, a basket of B items a request: `prefill` runs all but the last
of the session's events into a cache slot; then B `step`s, one token each:
step 0 feeds the last event, step i the item step i-1 chose (the argmax of
the head over the view's real rows, fed back on the device). The hidden
state of step i is what the catalog scan ranks for position i.

A slot holds two kinds of state side by side: a Mamba layer's recurrent
state h [d_state, d_inner] and the last d_conv - 1 inputs of its conv, both
of a fixed size whatever the session's length, and an attention layer's
keys and values, one row a position. A padded position changes neither.

Precision: weights in their stored dtype (bfloat16 as published), the
activations enter every product in that dtype and accumulate in float32; the
residual stream, the norms, the softmax, the softplus, the conv, dt, A and the
recurrence with its state are float32; keys and values are stored in the
weights' dtype.

Two forms live here. `prefill` / `decode_step` are the served ones: the slot cache
on the device, fixed shapes. A prefill starts its rows from zero and walks
their positions, the recurrence as a Pallas kernel chunked over positions
(`selective_scan`); a step continues every live row's slot by ONE position,
the conv and the recurrence as two Pallas kernels that read and write the
slots' state where it lies (`step_conv`, `step_scan`). `reference_forward` is
the plain one: float32, `highest` precision, a sequential scan, full causal
attention, no cache.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.decoder import (
    DecoderEncoder, Layout, advance, attend, basket, dot, reset, rms_norm, swiglu, view_head,
)

# a layer's tensors (`layer_shapes`): the feed-forward's in every layer, the
# mixer's by the layer's kind. Channels lie on the last axis (the lanes):
# `conv_w` is [d_conv, d_inner] and `A_log` [d_state, d_inner]
NORM_TENSORS = ("ln1", "ln2", "dt_norm", "b_norm", "c_norm")
# the recurrence's own parameters stay float32 whatever the weights' dtype
FLOAT32_TENSORS = ("dt_bias", "A_log", "D")
DT_INIT = (1e-3, 1e-1)  # softplus(b_dt) is drawn log-uniform in this range

_LANE = 128
SCAN_CHUNK = 8       # positions a grid step of the scan walks
SCAN_CHANNELS = 512  # channels whose state a loop iteration holds in registers


class JambaConfig(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    intermediate: int
    layers: int
    vocab: int
    attn_period: int
    attn_offset: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    eps: float = 1e-6
    basket: int = 4          # items generated a request
    max_len: int = 100       # longest session a slot holds

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden

    @property
    def positions(self) -> int:
        return self.max_len + self.basket

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_period == self.attn_offset

    @staticmethod
    def from_extensions(ext) -> "JambaConfig":
        """From an artifact's extensions: the source's own key names."""
        g = ext
        return JambaConfig(
            hidden=int(g("hidden_size")),
            heads=int(g("num_attention_heads")),
            kv_heads=int(g("num_key_value_heads")),
            intermediate=int(g("intermediate_size")),
            layers=int(g("num_hidden_layers")),
            vocab=int(g("vocab_size")),
            attn_period=int(g("attn_layer_period")),
            attn_offset=int(g("attn_layer_offset")),
            d_state=int(g("mamba_d_state", 16)),
            d_conv=int(g("mamba_d_conv", 4)),
            dt_rank=int(g("mamba_dt_rank", 160)),
            expand=int(g("mamba_expand", 2)),
            eps=float(g("rms_norm_eps", 1e-6)),
            basket=int(g("basket", 4)),
            max_len=int(g("max_len", 100)),
        )

    def to_extensions(self) -> dict:
        return {
            "hidden_size": self.hidden, "num_attention_heads": self.heads,
            "num_key_value_heads": self.kv_heads, "intermediate_size": self.intermediate,
            "num_hidden_layers": self.layers, "vocab_size": self.vocab,
            "attn_layer_period": self.attn_period, "attn_layer_offset": self.attn_offset,
            "mamba_d_state": self.d_state, "mamba_d_conv": self.d_conv,
            "mamba_dt_rank": self.dt_rank, "mamba_expand": self.expand,
            "rms_norm_eps": self.eps, "basket": self.basket, "max_len": self.max_len,
        }


def layer_shapes(cfg: JambaConfig, layer: int) -> dict[str, tuple]:
    H, F, C, N, R = cfg.hidden, cfg.intermediate, cfg.d_inner, cfg.d_state, cfg.dt_rank
    out = {"ln1": (H,), "ln2": (H,), "wg": (H, F), "wu": (H, F), "wd": (F, H)}
    if cfg.is_attention(layer):
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        out.update(wq=(H, q), wk=(H, kv), wv=(H, kv), wo=(q, H))
    else:
        out.update(
            in_proj=(H, 2 * C), conv_w=(cfg.d_conv, C), conv_b=(C,), x_proj=(C, R + 2 * N),
            dt_norm=(R,), b_norm=(N,), c_norm=(N,), dt_proj=(R, C), dt_bias=(C,),
            A_log=(N, C), D=(C,), out_proj=(C, H),
        )
    return out


@partial(jax.jit, static_argnums=(1, 2))
def _dt_bias(key, shape, dtype):
    """b_dt with softplus(b_dt) log-uniform in DT_INIT (Mamba's published
    initialisation): the inverse softplus of the drawn step."""
    lo, hi = DT_INIT
    dt = jnp.exp(jax.random.uniform(key, shape) * (math.log(hi) - math.log(lo)) + math.log(lo))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2))
def _conv_weight(key, shape, dtype):
    """Uniform in +-1/sqrt(d_conv): the depthwise conv's default
    initialisation (its fan-in is its width), which Mamba keeps."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, minval=-bound, maxval=bound).astype(dtype)


def _a_log(key, shape, dtype):
    """log 1..d_state down the state axis."""
    return jnp.tile(jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))[:, None], (1, shape[1]))


# Mamba's published initialisation of the mixer's own parameters (A_log, D = 1,
# b_dt, the conv's weight), without which the state saturates or dies, or the
# conv passes next to nothing, and the recurrence carries no weight in the output
LAYOUT = Layout(
    "Jamba", layer_shapes, NORM_TENSORS + ("D",),
    special={"A_log": _a_log, "dt_bias": _dt_bias, "conv_w": _conv_weight}, float32=FLOAT32_TENSORS,
)
tensor_shapes, param_count, init_tensors = LAYOUT.tensor_shapes, LAYOUT.param_count, LAYOUT.init_tensors
params_of, init_params = LAYOUT.params_of, LAYOUT.init_params


# -- the selective scan: one Pallas kernel, chunked over positions -----------

def _scan_kernel(len_ref, x_ref, dt_ref, bb_ref, cc_ref, a_ref, d_ref, h0_ref, y_ref, h_ref, *, chunk, width):
    """Grid (row, chunk of positions). The row's state h [N, C] (channels on
    lanes) stays in VMEM as the revisited output block; a chunk walks its
    positions in order, `width` channels at a time. A chunk wholly past the
    row's length does nothing but zero its outputs."""
    r, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_ref[...] = h0_ref[...]

    live = t * chunk < len_ref[r]

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _():
        lanes = min(width, _LANE)

        def channels(i, carry):
            for q in range(width // lanes):  # independent chains, one lane tile each
                sl = pl.ds(pl.multiple_of(i * width, width) + q * lanes, lanes)
                a, d, h = a_ref[:, sl], d_ref[:, sl], h_ref[:, sl]
                for j in range(chunk):
                    dt, x = dt_ref[j:j + 1, sl], x_ref[j:j + 1, sl]        # [1, lanes]
                    b, c = bb_ref[j][:, :lanes], cc_ref[j][:, :lanes]      # [N, lanes]
                    h = jnp.exp(dt * a) * h + (dt * x) * b
                    y_ref[j:j + 1, sl] = jnp.sum(h * c, axis=0, keepdims=True) + d * x
                h_ref[:, sl] = h
            return carry

        jax.lax.fori_loop(0, x_ref.shape[1] // width, channels, 0)


def _channel_block(ch: int) -> int:
    width = min(SCAN_CHANNELS, ch)
    if ch % width or (width > _LANE and width % _LANE):
        raise ValueError(f"{ch} channels do not divide into blocks of {width}")
    return width


def selective_scan(x, dt, b, c, a, d, h0, lengths):
    """The recurrence h_t = exp(dt_t A) h_{t-1} + (dt_t B_t) x_t,
    y_t = h_t C_t + D x_t over the first `lengths[r]` positions of each row;
    a position at or past a row's length changes no state (its y is not to
    be read). All float32:

    x, dt [R,T,C]; b, c [R,T,N]; a [N,C] (= -exp(A_log)); d [C]; h0 [R,N,C];
    lengths [R] int32 -> (y [R,T,C], h [R,N,C] after the last real position).
    """
    f32 = jnp.float32
    rows, t, ch = x.shape
    n = a.shape[0]
    chunk, width = SCAN_CHUNK, _channel_block(ch)
    real = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
    dt = jnp.where(real[:, :, None], dt, 0.0)  # exp(0 A) = 1 and 0 B x = 0: h stays
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, b, c))
    tp = t + pad
    lane = min(_LANE, ch)
    # B_t and C_t scale a whole column of the state: handed over already
    # spread along a lane tile, so the kernel broadcasts nothing across lanes
    bb = jnp.broadcast_to(b.astype(f32)[..., None], (rows, tp, n, lane))
    cc = jnp.broadcast_to(c.astype(f32)[..., None], (rows, tp, n, lane))
    seq = pl.BlockSpec((None, chunk, ch), lambda r, i, lens: (r, i, 0))
    col = pl.BlockSpec((None, chunk, n, lane), lambda r, i, lens: (r, i, 0, 0))
    state = pl.BlockSpec((None, n, ch), lambda r, i, lens: (r, 0, 0))
    y, h = pl.pallas_call(
        partial(_scan_kernel, chunk=chunk, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, tp // chunk),
            in_specs=[
                seq, seq, col, col,
                pl.BlockSpec((n, ch), lambda r, i, lens: (0, 0)),
                pl.BlockSpec((1, ch), lambda r, i, lens: (0, 0)),
                state,
            ],
            out_specs=[seq, state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, tp, ch), f32),
            jax.ShapeDtypeStruct((rows, n, ch), f32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="jamba_scan",
    )(
        lengths.astype(jnp.int32), x.astype(f32), dt.astype(f32), bb, cc,
        a.astype(f32), d.astype(f32).reshape(1, ch), h0.astype(f32),
    )
    return y[:, :t], h


# -- the one-token step: a slot's state read and written where it lies ---------
#
# The state operand of both kernels is the layer's WHOLE slot array, aliased
# to the output and held in HBM; the blocks a grid step works on are chosen
# through scalar-prefetched vectors, so nothing is gathered before the kernel
# and nothing scattered after it, and a row that is not live changes nothing.
# Live rows name distinct slots.

_ROWS = 8  # a float32 sublane tile: rows are handed over, and slots grouped, by eight


def _row_of(block, r):
    """Row `r` (traced) of a [rows, lanes] block as [1, lanes]: Mosaic loads
    no sublane at a traced offset, so the row is masked out and summed."""
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == r
    return jnp.sum(jnp.where(at, block, 0.0), axis=0, keepdims=True)


def _with_row(block, r, value):
    """`block` with its row `r` (traced) set to `value` [1, lanes]."""
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == r
    return jnp.where(at, value, block)


def _in_hbm(state):
    """The out_shape of a slot array updated in place. It stays in HBM, and
    so does the input aliased to it: left free, XLA stages the whole array
    through VMEM around the call (10.8 MB in and out a layer)."""
    return pltpu.HBM(state.shape, state.dtype)


def _step_conv_kernel(group_ref, fresh_ref, slot_ref, live_ref, x_ref, w_ref, bias_ref, tail_ref, xc_ref, new_ref, *, sub):
    """Grid (group of eight slots that holds a live row). x [D,C] the rows'
    new conv inputs, w [K,C], bias [1,C], tail [K-1, 8, C] the group's last
    inputs -> xc[row] = silu(conv + bias) for each live row of the group, its
    slot's tail moved on by one; zeros for a row that is not live. Past the
    `fresh` groups the grid revisits the last one and does nothing."""
    g = pl.program_id(0)
    k = w_ref.shape[0]
    per = tail_ref.shape[1]
    fresh = g < fresh_ref[0]

    @pl.when(g == 0)
    def _():
        xc_ref[...] = jnp.zeros_like(xc_ref)

    @pl.when(fresh | (g == 0))
    def _():
        new_ref[...] = tail_ref[...]

    @pl.when(fresh)
    def _():
        def row(i, carry):
            @pl.when((live_ref[i] != 0) & (slot_ref[i] // per == group_ref[g]))
            def _():
                eight = pl.ds(pl.multiple_of((i // sub) * sub, sub), sub)
                r = slot_ref[i] % per
                x = _row_of(x_ref[eight, :], i % sub)
                acc = _row_of(tail_ref[0], r) * w_ref[0:1, :]
                for j in range(1, k - 1):
                    acc = acc + _row_of(tail_ref[j], r) * w_ref[j:j + 1, :]
                acc = acc + x * w_ref[k - 1:k, :]
                xc_ref[eight, :] = _with_row(xc_ref[eight, :], i % sub, jax.nn.silu(acc + bias_ref[...]))
                for j in range(k - 2):
                    new_ref[j] = _with_row(new_ref[j], r, _row_of(tail_ref[j + 1], r))
                new_ref[k - 2] = _with_row(new_ref[k - 2], r, x)

            return carry

        jax.lax.fori_loop(0, slot_ref.shape[0], row, 0)


def step_conv(p: dict, x, conv, slots, live):
    """The conv at ONE new position of each row: x [D,C] float32 the rows'
    inputs, conv [S, d_conv - 1, C] the slots' last inputs -> (silu(conv +
    bias) [D,C], conv with every live row's slot moved on by one)."""
    f32, i32 = jnp.float32, jnp.int32
    d, ch = x.shape
    k = p["conv_w"].shape[0]
    s = conv.shape[0]
    sub = _ROWS if d % _ROWS == 0 else d
    per = min(_ROWS, s)
    groups = pl.cdiv(s, per)
    steps = min(d, groups)
    # the groups that hold a live row, each once and in order; then the last again
    held = jnp.any(
        live[:, None] & (slots[:, None] // per == jnp.arange(groups, dtype=i32)[None, :]), axis=0
    )
    fresh = jnp.sum(held.astype(i32))
    order = jnp.argsort(jnp.logical_not(held), stable=True).astype(i32)
    visit = order[jnp.minimum(jnp.arange(steps, dtype=i32), jnp.maximum(fresh - 1, 0))]
    whole = lambda n: pl.BlockSpec((n, ch), lambda g, *_: (0, 0))  # noqa: E731
    # the chip keeps [S, K-1, C] with the K-1 inputs outermost (three rows
    # would pad a sublane tile of eight): handed over as it lies, [K-1, S, C],
    # the swap is no copy, and a group's block is eight rows of each input's plane
    tails = jnp.swapaxes(conv, 0, 1)
    group = pl.BlockSpec((k - 1, per, ch), lambda g, visit, *_: (0, visit[g], 0))
    xc, tails = pl.pallas_call(
        partial(_step_conv_kernel, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[whole(d), whole(k), whole(1), group],
            out_specs=[whole(d), group],
        ),
        out_shape=[jax.ShapeDtypeStruct((d, ch), f32), _in_hbm(tails)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() != "tpu",
        name="jamba_step_conv",
    )(
        visit, fresh.reshape(1), slots.astype(i32), live.astype(i32),
        x.astype(f32), p["conv_w"].astype(f32), p["conv_b"].astype(f32).reshape(1, ch), tails,
    )
    return xc, jnp.swapaxes(tails, 0, 1)


def _step_scan_kernel(slot_ref, live_ref, x_ref, dt_ref, bb_ref, cc_ref, a_ref, d_ref, h_ref, y_ref, new_ref, *, width):
    """Grid (row). ONE position of the recurrence for the row: x, dt [8,C] of
    the row's tile of eight rows, bb, cc [N, lane tile] the row's B and C, h
    [N,C] its slot's state -> y[row] and the slot's state after the position,
    `width` channels at a time as `_scan_kernel` walks them. A row that is not
    live yields zeros and passes its slot's block through: padding rows all
    name the scratch slot and lie together, so they revisit one block, fetched
    once and written back once."""
    i = pl.program_id(0)
    r = i % x_ref.shape[0]
    live = live_ref[i] != 0

    @pl.when(live)
    def _():
        lanes = min(width, _LANE)
        b, c = bb_ref[:, :lanes], cc_ref[:, :lanes]

        def channels(j, carry):
            for q in range(width // lanes):  # independent chains, one lane tile each
                sl = pl.ds(pl.multiple_of(j * width, width) + q * lanes, lanes)
                dt, x = _row_of(dt_ref[:, sl], r), _row_of(x_ref[:, sl], r)    # [1, lanes]
                h = jnp.exp(dt * a_ref[:, sl]) * h_ref[:, sl] + (dt * x) * b
                y = jnp.sum(h * c, axis=0, keepdims=True) + d_ref[:, sl] * x
                y_ref[:, sl] = _with_row(y_ref[:, sl], r, y)
                new_ref[:, sl] = h
            return carry

        jax.lax.fori_loop(0, x_ref.shape[1] // width, channels, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = _with_row(y_ref[...], r, 0.0)
        # where the row before named the same slot the output block is
        # revisited and already holds what has to go back
        before = slot_ref[jnp.maximum(i - 1, 0)]

        @pl.when((i == 0) | (before != slot_ref[i]))
        def _():
            new_ref[...] = h_ref[...]


def step_scan(x, dt, b, c, a, d, h, slots, live):
    """`selective_scan`'s recurrence at ONE position of each row, on the
    slots' state where it lies: x, dt [D,C]; b, c [D,N]; a [N,C]; d [C]; h
    [S,N,C] float32 -> (y [D,C], h with every live row's slot advanced)."""
    f32, i32 = jnp.float32, jnp.int32
    rows, ch = x.shape
    n = a.shape[0]
    lane = min(_LANE, ch)
    sub = _ROWS if rows % _ROWS == 0 else rows
    # B and C spread along a lane tile, as `selective_scan` hands them over
    bb = jnp.broadcast_to(b.astype(f32)[..., None], (rows, n, lane))
    cc = jnp.broadcast_to(c.astype(f32)[..., None], (rows, n, lane))
    eight = pl.BlockSpec((sub, ch), lambda i, *_: (i // sub, 0))
    col = pl.BlockSpec((None, n, lane), lambda i, *_: (i, 0, 0))
    whole = lambda r: pl.BlockSpec((r, ch), lambda i, *_: (0, 0))  # noqa: E731
    slot = pl.BlockSpec((None, n, ch), lambda i, slots, live: (slots[i], 0, 0))
    return pl.pallas_call(
        partial(_step_scan_kernel, width=_channel_block(ch)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[eight, eight, col, col, whole(n), whole(1), slot],
            out_specs=[eight, slot],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, ch), f32), _in_hbm(h)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() != "tpu",
        name="jamba_step_scan",
    )(
        slots.astype(i32), live.astype(i32), x.astype(f32), dt.astype(f32), bb, cc,
        a.astype(f32), d.astype(f32).reshape(1, ch), h,
    )


# -- pieces both served programs share (ops/decoder.py `dot`: the dtype of the
# weights decides the precision of a product's inputs) ----------------------

def _mlp(cfg: JambaConfig, p: dict, x):
    with jax.named_scope("jamba.mlp"):
        u = rms_norm(x, p["ln2"], cfg.eps)
        return x + swiglu(u, p["wg"], p["wu"], p["wd"])


def _conv(p: dict, window):
    """window [..., T + d_conv - 1, C] float32 (the d_conv - 1 inputs before
    the first position in front) -> silu(conv + bias) [..., T, C]."""
    w = p["conv_w"].astype(jnp.float32)
    k = w.shape[0]
    t = window.shape[-2] - (k - 1)
    out = sum(window[..., j:j + t, :] * w[j] for j in range(k))
    return jax.nn.silu(out + p["conv_b"].astype(jnp.float32))


def _ssm_inputs(cfg: JambaConfig, p: dict, xc):
    """xc [..., C] (after the conv) -> dt [..., C], B, C [..., N] float32."""
    r, n = cfg.dt_rank, cfg.d_state
    dbc = dot(xc, p["x_proj"])
    dt = rms_norm(dbc[..., :r], p["dt_norm"], cfg.eps)
    b = rms_norm(dbc[..., r:r + n], p["b_norm"], cfg.eps)
    c = rms_norm(dbc[..., r + n:], p["c_norm"], cfg.eps)
    return jax.nn.softplus(dot(dt, p["dt_proj"]) + p["dt_bias"]), b, c


def _mamba(cfg: JambaConfig, p: dict, x, tail, h0, lengths):
    """The Mamba mixer over x [R,T,H] float32 (the residual stream) from the
    conv's last inputs `tail` [R, d_conv - 1, C] and the state `h0` [R,N,C]:
    (x + mixer, the new tail, the new state). Positions at or past
    `lengths` [R] are padding: they change neither."""
    k = cfg.d_conv
    with jax.named_scope("jamba.mamba"):
        xz = dot(rms_norm(x, p["ln1"], cfg.eps), p["in_proj"])
        xs, z = xz[..., : cfg.d_inner], xz[..., cfg.d_inner:]
        window = jnp.concatenate([tail, xs], axis=1)
        # the last d_conv - 1 REAL inputs: window rows lengths .. lengths + k - 2
        at = lengths[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
        with jax.named_scope("jamba.scan"):
            xc = _conv(p, window)
        dt, b, c = _ssm_inputs(cfg, p, xc)
        with jax.named_scope("jamba.scan"):
            y, h = selective_scan(xc, dt, b, c, -jnp.exp(p["A_log"]), p["D"], h0, lengths)
        return x + dot(y * jax.nn.silu(z), p["out_proj"]), new_tail, h


def _qkv(cfg: JambaConfig, p: dict, u):
    r, t = u.shape[0], u.shape[1]
    q = dot(u, p["wq"]).reshape(r, t, cfg.heads, cfg.head_dim)
    k = dot(u, p["wk"]).reshape(r, t, cfg.kv_heads, cfg.head_dim)
    v = dot(u, p["wv"]).reshape(r, t, cfg.kv_heads, cfg.head_dim)
    return q, k, v


# -- the served form: a slot cache of two kinds of state, fixed shapes -------

def init_state(cfg: JambaConfig, slots: int, dtype=jnp.bfloat16) -> dict:
    """Per-request state for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there). By layer (None where the layer
    is of the other kind): h, conv: a Mamba layer's recurrent state and its
    conv's last inputs, float32; k, v: an attention layer's keys and values.
    x_in: the next step's input embedding; z / row / step: the basket (for
    each position generated the hidden state, the view row chosen and the
    step that chose it: ops/decoder.py `basket`)."""
    s = slots + 1
    kv = (s, cfg.positions, cfg.kv_heads, cfg.head_dim)
    attn = [cfg.is_attention(l) for l in range(cfg.layers)]
    f32 = jnp.float32
    return {
        "h": [None if a else jnp.zeros((s, cfg.d_state, cfg.d_inner), f32) for a in attn],
        "conv": [None if a else jnp.zeros((s, cfg.d_conv - 1, cfg.d_inner), f32) for a in attn],
        "k": [jnp.zeros(kv, dtype) if a else None for a in attn],
        "v": [jnp.zeros(kv, dtype) if a else None for a in attn],
        **basket(cfg, slots, dtype),
    }


def state_bytes(cfg: JambaConfig, slots: int, itemsize: int = 2) -> dict[str, int]:
    """Bytes of the slots' state by its kind: `recurrent` is the same
    whatever a session's length, `kv` grows a row a position."""
    s = slots + 1
    mamba = sum(1 for l in range(cfg.layers) if not cfg.is_attention(l))
    recurrent = mamba * s * (cfg.d_state + cfg.d_conv - 1) * cfg.d_inner * 4
    kv = (cfg.layers - mamba) * 2 * s * cfg.positions * cfg.kv_heads * cfg.head_dim * itemsize
    return {"recurrent": recurrent, "kv": kv}


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: JambaConfig, params: dict, state: dict, tokens, lengths, slots, last):
    """tokens [P,T] int32 (right-padded) = each session WITHOUT its last
    event, lengths [P], slots [P] (the scratch slot for a padding row, whose
    length is 0), last [P] the last event's token -> (state, the stream
    [P,H] at each row's last position). Starts every slot from zero: writes
    the recurrent state, conv inputs, keys and values the events leave, the
    last event as the first step's input, and an empty basket."""
    p_rows, t = tokens.shape
    f32 = jnp.float32
    dt = params["layers"][0]["wg"].dtype
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (p_rows, t))
    live = pos < lengths[:, None]
    allowed = (pos[:, None, :] <= pos[:, :, None]) & live[:, None, :]
    x = params["E_in"][tokens].astype(f32)
    new = {key: list(state[key]) for key in ("h", "conv", "k", "v")}
    zero_tail = jnp.zeros((p_rows, cfg.d_conv - 1, cfg.d_inner), f32)
    zero_h = jnp.zeros((p_rows, cfg.d_state, cfg.d_inner), f32)
    for l, p in enumerate(params["layers"]):
        if cfg.is_attention(l):
            with jax.named_scope("jamba.attn"):
                q, k, v = _qkv(cfg, p, rms_norm(x, p["ln1"], cfg.eps))
                new["k"][l] = new["k"][l].at[slots, :t].set(k.astype(new["k"][l].dtype))
                new["v"][l] = new["v"][l].at[slots, :t].set(v.astype(new["v"][l].dtype))
                x = x + dot(attend(cfg, q, k, v, allowed, dt), p["wo"])
        else:
            x, tail, h = _mamba(cfg, p, x, zero_tail, zero_h, lengths)
            new["conv"][l] = new["conv"][l].at[slots].set(tail)
            new["h"][l] = new["h"][l].at[slots].set(h)
        x = _mlp(cfg, p, x)
    hidden = x[jnp.arange(p_rows), jnp.maximum(lengths - 1, 0)]
    return reset(state, slots, params["E_in"][last], **new), hidden


def _mamba_step(cfg: JambaConfig, p: dict, x, conv, h, slots, live):
    """`_mamba` at ONE position of each row: x [D,H] float32 the residual
    stream, conv and h the layer's WHOLE slot arrays -> (x + mixer, conv, h)
    with the live rows' slots moved on by the position."""
    with jax.named_scope("jamba.mamba"):
        xz = dot(rms_norm(x, p["ln1"], cfg.eps), p["in_proj"])
        xs, z = xz[:, : cfg.d_inner], xz[:, cfg.d_inner:]
        with jax.named_scope("jamba.scan"):
            xc, conv = step_conv(p, xs, conv, slots, live)
        dt, b, c = _ssm_inputs(cfg, p, xc)
        with jax.named_scope("jamba.scan"):
            y, h = step_scan(xc, dt, b, c, -jnp.exp(p["A_log"]), p["D"], h, slots, live)
        return x + dot(y * jax.nn.silu(z), p["out_proj"]), conv, h


def _token_hidden(cfg: JambaConfig, params: dict, state: dict, slots, pos, live):
    """The layers over ONE token of each of `slots` [D] (its input embedding
    is the slot's `x_in`, its position `pos` [D]): the final-normed hidden
    state [D,H] float32 and the layers' new state. A Mamba layer's state is
    updated in its slot; only `live` rows' slots change."""
    f32 = jnp.float32
    dt = params["layers"][0]["wg"].dtype
    x = state["x_in"][slots].astype(f32)                                        # [D,H]
    new = {key: list(state[key]) for key in ("h", "conv", "k", "v")}
    allowed = (jnp.arange(cfg.positions, dtype=jnp.int32)[None, :] <= pos[:, None])[:, None, :]
    for l, p in enumerate(params["layers"]):
        if cfg.is_attention(l):
            with jax.named_scope("jamba.attn"):
                q, k, v = _qkv(cfg, p, rms_norm(x, p["ln1"], cfg.eps)[:, None, :])
                new["k"][l] = new["k"][l].at[slots, pos].set(k[:, 0].astype(new["k"][l].dtype))
                new["v"][l] = new["v"][l].at[slots, pos].set(v[:, 0].astype(new["v"][l].dtype))
                o = attend(
                    cfg, q, new["k"][l][slots].astype(f32), new["v"][l][slots].astype(f32), allowed, dt
                )
                x = x + dot(o[:, 0], p["wo"])
        else:
            x, new["conv"][l], new["h"][l] = _mamba_step(
                cfg, p, x, new["conv"][l], new["h"][l], slots, live
            )
        x = _mlp(cfg, p, x)
    return rms_norm(x, params["final_norm"], cfg.eps), new


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(cfg: JambaConfig, params: dict, state: dict, view, n_valid, slots, lengths, live, step):
    """One token of every sequence in `slots` [D] (the scratch slot and live
    False for a padding row): the layers over each slot's pending input at
    position lengths + step, the head over the `n_valid` real rows of `view`
    [rows, H], and the argmax fed back: its view row (the tied embedding) is
    the slot's next input. `step` [D] is each sequence's own step number, the
    basket position it fills.

    -> (state, out) with out = {"z": [D,B,H] float32 hidden of each position
    generated so far, "row": [D,B] the view rows chosen, "step": [D,B] the
    steps that chose them}: what a finished request needs, and every row's,
    so one fetch serves whichever finished."""
    z, new = _token_hidden(cfg, params, state, slots, lengths + step, live)
    with jax.named_scope("jamba.head"):
        _top, arg, _conf = view_head(z, view, n_valid)
        fed = view[arg][:, : cfg.hidden]
    return advance(state, slots, step, live, z, arg, fed, **new)


# -- behind the encoder seam (ops/seq.py) ------------------------------------

class JambaEncoder(DecoderEncoder):
    """A Jamba decoder behind the seam (ops/decoder.py DecoderEncoder)."""

    name, config, layout = "jamba", JambaConfig, LAYOUT
    programs, slot_state = (prefill, decode_step), (init_state, state_bytes)
    unknown_token = None  # a step feeds back the view's own row: no token needed
    # 4 sessions x 100 positions is still about a pass over the weights' worth
    # of MXU time (12 ms beside 7); 8 x 100 was 32 ms for what is mostly padding
    prefill_rows = 4

    def prefill(self, params, state, *packed):
        return (*self.programs[0](self.cfg, params, state, *packed), {})  # no expert layer, no tallies

    def call_step(self, params, state, view, n_valid, row_token, rows):
        return self.programs[1](self.cfg, params, state, view, n_valid, *rows)  # the tied head needs no row_token


# -- the plain reference: float32, highest precision, no cache ---------------

def _reference_mamba(cfg: JambaConfig, p: dict, u):
    """u [T,H] float32 (normalised) -> the mixer's output [T,H]: the
    recurrence one position after another."""
    f32 = jnp.float32
    k = cfg.d_conv
    xz = u @ p["in_proj"].astype(f32)
    x, z = xz[:, : cfg.d_inner], xz[:, cfg.d_inner:]
    window = jnp.concatenate([jnp.zeros((k - 1, cfg.d_inner), f32), x], axis=0)
    w = p["conv_w"].astype(f32)
    x = jax.nn.silu(
        sum(window[j:j + x.shape[0]] * w[j] for j in range(k)) + p["conv_b"].astype(f32)
    )
    r, n = cfg.dt_rank, cfg.d_state
    dbc = x @ p["x_proj"].astype(f32)
    dt = rms_norm(dbc[:, :r], p["dt_norm"], cfg.eps)
    b = rms_norm(dbc[:, r:r + n], p["b_norm"], cfg.eps)
    c = rms_norm(dbc[:, r + n:], p["c_norm"], cfg.eps)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(f32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                                                    # [N,C]

    def one(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0) + p["D"] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((n, cfg.d_inner), f32), (x, dt, b, c))
    return (y * jax.nn.silu(z)) @ p["out_proj"].astype(f32)


def reference_forward(cfg: JambaConfig, params: dict, tokens):
    """tokens [T] int32 -> final-normed hidden [T,H] float32: one full causal
    forward pass, nothing cached, nothing padded."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        causal = jnp.tril(jnp.ones((t, t), bool))
        x = params["E_in"][tokens].astype(f32)
        for l, p in enumerate(params["layers"]):
            u = rms_norm(x, p["ln1"], cfg.eps)
            if cfg.is_attention(l):
                q = (u @ p["wq"].astype(f32)).reshape(t, cfg.heads, cfg.head_dim)
                k = (u @ p["wk"].astype(f32)).reshape(t, cfg.kv_heads, cfg.head_dim)
                v = (u @ p["wv"].astype(f32)).reshape(t, cfg.kv_heads, cfg.head_dim)
                k = jnp.repeat(k, cfg.heads // cfg.kv_heads, axis=1)
                v = jnp.repeat(v, cfg.heads // cfg.kv_heads, axis=1)
                s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(cfg.head_dim)
                s = jnp.where(causal[None], s, -jnp.inf)
                o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
                x = x + o.reshape(t, cfg.heads * cfg.head_dim) @ p["wo"].astype(f32)
            else:
                x = x + _reference_mamba(cfg, p, u)
            u = rms_norm(x, p["ln2"], cfg.eps)
            x = x + (jax.nn.silu(u @ p["wg"].astype(f32)) * (u @ p["wu"].astype(f32))) @ p["wd"].astype(f32)
        return rms_norm(x, params["final_norm"], cfg.eps)


def reference_generate(cfg: JambaConfig, params: dict, e_out, session, n_valid=None):
    """A basket by the plain form: session [n] int32 tokens, e_out [rows, H]
    (the tied embedding: row i is token i's) -> {"row": [B] catalog rows
    chosen, "logits": [B, rows] float32}. A full forward pass a position."""
    n_valid = int(e_out.shape[0]) if n_valid is None else int(n_valid)
    tokens = [int(t) for t in session]
    rows, all_logits = [], []
    for _ in range(cfg.basket):
        z = reference_forward(cfg, params, jnp.asarray(tokens, dtype=jnp.int32))[-1]
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(jnp.asarray(e_out, jnp.float32)[:n_valid] @ z)
        all_logits.append(logits)
        rows.append(int(np.argmax(logits)))
        tokens.append(rows[-1])
    return {"row": np.asarray(rows), "logits": np.stack(all_logits)}
