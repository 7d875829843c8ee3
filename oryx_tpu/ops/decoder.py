"""What the generating decoders behind the encoder seam (ops/seq.py
`Encoder`) share, written once: the layer pieces, the artifact's layout, the
basket a one-token step fills, and the seam's class. An architecture's file
(ops/sdar.py, jamba.py, joyai.py, trinity.py, xing.py) keeps its Config, its
layers, its slot state, its two jitted programs and its plain reference, and
imports what it shares from here (and a family's shared layer, such as
ops/mla.py's latent attention), never from another architecture.

Precision: weights in their stored dtype; the activations enter every
product in that dtype and accumulate in float32 (`dot`); the norms, the
rotation and the softmax are float32.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops.pallas_head import head_rows
from oryx_tpu.ops.seq import announced_tokens, catalog_head

BIAS_INIT = 0.1  # a router's selecting bias is drawn normal x this (a trained model carries one)


# -- layer pieces --------------------------------------------------------------

def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x [..., T, heads, d] float32, pos [..., T] -> rotated over the whole
    head (the rotate-half form: dimension i pairs with i + d/2)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv            # [..., T, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def dot(x, w):
    """x in w's dtype times w, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def swiglu(u, wg, wu, wd):
    return dot(jax.nn.silu(dot(u, wg)) * dot(u, wu), wd)


def masked_softmax(s, allowed):
    """Scores float32 -> probabilities over the last axis where `allowed`; a
    padding query may be allowed nothing: its row is zeros, not NaN."""
    s = jnp.where(allowed, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def attend(cfg, q, k, v, allowed, dt):
    """Grouped-query attention: q [R,T,heads,d], k/v [R,S,kv,d], allowed
    [R,T,S] bool -> [R,T,heads*d] float32; query head j reads key-value head
    j // (heads / kv_heads). Scores and softmax in float32; the products take
    q, k, the probabilities and v in `dt`."""
    f32 = jnp.float32
    r, t = q.shape[0], q.shape[1]
    group = cfg.heads // cfg.kv_heads
    qg = q.reshape(r, t, cfg.kv_heads, group, cfg.head_dim).astype(dt)
    s = jnp.einsum("rtgjd,rsgd->rgjts", qg, k.astype(dt), preferred_element_type=f32)
    prob = masked_softmax(s / math.sqrt(cfg.head_dim), allowed[:, None, None, :, :])
    o = jnp.einsum("rgjts,rsgd->rtgjd", prob.astype(dt), v.astype(dt), preferred_element_type=f32)
    return o.reshape(r, t, cfg.heads * cfg.head_dim)


# -- the artifact's layout -----------------------------------------------------

@partial(jax.jit, static_argnums=(1, 2))
def normal(key, shape, dtype):
    """A weight's seeded draw: standard normal x 0.02."""
    return (jax.random.normal(key, shape, dtype=jnp.float32) * 0.02).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2))
def router_bias(key, shape, dtype):
    """A router's selecting bias: normal x `BIAS_INIT` (zeros, a fresh model's,
    would make it invisible: at the published widths the scores spread by 0.2,
    so a bias of 0.1 changes which experts a token reaches and nothing drowns)."""
    return (jax.random.normal(key, shape, dtype=jnp.float32) * BIAS_INIT).astype(dtype)


class Layout(NamedTuple):
    """An architecture's artifact: beside the catalog ("E", the FactorStore's)
    the tensors "E_in", "final_norm" and, for layer l, "L<l>.<name>" of each of
    `layer_shapes(cfg, l)`. A layer's tensors are arrays of their own and never
    slices of a stacked one: the grouped kernel takes whole buffers, and a
    slice of 400 MB of experts would be copied for it at every step."""

    label: str                  # the model's name in what `params_of` refuses
    layer_shapes: Callable      # (cfg, layer) -> {name: shape}
    ones: tuple                 # the kinds (a name's last part) drawn as ones
    special: dict = {}          # kind -> its own initialiser (key, shape, dtype)
    float32: tuple = ()         # kinds kept in float32 whatever the weights' dtype

    def tensor_shapes(self, cfg) -> dict[str, tuple]:
        """Every tensor of an artifact by its name."""
        out = {"E_in": (cfg.vocab, cfg.hidden), "final_norm": (cfg.hidden,)}
        for l in range(cfg.layers):
            out.update({f"L{l}.{k}": v for k, v in self.layer_shapes(cfg, l).items()})
        return out

    def param_count(self, cfg) -> int:
        return sum(int(np.prod(v)) for v in self.tensor_shapes(cfg).values())

    def init_tensors(self, cfg, seed: int, dtype=jnp.bfloat16) -> dict:
        """An artifact's tensors from the seed, made on the device one at a
        time: the i-th of the sorted names draws from fold_in(seed, i); norm
        weights are 1, a special kind is its initialiser's, the rest `normal`."""
        out = {}
        for i, (name, shape) in enumerate(sorted(self.tensor_shapes(cfg).items())):
            kind = name.split(".")[-1]
            key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), i)
            dt = jnp.float32 if kind in self.float32 else dtype
            if kind == "final_norm" or kind in self.ones:
                out[name] = jnp.ones(shape, dtype=dt)
            else:
                out[name] = self.special.get(kind, normal)(key, shape, dt)
        return out

    def params_of(self, cfg, tensors: dict, dtype=None) -> dict:
        """An artifact's tensors -> the parameters the served and plain forms
        take: {"E_in", "final_norm", "layers": [{name: array}, ...]}, checked
        against the shapes the configuration states; cast to `dtype` where one
        is given, the float32 kinds to float32 always (an array already on the
        device in that dtype is taken as it is)."""
        for name, shape in self.tensor_shapes(cfg).items():
            if name not in tensors:
                raise ValueError(f"{self.label} model lacks tensor {name!r}")
            if tuple(np.shape(tensors[name])) != shape:
                raise ValueError(
                    f"{self.label} tensor {name!r} shaped {tuple(np.shape(tensors[name]))}, "
                    f"the extensions say {shape}"
                )

        def take(name):
            kind = name.split(".")[-1]
            return jnp.asarray(tensors[name], dtype=jnp.float32 if kind in self.float32 else dtype)

        return {
            "E_in": take("E_in"), "final_norm": take("final_norm"),
            "layers": [
                {k: take(f"L{l}.{k}") for k in self.layer_shapes(cfg, l)} for l in range(cfg.layers)
            ],
        }

    def init_params(self, cfg, seed: int, dtype=jnp.bfloat16) -> dict:
        return self.params_of(cfg, self.init_tensors(cfg, seed, dtype))


# -- the basket: what a request's one-token steps generate -----------------------
#
# None of these opens a named scope: each runs under its caller's.

def basket(cfg, slots: int, dtype) -> dict:
    """The basket's state for `slots` requests and the scratch slot. x_in: the
    next step's input embedding; z / row / step: for each position generated
    the hidden state, the view row chosen and the step that chose it."""
    s, b = slots + 1, cfg.basket
    return {
        "x_in": jnp.zeros((s, cfg.hidden), dtype),
        "z": jnp.zeros((s, b, cfg.hidden), jnp.float32),
        "row": jnp.full((s, b), -1, jnp.int32),
        "step": jnp.full((s, b), -1, jnp.int32),
    }


def reset(state: dict, slots, fed, **caches) -> dict:
    """A prefill's end: the layers' new slot arrays `caches` in, `fed` [P,H]
    the slots' first step's input, their baskets emptied."""
    return dict(
        state, **caches,
        x_in=state["x_in"].at[slots].set(fed.astype(state["x_in"].dtype)),
        z=state["z"].at[slots].set(jnp.zeros(state["z"].shape[1:], jnp.float32)),
        row=state["row"].at[slots].set(-1),
        step=state["step"].at[slots].set(-1),
    )


def view_head(z, view, n_valid):
    """`catalog_head` (ops/seq.py) for z [R, hidden]: cast to the view's dtype
    and padded to its lane width (a served view is lane-padded,
    ops/pallas_topk.py view_shape) -> (top, view row, confidence)."""
    zq = jnp.pad(z.astype(view.dtype), ((0, 0), (0, view.shape[1] - z.shape[1])))
    return catalog_head(zq, view, n_valid)


def fed_back(params: dict, row_token, arg):
    """An untied head's choice as the next input: view row `arg` [D] through
    `row_token` to its E_in row; a row with no input embedding yet (-1) feeds
    zeros."""
    token = row_token[arg]
    return jnp.where((token >= 0)[:, None], params["E_in"][jnp.maximum(token, 0)], 0)


def advance(state: dict, slots, step, live, z, arg, fed, **caches):
    """A step's end: each live row of `slots` [D] files its hidden state `z`
    [D,H] and view row `arg` [D] at its basket position `step` [D]; `fed`
    [D,H] is its next input, `caches` the layers' new slot arrays. A padding
    row (live False) files nothing. -> (state, out) with out = {"z": [D,B,H]
    float32 hidden of each position generated so far, "row": [D,B] the view
    rows chosen, "step": [D,B] the steps that chose them}: what a finished
    request needs, and every row's, so one fetch serves whichever finished."""
    here = (jnp.arange(state["z"].shape[1])[None, :] == step[:, None]) & live[:, None]   # [D,B]
    new_z = jnp.where(here[:, :, None], z[:, None, :], state["z"][slots])
    new_row = jnp.where(here, arg[:, None], state["row"][slots])
    new_step = jnp.where(here, step[:, None], state["step"][slots])
    state = dict(
        state, **caches,
        x_in=state["x_in"].at[slots].set(fed.astype(state["x_in"].dtype)),
        z=state["z"].at[slots].set(new_z),
        row=state["row"].at[slots].set(new_row),
        step=state["step"].at[slots].set(new_step),
    )
    return state, {"z": new_z, "row": new_row, "step": new_step}


# -- behind the encoder seam ---------------------------------------------------

class DecoderEncoder:
    """A generating decoder behind the seam (ops/seq.py `Encoder`): `prefill`
    runs a request's events but the last into its cache slot, `steps`
    one-token steps follow (step 0 feeds the last event, step i the item step
    i-1 chose) and the request hands the catalog scan `block` rows, one a
    basket position. Shapes are few and fixed: a prefill is `prefill_rows`
    sessions padded to a length bucket, a step is `step_rows` tokens.

    An architecture states `name`, `config` (its Config class, which reads an
    artifact's extensions), `layout`, `programs` (its jitted prefill and step),
    `slot_state` (its `init_state` and `state_bytes`) and `prefill_rows`, and
    overrides what its generation does otherwise."""

    own_input = True      # E_in: an input embedding apart from the catalog
    step_kind = "decode"
    step_tokens = 1       # a step runs one token a sequence
    # what a step feeds for a view row with no input embedding yet: the model
    # has no id to stand for one, so `row_token` says -1 and the step feeds zeros
    unknown_token = -1
    step_rows = 32
    feeds_last = True     # a session's last event is the first step's input

    def __init__(self, cfg, dtype=jnp.bfloat16):
        self.cfg = cfg
        self.dtype = dtype
        self.dim = cfg.hidden
        self.window = cfg.max_len  # the events of a session kept
        self.length_buckets = tuple(sorted({min(32, cfg.max_len), cfg.max_len}))

    @property
    def steps(self) -> int:
        return self.cfg.basket

    @property
    def block(self) -> int:
        return self.cfg.basket

    @classmethod
    def from_extensions(cls, ext):
        return cls(cls.config.from_extensions(ext), jnp.dtype(str(ext("dtype", "bfloat16"))))

    def load_params(self, tensors: dict) -> dict:
        """An artifact's tensors -> the parameters on the device in the dtype
        the artifact states, checked against the shapes its extensions state."""
        return self.layout.params_of(self.cfg, tensors, self.dtype)

    def device_params(self, params: dict) -> dict:
        return params  # `load_params` put them there

    def init_state(self, slots: int):
        return self.slot_state[0](self.cfg, slots, self.dtype)

    def state_bytes(self, slots: int) -> dict[str, int]:
        return self.slot_state[1](self.cfg, slots, jnp.dtype(self.dtype).itemsize)

    def prepare(self, seq_state, context_items):
        """The E_in rows of the newest `max_len` context items that have one
        (an item that arrived by UP since the model is skipped as context
        until the next generation)."""
        return announced_tokens(seq_state, context_items, self.cfg.max_len)

    def length(self, prepared) -> int:
        return int(prepared.shape[0]) - int(self.feeds_last)

    def pack(self, prepared: list, bucket: int, slots, scratch: int):
        tokens = np.zeros((self.prefill_rows, bucket), dtype=np.int32)
        lengths = np.zeros((self.prefill_rows,), dtype=np.int32)
        slot_of = np.full((self.prefill_rows,), scratch, dtype=np.int32)
        last = np.zeros((self.prefill_rows,), dtype=np.int32)
        for i, tok in enumerate(prepared):
            n = self.length(tok)
            tokens[i, :n] = tok[:n]
            lengths[i], last[i], slot_of[i] = n, tok[-1], slots[i]
        return (tokens, lengths, slot_of, last) if self.feeds_last else (tokens, lengths, slot_of)

    # host operands ride the jitted call (the seam's docstring, ops/seq.py)
    def prefill(self, params, state, *packed):
        state, hidden, counts = self.programs[0](self.cfg, params, state, *packed)
        return state, hidden, {"counts": counts}

    def step(self, params, state, head, slots, lengths, live, step):
        view, n_valid, row_token = head
        state, out = self.call_step(params, state, view, np.int32(n_valid), row_token, (slots, lengths, live, step))
        out["head_rows"] = head_rows(view.shape[0], int(n_valid))
        return state, out

    def call_step(self, params, state, view, n_valid, row_token, rows):
        return self.programs[1](self.cfg, params, state, view, n_valid, row_token, *rows)
