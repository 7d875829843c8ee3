"""Next-item sequence model kernels: a compact GRU over item embeddings.

The fourth packaged app's device math (ROADMAP item 4). One recurrent
cell, embedding-tied output — logits for "which item comes next" are
``h @ E.T`` over the SAME item-embedding matrix the inputs gather from —
so the serving layer scores the whole catalog with exactly the top-k
matmul shape the ALS path already dispatches through the micro-batcher
(serving/batcher.py): the hidden state is the "user vector", E is the
"item matrix", and score modes / shedding / perfstats all come for free.

Training is minibatched softmax cross-entropy with an Adagrad step,
``lax.scan`` over the window inside one jitted step function, and the
same prediction-convergence early stop discipline ALS warm starts use
(ml/update.py lineage): relative change of sampled next-item scores, not
parameter norms — embeddings keep drifting along directions the
predictions no longer care about.
"""

from __future__ import annotations

import importlib
import math
from typing import NamedTuple, Protocol

import numpy as np

import jax
import jax.numpy as jnp

from oryx_tpu.ops.pallas_head import head_pallas

# Non-embedding GRU parameter names, in artifact/tensor order. The
# embedding matrix "E" rides separately: it is also the serving catalog
# (streamed row-by-row as UP messages, like ALS factor rows).
GRU_PARAM_NAMES = ("Wx", "Wh", "b")


class GruModel(NamedTuple):
    """A trained next-item model: item embeddings + recurrent weights."""

    e: np.ndarray          # [V, d] item embeddings (also the output table)
    params: dict           # Wx [d,3d], Wh [d,3d], b [3d]
    item_ids: list         # [V] row-aligned item id strings


def init_gru_params(key, dim: int) -> dict:
    """Recurrent weights at 1/sqrt(d) scale; gate order is (z, r, n)."""
    kx, kh = jax.random.split(key)
    s = 1.0 / math.sqrt(dim)
    return {
        "Wx": np.array(jax.random.normal(kx, (dim, 3 * dim)) * s, dtype=np.float32),
        "Wh": np.array(jax.random.normal(kh, (dim, 3 * dim)) * s, dtype=np.float32),
        "b": np.zeros(3 * dim, dtype=np.float32),
    }


def _gru_cell(params, x, h):
    """One GRU step: x [B,d] inputs, h [B,d] state -> new state."""
    d = h.shape[-1]
    gx = x @ params["Wx"] + params["b"]
    gh = h @ params["Wh"]
    z = jax.nn.sigmoid(gx[:, :d] + gh[:, :d])
    r = jax.nn.sigmoid(gx[:, d : 2 * d] + gh[:, d : 2 * d])
    n = jnp.tanh(gx[:, 2 * d :] + r * gh[:, 2 * d :])
    return (1.0 - z) * n + z * h


def _encode_embedded(params, xs, mask):
    """Scan the cell over time: xs [B,L,d] embedded inputs, mask [B,L]
    (1 = real event, 0 = left padding); returns final h [B,d]. Masked
    steps carry the state through unchanged, so short sessions and full
    windows share one compiled program."""

    def step(h, xm):
        x, m = xm
        h2 = _gru_cell(params, x, h)
        return jnp.where(m[:, None] > 0, h2, h), None

    h0 = jnp.zeros((xs.shape[0], xs.shape[2]), dtype=xs.dtype)
    h, _ = jax.lax.scan(step, h0, (jnp.swapaxes(xs, 0, 1), mask.T))
    return h


@jax.jit
def encode_vectors(params, xs, mask):
    """Jitted session encoder over pre-gathered embedding vectors —
    the serving path's form: the request carries item ids, the caller
    gathers their rows from the factor store, no vocab table needed."""
    return _encode_embedded(params, xs, mask)


def _encode_idx(params, e, idx, mask):
    return _encode_embedded(params, e[idx], mask)


def _nll(weights, idx, mask, targets):
    """Mean next-item negative log-likelihood of a minibatch under the
    embedding-tied softmax (logits = h @ E.T)."""
    e, params = weights["E"], weights
    h = _encode_idx(params, e, idx, mask)
    logits = h @ e.T
    return -jnp.mean(
        jax.nn.log_softmax(logits, axis=-1)[jnp.arange(idx.shape[0]), targets]
    )


@jax.jit
def _adagrad_step(weights, accum, idx, mask, targets, lr):
    """One minibatch step; returns (weights, accum, loss). Adagrad keeps
    the per-parameter scale adaptive with only the accumulator as state
    — which train_gru seeds at 0 for cold starts and at 1.0 for warm
    resumes (see the accum_0 comment there: a zero restart takes
    lr-sized sign steps that re-shock a converged model)."""
    loss, grads = jax.value_and_grad(_nll)(weights, idx, mask, targets)
    new_w, new_a = {}, {}
    for k in weights:
        g = grads[k]
        a = accum[k] + g * g
        new_w[k] = weights[k] - lr * g / jnp.sqrt(a + 1e-8)
        new_a[k] = a
    return new_w, new_a, loss


@jax.jit
def _sampled_scores(weights, idx, mask, targets):
    """Predicted scores of the true next items on a fixed probe sample —
    the convergence signal (prediction space, not parameter space)."""
    h = _encode_idx(weights, weights["E"], idx, mask)
    return jnp.sum(h * weights["E"][targets], axis=-1)


def train_gru(
    contexts: np.ndarray,
    mask: np.ndarray,
    targets: np.ndarray,
    n_items: int,
    dim: int,
    item_ids,
    epochs: int = 30,
    lr: float = 0.5,
    batch: int = 1024,
    seed_key=None,
    resume_e: np.ndarray | None = None,
    resume_params: dict | None = None,
    tol: float = 0.0,
    min_epochs: int = 2,
    check_every: int = 2,
    probe: int = 512,
) -> tuple[GruModel, int]:
    """Train the next-item GRU; returns (model, epochs actually run).

    contexts [N,L] int32 item rows (left-padded), mask [N,L], targets [N]
    item rows. resume_e/resume_params warm-start from the previous
    generation (ids already aligned by the caller via ops/als.py
    align_factors); tol > 0 enables the prediction-convergence early stop
    checked every ``check_every`` epochs after ``min_epochs``.
    """
    n = int(contexts.shape[0])
    if n == 0 or n_items == 0:
        raise ValueError("no training examples")
    key = seed_key
    if key is None:
        from oryx_tpu.common.rng import RandomManager

        key = RandomManager.get_key()
    k_e, k_p, k_s = jax.random.split(key, 3)
    if resume_e is not None and resume_e.shape == (n_items, dim):
        e0 = np.asarray(resume_e, dtype=np.float32)
    else:
        e0 = np.array(
            jax.random.normal(k_e, (n_items, dim)) * (1.0 / math.sqrt(dim)),
            dtype=np.float32,
        )
    params = (
        {k: np.asarray(v, dtype=np.float32) for k, v in resume_params.items()}
        if resume_params is not None
        and all(k in resume_params for k in GRU_PARAM_NAMES)
        and np.shape(resume_params.get("Wh")) == (dim, 3 * dim)
        else init_gru_params(k_p, dim)
    )
    weights = {"E": jnp.asarray(e0), **{k: jnp.asarray(params[k]) for k in GRU_PARAM_NAMES}}
    # Warm resumes seed the Adagrad accumulator at 1.0 instead of 0: a
    # zero accumulator makes every first step lr-sized REGARDLESS of the
    # gradient (sign steps), which re-shocks a converged model for
    # several epochs before the prediction-convergence stop can fire;
    # with the floor, steps near convergence are ~lr·g — small where the
    # model is already right, full-sized where the new window disagrees.
    accum_0 = 1.0 if resume_e is not None and resume_params is not None else 0.0
    accum = {k: jnp.full_like(v, accum_0) for k, v in weights.items()}

    batch = max(1, min(batch, n))
    # fixed probe sample for the convergence signal (deterministic)
    rng = np.random.default_rng(int(jax.random.randint(k_s, (), 0, 1 << 30)))
    probe_rows = rng.choice(n, size=min(probe, n), replace=False)
    p_idx = jnp.asarray(contexts[probe_rows])
    p_mask = jnp.asarray(mask[probe_rows])
    p_tgt = jnp.asarray(targets[probe_rows])

    lr_j = jnp.float32(lr)
    prev_scores = None
    ran = 0
    for epoch in range(max(1, int(epochs))):
        order = rng.permutation(n)
        for lo in range(0, n, batch):
            rows = order[lo : lo + batch]
            if len(rows) < batch:  # pad to the compiled batch shape
                rows = np.concatenate([rows, order[: batch - len(rows)]])
            weights, accum, _ = _adagrad_step(
                weights, accum,
                jnp.asarray(contexts[rows]), jnp.asarray(mask[rows]),
                jnp.asarray(targets[rows]), lr_j,
            )
        ran = epoch + 1
        if tol > 0 and ran >= min_epochs and ran % max(1, check_every) == 0:
            scores = np.asarray(_sampled_scores(weights, p_idx, p_mask, p_tgt))
            if prev_scores is not None:
                denom = float(np.linalg.norm(prev_scores)) or 1.0
                rel = float(np.linalg.norm(scores - prev_scores)) / denom
                if rel < tol:
                    break
            prev_scores = scores
    model = GruModel(
        e=np.asarray(weights["E"], dtype=np.float32),
        params={k: np.asarray(weights[k], dtype=np.float32) for k in GRU_PARAM_NAMES},
        item_ids=list(item_ids),
    )
    return model, ran


def next_item_hit_rate(
    e: np.ndarray,
    params: dict,
    contexts: np.ndarray,
    mask: np.ndarray,
    targets: np.ndarray,
    k: int = 10,
    chunk: int = 2048,
) -> float:
    """Mean hit-rate@k over next-item examples: the fraction whose true
    next item lands in the model's top-k — the ONE definition the batch
    eval and the quality gate share. NaN when
    there is nothing to evaluate."""
    n = int(contexts.shape[0])
    if n == 0:
        return float("nan")
    e_j = jnp.asarray(np.asarray(e, dtype=np.float32))
    jp = {name: jnp.asarray(np.asarray(params[name], dtype=np.float32))
          for name in GRU_PARAM_NAMES}
    k = min(k, int(e.shape[0]))
    hits = 0
    gru = GruEncoder(int(e.shape[1]), int(contexts.shape[1]))
    for lo in range(0, n, chunk):
        _, h, _ = gru.prefill(
            jp, None, e_j[jnp.asarray(contexts[lo : lo + chunk])],
            jnp.asarray(mask[lo : lo + chunk]),
        )
        logits = np.asarray(h @ e_j.T)
        top = np.argpartition(-logits, k - 1, axis=1)[:, :k]
        hits += int((top == targets[lo : lo + chunk, None]).any(axis=1).sum())
    return hits / n


def catalog_head(z, view, n_valid):
    """The head of an encoder that generates, over the served view: z [R, F]
    (in the view's dtype, lane-padded as the view is, ops/pallas_topk.py
    view_shape) x view [rows, F] -> for each row of z the largest logit over
    the view's first `n_valid` rows, its view row (int32; the first such row
    on ties, as jnp.argmax) and its softmax probability over those rows (the
    confidence: SDAR's step picks its position by it, the decoders drop it).
    Logits accumulate in float32.

    What it reads and writes (PR 46; ops/pallas_head.py): the view in blocks
    of HEAD_BLOCK_ROWS (1,024) rows, only the ceil(n_valid / 1,024) blocks
    that hold a valid row; `n_valid` is traced (scalar prefetch), so every
    catalog size a view holds shares one program, and behind the last live
    block no copy starts. Rows at or past n_valid inside it are never
    selected and never enter the sum. No logit is written to HBM: each block
    is reduced as it arrives into a running maximum, its first row and a
    running sum of exp(logit - maximum).

    Valid view rows, one v5e (PR 46; ms a head, 40 inside one jitted loop,
    median of five; the parent's masked dense product -> this, at the cell's
    n_valid | with the view filled to capacity; block 1,024; every argmax
    and top the parent's, the confidence within 1e-4 relative):

        R x rows x F, n_valid                parent      valid rows | capacity
        128 x 196,608 x 2,048, 151,935 conf   1.196  ->  0.869 | 1.105
         32 x 229,376 x 3,072, 200,192        1.948  ->  1.668 | 1.900
         32 x 163,840 x 2,048, 129,280        0.937  ->  0.744 | 0.925
         32 x  81,920 x 2,560,  65,536        0.610  ->  0.482 | 0.592

    Blocks of 512 to 4,096 rows read within 3 % of each other; the view as
    the dot's left operand read the same; the sum costs nothing measurable
    (0.869 with it, 0.871 without at the first shape)."""
    return head_pallas(z, view, n_valid, interpret=jax.default_backend() != "tpu")


# -- the encoder seam ---------------------------------------------------------

class Encoder(Protocol):
    """What turns a session into the vector(s) the catalog scan ranks. The
    three tiers reach it through this seam and never call a model's functions
    directly; which encoder a model has is written in its artifact (extension
    "encoder", absent: "gru"; `encoder_for`), never in a config key. The GRU
    answers after its prefill; a generating decoder (ops/decoder.py
    `DecoderEncoder`) runs `steps` device steps after it, and only an encoder
    with steps has the members marked (steps).

    Host operands ride the jitted call: `pack`'s arrays and `step`'s `slots,
    lengths, live, step` are HOST arrays (numpy, fresh every dispatch), and
    they and the head's valid rows (an `np.int32`) are handed to the jitted
    program as they are: the call's own argument path transfers them. A
    wrapper uploads nothing before it (no `jnp.asarray`, `jnp.int32` or
    `device_put`: each is a trip through Python and a transfer of its own,
    0.27-0.38 ms on the serving host where the call's own transfer of a numpy
    operand is 0.14); a jitted program takes device arrays too.

    The GRU's own: `train(...)` / `loss` (the batch layer trains it) and
    `encode_host` (the speed layer's fold)."""

    name: str
    dim: int                   # the hidden state's width
    window: int                # the newest context items a session keeps
    own_input: bool            # an input embedding apart from the catalog, row-aligned with the announced ids
    steps: int                 # device steps after the prefill (0: the prefill's hidden state is the answer)
    block: int                 # rows a request hands the scan
    length_buckets: tuple      # the padded context lengths a prefill compiles
    prefill_rows: int          # rows of a prefill dispatch
    step_rows: int             # rows of a step dispatch
    step_kind: str             # (steps) the label a step dispatch counts under
    step_tokens: int           # (steps) the tokens a row of a step runs: a block's positions, or one
    unknown_token: int | None  # (steps) what a step feeds for a view row with no input embedding yet (None: the row)

    def load_params(self, tensors: dict) -> dict:
        """An artifact's tensors -> parameters, checked."""

    def device_params(self, params: dict) -> dict:
        """The parameters as the device calls take them."""

    def init_state(self, slots: int):
        """Per-request device state for `slots` requests (None: the encoder keeps none)."""

    def state_bytes(self, slots: int) -> dict[str, int]:
        """(steps) {kind of state: bytes} of the slots' state."""

    def prepare(self, seq_state, context_items):
        """One request's host input, or None when no context item is known to the model."""

    def length(self, prepared) -> int:
        """Its context length (picks the bucket)."""

    def pack(self, prepared: list, bucket: int, slots, scratch: int) -> tuple:
        """The host arrays of one prefill."""

    def prefill(self, params, state, *packed):
        """-> (state, hidden [rows, d], the tallies the dispatch made on the
        device, by name: serving/stepper.py `TALLIES`; {} where it makes
        none)."""

    def step(self, params, state, head, slots, lengths, live, step):
        """(steps) -> (state, out); `out["head_rows"]`: (rows walked, rows
        skipped) of the step's catalog head, host numbers; the tallies of
        serving/stepper.py `TALLIES` that the model makes, as `prefill`'s."""


class GruEncoder:
    """The GRU behind the seam: its state is h, and it answers after
    `prefill` (no steps). `prefill` is `encode_vectors`, unchanged."""

    name = "gru"
    own_input = False  # its input embedding IS the catalog row
    steps = 0
    block = 1
    prefill_rows = 8
    step_rows = 0

    def __init__(self, dim: int, window: int):
        self.dim = int(dim)
        self.window = int(window)
        self.length_buckets = (self.window,)

    @classmethod
    def from_extensions(cls, ext) -> "GruEncoder":
        return cls(int(ext("dim")), int(ext("window", 8)))

    def state_spec(self) -> dict:
        return {"h": ((self.dim,), np.float32)}

    def load_params(self, tensors: dict) -> dict:
        """An artifact's tensors -> the host parameters (float32), checked
        against the width the artifact states."""
        params = {
            name: np.asarray(tensors[name], dtype=np.float32)
            for name in GRU_PARAM_NAMES if name in tensors
        }
        if len(params) != len(GRU_PARAM_NAMES):
            raise ValueError("seq MODEL message lacks recurrent weight tensors")
        if np.shape(params["Wh"]) != (self.dim, 3 * self.dim):
            raise ValueError(
                f"seq recurrent weights shaped {np.shape(params['Wh'])} "
                f"inconsistent with dim={self.dim}"
            )
        return params

    def device_params(self, host: dict) -> dict:
        return {k: jnp.asarray(np.asarray(host[k], dtype=np.float32)) for k in GRU_PARAM_NAMES}

    def init_state(self, slots: int):
        return None

    def prepare(self, seq_state, context_items):
        """(vectors [window, d] left-padded, mask [window]) of the newest
        `window` context items, gathered from the item store."""
        ctx = list(context_items)[-self.window:]
        if not ctx:
            return None
        vecs, have = seq_state.items.get_many(ctx)
        if not have.any():
            return None
        mat = np.zeros((self.window, self.dim), dtype=np.float32)
        mask = np.zeros((self.window,), dtype=np.float32)
        mat[self.window - len(ctx):] = vecs
        mask[self.window - len(ctx):] = have.astype(np.float32)
        return mat, mask

    def length(self, prepared) -> int:
        return self.window

    def pack(self, prepared: list, bucket: int, slots, scratch: int):
        mats = np.zeros((self.prefill_rows, self.window, self.dim), dtype=np.float32)
        masks = np.zeros((self.prefill_rows, self.window), dtype=np.float32)
        for i, (mat, mask) in enumerate(prepared):
            mats[i], masks[i] = mat, mask
        return mats, masks

    def prefill(self, params, state, mats, masks):
        return state, encode_vectors(params, mats, masks), {}

    def step(self, params, state, head, slots, lengths, live, step):
        raise NotImplementedError("the GRU answers after prefill")

    loss = staticmethod(_nll)

    def train(self, *args, **kw):
        return train_gru(*args, **kw)

    def encode_host(self, params: dict, item_vectors: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Host-friendly prefill: pre-gathered [B,L,d] item vectors (zeros
        on padded steps) -> [B,d] hidden states."""
        return np.asarray(
            encode_vectors(
                self.device_params(params),
                jnp.asarray(np.asarray(item_vectors, dtype=np.float32)),
                jnp.asarray(np.asarray(mask, dtype=np.float32)),
            )
        )


def announced_tokens(seq_state, context_items, max_len: int):
    """The input-embedding rows of the newest `max_len` context items that
    have one (`SeqState.token_of`), or None where none has: an item the model
    was not announced with (it arrived by UP since) has a head row and no
    input embedding, and is skipped as context until the next generation."""
    token_of = seq_state.token_of
    tokens = [token_of[i] for i in context_items if i in token_of]
    return np.asarray(tokens[-max_len:], dtype=np.int32) if tokens else None


# an artifact's "encoder" -> (module, class), imported when a model names it
ENCODERS = {
    "gru": (__name__, "GruEncoder"),
    "sdar": ("oryx_tpu.ops.sdar", "SdarEncoder"),
    "jamba": ("oryx_tpu.ops.jamba", "JambaEncoder"),
    "joyai": ("oryx_tpu.ops.joyai", "JoyaiEncoder"),
    "trinity": ("oryx_tpu.ops.trinity", "TrinityEncoder"),
    "xing": ("oryx_tpu.ops.xing", "XingEncoder"),
}


def encoder_for(name: str, ext) -> Encoder:
    """The encoder an artifact names; `ext(key, default)` reads the
    artifact's extensions."""
    if name not in ENCODERS:
        raise ValueError(f"unknown seq encoder {name!r}")
    module, cls = ENCODERS[name]
    return getattr(importlib.import_module(module), cls).from_extensions(ext)
