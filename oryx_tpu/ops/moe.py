"""A sparse mixture-of-experts feed-forward layer on one chip.

    softmax:  p = softmax(u Wr) over the E experts, in float32; the k
              largest, their weights divided by their sum
    sigmoid:  s = sigmoid(u Wr); the k largest of s + b (b a learned
              correction bias that selects and never weighs); their s
              divided by their sum, times a scale
    y = sum_e w_e * (silu(u Wg_e) * (u Wu_e)) Wd_e

Which rule is a property of the model (its encoder's configuration states
it), never an option of the user; everything after the routing is one path.

No token is dropped, whatever the load of an expert: the (token, expert)
pairs are sorted by expert and each expert multiplies the contiguous rows
that chose it (a grouped product), so an expert nobody chose costs no
FLOP and, on the grouped kernel, no read of its weights.

The layer may be told which experts it HOLDS (`held`: the first and how
many, the chip's share of a layer whose experts lie on several chips). The
router still scores, selects and normalises over every expert of the
model; the layer computes the part of the result its own experts give, and
a pair whose expert is held elsewhere goes to no group, exactly as a
padding token's does. Nothing stands in for the absent chips or their
exchange: what they would have added is left out.

`moe_apply` also counts, on the device and in the step's own dispatch,
what the serving counters report (serving/stepper.py, oryx_moe_*): the
pairs that reached an expert here, the experts touched, the busiest
expert's tokens and, for a layer told its share, the pairs sent elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# rows of the sorted (token, expert) pairs are padded up to a multiple of
# this: the grouped kernel's row tile
ROW_TILE = 128


SCORINGS = ("softmax", "sigmoid")


def route(u: jax.Array, wr: jax.Array, k: int, scoring: str = "softmax", bias=None, scale: float = 1.0):
    """Router: (weights [N,k] float32 summing to `scale`, experts [N,k]
    int32). Logits and scores in float32 at `highest` precision (a default
    f32 product on the TPU is one bf16 pass). `softmax`: the k largest
    probabilities. `sigmoid`: the k experts with the largest s + `bias` [E],
    weighted by their s alone."""
    if scoring not in SCORINGS:
        raise ValueError(f"unknown router scoring {scoring!r}")
    logits = jnp.dot(
        u.astype(jnp.float32), wr.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        w, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        s = jax.nn.sigmoid(logits)
        # a model with expert groups would keep the best groups' experts
        # alone here; with one group that limit is the identity
        _, e = jax.lax.top_k(s if bias is None else s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, e, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # no multiply at scale 1: the softmax model's program stays the one it was
    return w * scale if scale != 1.0 else w, e.astype(jnp.int32)


def _grouped(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array) -> jax.Array:
    """lhs[rows of group g] @ rhs[g] for every group, float32 out. On the
    TPU the Pallas grouped matmul (it visits only the row tiles of groups
    that hold rows); elsewhere XLA's ragged dot."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        k, n = rhs.shape[1], rhs.shape[2]
        return gmm(
            lhs, rhs, sizes, preferred_element_type=jnp.float32,
            tiling=(ROW_TILE, k, min(n, 1024)),
        )
    return jax.lax.ragged_dot(
        lhs, rhs, sizes, preferred_element_type=jnp.float32
    )


def moe_apply(
    u: jax.Array, wr: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
    k: int, live: jax.Array | None = None, held: tuple[int, int] | None = None, **rule,
):
    """u [N,H] float32 (normalised) -> (y [N,H] float32, counts int32[3],
    or int32[4] for a layer told its share). `rule`: the model's routing
    rule where it is not the softmax one (`route`'s `scoring`, `bias`,
    `scale`).

    wr [H,E], wg/wu [E,H,F], wd [E,F,H] in the weights' dtype; the
    activations enter each product in that dtype and accumulate in
    float32. `live` [N] bool marks the real tokens of a padded step: a
    padding token's pairs are sent to no expert (they sort behind every
    group) and count nowhere. `held` = (first, count): wg/wu/wd are those
    `count` experts' alone ([count, ...]) while wr keeps every expert's
    column; a real token's pair for an expert outside them sorts behind
    every group too, reads no weight and adds nothing. counts = (pairs
    that reached an expert here, experts touched, the busiest expert's
    pairs[, pairs sent to experts held elsewhere])."""
    n, h = u.shape
    w, e = route(u, wr, k, **rule)
    if live is None:
        live = jnp.ones((n,), dtype=bool)
    here = live[:, None]
    if held is None:
        n_experts = wr.shape[1]
    else:
        first, n_experts = held
        e = e - first
        here = here & (e >= 0) & (e < n_experts)
    pairs = n * k
    flat_e = jnp.where(here, e, n_experts).reshape(pairs)
    order = jnp.argsort(flat_e, stable=True)
    token = (order // k).astype(jnp.int32)
    sizes = jnp.bincount(flat_e, length=n_experts + 1)[:n_experts].astype(jnp.int32)
    rows = -(-pairs // ROW_TILE) * ROW_TILE
    x = u.astype(wg.dtype)[token]
    if rows > pairs:
        x = jnp.pad(x, ((0, rows - pairs), (0, 0)))
    g = _grouped(x, wg, sizes)
    up = _grouped(x, wu, sizes)
    mid = (jax.nn.silu(g) * up).astype(wd.dtype)
    out = _grouped(mid, wd, sizes)[:pairs]
    # back to token order: pair j of token i sits at sorted row inv[i*k+j].
    # A grouped product writes no row past its last group (the padding
    # tokens' pairs): whatever those hold is dropped, not weighted by zero
    inv = jnp.argsort(order).astype(jnp.int32)
    out = out[inv].reshape(n, k, h)
    w = jnp.where(here, w, 0.0)
    y = jnp.sum(jnp.where(w[:, :, None] > 0, out, 0.0) * w[:, :, None], axis=1)
    counts = [jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)]
    if held is not None:
        counts.append(jnp.sum(live[:, None] & ~here))
    return y, jnp.stack(counts).astype(jnp.int32)


def moe_reference(
    u, wr, wg, wu, wd, k: int, scoring: str = "softmax", bias=None, scale: float = 1.0,
    held: tuple[int, int] | None = None,
):
    """The plain form, float32 throughout: every expert in turn on every
    token, weighted by the token's routing weight for it (zero unless it
    is one of the token's k). `held` = (first, count): wg/wu/wd are those
    experts' alone and the others' part of the result is left out; the
    routing is over every expert all the same. For tests and the plain
    reference; run it under `jax.default_matmul_precision("highest")`."""
    f32 = jnp.float32
    u = u.astype(f32)
    if scoring == "softmax":
        p = jax.nn.softmax(u @ wr.astype(f32), axis=-1)
        w, e = jax.lax.top_k(p, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:
        p = jax.nn.sigmoid(u @ wr.astype(f32))
        _, e = jax.lax.top_k(p + (0.0 if bias is None else bias.astype(f32)), k)
        w = jnp.take_along_axis(p, e, axis=-1)
        w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
    dense_w = jnp.zeros(p.shape, f32).at[jnp.arange(u.shape[0])[:, None], e].add(w)
    if held is not None:
        dense_w = dense_w[:, held[0]:held[0] + held[1]]

    def one_expert(acc, xs):
        g_w, u_w, d_w, col = xs
        y = (jax.nn.silu(u @ g_w.astype(f32)) * (u @ u_w.astype(f32))) @ d_w.astype(f32)
        return acc + col[:, None] * y, None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, f32), (wg, wu, wd, dense_w.T))
    return acc
