"""The three controls of kind `seq-serving`: the program broken underneath
in a way a sound comparison has to see. Each takes `setattr(obj, name,
value)` (pytest's `monkeypatch.setattr`, or the builtin for a scratch run on
the chip) and patches the PROGRAM from outside; none is an option of it.
Call before the first request of a run: they clear jax's jit caches."""

from __future__ import annotations

import numpy as np


def _retrace():
    import jax

    jax.clear_caches()


def causal_block(setattr_) -> None:
    """A causal mask INSIDE the block: a block position sees the prefix and
    only the block positions up to itself."""
    import jax.numpy as jnp

    from oryx_tpu.ops import sdar

    sound = sdar._attend

    def attend(cfg, q, k, v, allowed, dt):
        b = cfg.block_length
        if allowed.shape[-2] == b and allowed.shape[-1] == cfg.max_len + b:
            inside = jnp.tril(jnp.ones((b, b), bool))
            allowed = allowed & jnp.concatenate(
                [jnp.ones((b, cfg.max_len), bool), inside], axis=-1
            )[None]
        return sound(cfg, q, k, v, allowed, dt)

    setattr_(sdar, "_attend", attend)
    _retrace()


def one_expert_short(setattr_) -> None:
    """k - 1 of the k experts a token: the least of its k is left out and
    the rest renormalised (its pair still sorts to that expert, weighted 0)."""
    import jax.numpy as jnp

    from oryx_tpu.ops import moe

    sound = moe.route

    def route(u, wr, k):
        w, e = sound(u, wr, k)  # descending: the last is the least
        w = w.at[:, -1].set(0.0)
        return w / jnp.sum(w, axis=-1, keepdims=True), e

    setattr_(moe, "route", route)
    _retrace()


def int8_experts(setattr_) -> None:
    """The experts' matrices enter their products through int8 (a scale per
    expert and output channel) and back to the stated dtype: the nearest
    precision below the configuration's. In the served products alone: the
    model's parameters, which the reference reads, stay as they are."""
    import jax.numpy as jnp

    from oryx_tpu.ops import moe

    sound = moe._grouped

    def grouped(lhs, rhs, sizes):
        x = rhs.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return sound(lhs, (q.astype(jnp.float32) * scale).astype(rhs.dtype), sizes)

    setattr_(moe, "_grouped", grouped)
    _retrace()


CONTROLS = {
    "int8_experts": int8_experts,
    "one_expert_short": one_expert_short,
    "causal_block": causal_block,
}
