"""The five controls of kind `joyai-serving`: the program broken underneath
in a way a sound comparison has to see. Each takes `setattr(obj, name,
value)` (pytest's `monkeypatch.setattr`, or the builtin for a scratch run on
the chip) and patches the PROGRAM from outside; none is an option of it.
Call before the first request of a run: they clear jax's jit caches."""

from __future__ import annotations


def _retrace():
    import jax

    jax.clear_caches()


def latent_cache_in_8_bits(setattr_) -> None:
    """The latent and the rotated key rounded to 8 bits (float8 e4m3) where
    they are made, the nearest precision below the bfloat16 the configuration
    states: what a slot keeps and what a prefill attends over."""
    import jax.numpy as jnp

    from oryx_tpu.ops import joyai

    sound = joyai._latent

    def latent(cfg, p, u, pos):
        low = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
        c, k_rope = sound(cfg, p, u, pos)
        return low(c), low(k_rope)

    setattr_(joyai, "_latent", latent)
    _retrace()


def cached_key_not_rotated(setattr_) -> None:
    """The shared key goes into the cache (and a prefill's scores) as W_kva
    left it, never turned by its position; the queries still are."""
    import jax.numpy as jnp

    from oryx_tpu.ops import joyai

    sound = joyai._latent

    def latent(cfg, p, u, pos):
        return sound(cfg, p, u, jnp.zeros_like(pos))

    setattr_(joyai, "_latent", latent)
    _retrace()


def bias_added_to_the_weights(setattr_) -> None:
    """The correction bias weighs as well as selects: the chosen experts'
    weights are their s + b, normalised and scaled."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import moe

    sound = moe.route

    def route(u, wr, k, scoring="softmax", bias=None, scale=1.0):
        if bias is None:
            return sound(u, wr, k, scoring, bias, scale)
        logits = jnp.dot(u.astype(jnp.float32), wr.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        w, e = jax.lax.top_k(jax.nn.sigmoid(logits) + bias.astype(jnp.float32), k)
        return scale * w / jnp.sum(w, axis=-1, keepdims=True), e.astype(jnp.int32)

    setattr_(moe, "route", route)
    _retrace()


def shared_expert_left_out(setattr_) -> None:
    """An expert layer is its routed experts alone."""
    import jax.numpy as jnp

    from oryx_tpu.ops import joyai

    setattr_(joyai, "_shared_expert", lambda p, u: jnp.zeros(u.shape, jnp.float32))
    _retrace()


def routed_scale_left_out(setattr_) -> None:
    """The chosen experts' weights sum to 1, not to routed_scaling_factor."""
    from oryx_tpu.ops import moe

    sound = moe.route

    def route(u, wr, k, scoring="softmax", bias=None, scale=1.0):
        return sound(u, wr, k, scoring, bias, 1.0)

    setattr_(moe, "route", route)
    _retrace()


CONTROLS = {
    "latent_cache_in_8_bits": latent_cache_in_8_bits,
    "cached_key_not_rotated": cached_key_not_rotated,
    "bias_added_to_the_weights": bias_added_to_the_weights,
    "shared_expert_left_out": shared_expert_left_out,
    "routed_scale_left_out": routed_scale_left_out,
}
