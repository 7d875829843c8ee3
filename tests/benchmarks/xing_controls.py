"""The controls of kind `xing-serving`: the program broken underneath in a way
a sound comparison has to see. Each takes `setattr(obj, name, value)`
(pytest's `monkeypatch.setattr`, or the builtin for a scratch run on the chip)
and patches the PROGRAM from outside; none is an option of it. Call before
the first request of a run: they clear jax's jit caches."""

from __future__ import annotations


def _retrace():
    import jax

    jax.clear_caches()


def maps_in_bfloat16(setattr_) -> None:
    """bfloat16 where float32 is stated: the maps' product takes the streams
    and phi at bfloat16's values, and every step of the Sinkhorn is rounded to
    bfloat16."""
    import jax.numpy as jnp

    from oryx_tpu.ops import xing

    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    sound_maps = xing._maps

    def maps(cfg, p, sub, x):
        phi = f"hc_{sub}_phi"
        return sound_maps(cfg, dict(p, **{phi: low(p[phi])}), sub, low(x))

    def sinkhorn(r, iters, eps):
        m = low(jnp.exp(r))
        for _ in range(iters):
            m = low(m / (jnp.sum(m, axis=0, keepdims=True) + eps))
            m = low(m / (jnp.sum(m, axis=1, keepdims=True) + eps))
        return m

    setattr_(xing, "_maps", maps)
    setattr_(xing, "sinkhorn", sinkhorn)
    _retrace()


def sinkhorn_at_5_iterations(setattr_) -> None:
    """The residual map projected by 5 alternating normalisations where the
    configuration states 20."""
    from oryx_tpu.ops import xing

    sound = xing.sinkhorn
    setattr_(xing, "sinkhorn", lambda r, iters, eps: sound(r, 5, eps))
    _retrace()


def streams_collapsed_to_one(setattr_) -> None:
    """One residual stream where four are stated: every sublayer reads stream
    0, writes its output into every stream with weight 1 and mixes nothing
    (M the identity), so the streams stay equal: a plain residual path."""
    import jax.numpy as jnp

    from oryx_tpu.ops import xing

    def maps(cfg, p, sub, x):
        n, rest = cfg.hc_mult, x.shape[1:-1]
        pre = jnp.zeros((n, *rest), jnp.float32).at[0].set(1.0)
        eye = jnp.eye(n, dtype=jnp.float32).reshape(n, n, *[1] * len(rest))
        return pre, jnp.ones((n, *rest), jnp.float32), jnp.broadcast_to(eye, (n, n, *rest))

    setattr_(xing, "_maps", maps)
    _retrace()


def latent_cache_in_8_bits(setattr_) -> None:
    """The latent and the rotated key rounded to 8 bits (float8 e4m3) where
    they are made, the nearest precision below the bfloat16 the configuration
    states for the cache: what a slot keeps and what a prefill attends over."""
    import jax.numpy as jnp

    from oryx_tpu.ops import xing

    sound = xing._latent

    def latent(cfg, p, u, pos):
        low = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
        c, k_rope = sound(cfg, p, u, pos)
        return low(c), low(k_rope)

    setattr_(xing, "_latent", latent)
    _retrace()


CONTROLS = {
    "maps_in_bfloat16": maps_in_bfloat16,
    "sinkhorn_at_5_iterations": sinkhorn_at_5_iterations,
    "streams_collapsed_to_one": streams_collapsed_to_one,
    "latent_cache_in_8_bits": latent_cache_in_8_bits,
}
