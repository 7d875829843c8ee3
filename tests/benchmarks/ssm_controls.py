"""The three controls of kind `ssm-serving`: the program broken underneath
in a way a sound comparison has to see. Each takes `setattr(obj, name,
value)` (pytest's `monkeypatch.setattr`, or the builtin for a scratch run on
the chip) and patches the PROGRAM from outside; none is an option of it.
Call before the first request of a run: they clear jax's jit caches."""

from __future__ import annotations


def _retrace():
    import jax

    jax.clear_caches()


def state_in_bfloat16(setattr_) -> None:
    """The recurrence's state kept in bfloat16, the nearest precision below
    the float32 the configuration states: every position's update is rounded
    to it (a sequential walk in place of the kernel, the same arithmetic
    otherwise), and so is what a slot keeps between dispatches."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    def scan(x, dt, b, c, a, d, h0, lengths):
        real = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :] < lengths[:, None]
        dt = jnp.where(real[:, :, None], dt, 0.0)
        low = lambda h: h.astype(jnp.bfloat16)  # noqa: E731

        def one(h, xs):
            x_t, dt_t, b_t, c_t = xs
            h = jnp.exp(dt_t[:, None, :] * a[None]) * h.astype(jnp.float32)
            h = low(h + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
            return h, jnp.sum(h.astype(jnp.float32) * c_t[:, :, None], axis=1) + d * x_t

        h, y = jax.lax.scan(one, low(h0), tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
        return jnp.swapaxes(y, 0, 1), h.astype(jnp.float32)

    setattr_(jamba, "selective_scan", scan)
    _retrace()


def conv_tail_not_carried(setattr_) -> None:
    """A prefill leaves zeros where the conv's last inputs belong: the first
    steps convolve over nothing where the session's last events were."""
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    sound = jamba._mamba

    def mamba(cfg, p, x, tail, h0, lengths):
        out, new_tail, h = sound(cfg, p, x, tail, h0, lengths)
        return out, (jnp.zeros_like(new_tail) if x.shape[1] > 1 else new_tail), h

    setattr_(jamba, "_mamba", mamba)
    _retrace()


def padding_advances_the_state(setattr_) -> None:
    """Every position of a padded dispatch runs through the recurrence: a
    session shorter than its length bucket has its state advanced by the
    padding behind it."""
    import jax.numpy as jnp

    from oryx_tpu.ops import jamba

    sound = jamba.selective_scan

    def scan(x, dt, b, c, a, d, h0, lengths):
        return sound(x, dt, b, c, a, d, h0, jnp.full_like(lengths, x.shape[1]))

    setattr_(jamba, "selective_scan", scan)
    _retrace()


CONTROLS = {
    "state_in_bfloat16": state_in_bfloat16,
    "conv_tail_not_carried": conv_tail_not_carried,
    "padding_advances_the_state": padding_advances_the_state,
}
