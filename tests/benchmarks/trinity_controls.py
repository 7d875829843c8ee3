"""The six controls of kind `trinity-serving`: the program broken underneath
in a way a sound comparison has to see. Each takes `setattr(obj, name,
value)` (pytest's `monkeypatch.setattr`, or the builtin for a scratch run on
the chip) and patches the PROGRAM from outside; none is an option of it.
Call before the first request of a run: they clear jax's jit caches."""

from __future__ import annotations


def _retrace():
    import jax

    jax.clear_caches()


def kv_cache_in_8_bits(setattr_) -> None:
    """Keys and values rounded to 8 bits (float8 e4m3) where they are made,
    the nearest precision below the bfloat16 the configuration states: what a
    slot keeps and what a prefill attends over."""
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity

    sound = trinity._qkv

    def qkv(cfg, p, a, pos, sliding):
        low = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
        q, k, v = sound(cfg, p, a, pos, sliding)
        return q, low(k), low(v)

    setattr_(trinity, "_qkv", qkv)
    _retrace()


def sliding_keys_not_rotated(setattr_) -> None:
    """A sliding layer's keys go into the cache (and a prefill's scores) as
    the norm left them, never turned by their position; the queries still are."""
    from oryx_tpu.ops import trinity

    sound = trinity._qkv

    def qkv(cfg, p, a, pos, sliding):
        q, _, v = sound(cfg, p, a, pos, sliding)
        return q, sound(cfg, p, a, pos, False)[1], v

    setattr_(trinity, "_qkv", qkv)
    _retrace()


def attention_gate_left_out(setattr_) -> None:
    """The attention's output reaches W_o ungated (a constant gate: the norm
    after W_o takes a constant factor out again)."""
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity

    sound = trinity._attn_out

    def attn_out(cfg, p, x, a, attended):
        return sound(cfg, dict(p, wgate=jnp.zeros_like(p["wgate"])), x, a, attended)

    setattr_(trinity, "_attn_out", attn_out)
    _retrace()


def shared_expert_left_out(setattr_) -> None:
    """An expert layer is its held routed experts alone."""
    import jax.numpy as jnp

    from oryx_tpu.ops import trinity

    setattr_(trinity, "_shared_expert", lambda p, u: jnp.zeros(u.shape, jnp.float32))
    _retrace()


def route_scale_left_out(setattr_) -> None:
    """The chosen experts' weights sum to 1, not to route_scale."""
    from oryx_tpu.ops import moe

    sound = moe.route

    def route(u, wr, k, scoring="softmax", bias=None, scale=1.0):
        return sound(u, wr, k, scoring, bias, 1.0)

    setattr_(moe, "route", route)
    _retrace()


def another_chips_share_computed(setattr_) -> None:
    """The layer is told it holds the NEXT chip's experts and computes their
    pairs with this chip's weights: as many pairs as before reach an expert
    here, the wrong ones (the counters cannot see it; the scores do)."""
    from oryx_tpu.ops import trinity

    def routing(cfg):
        first = (cfg.first_expert + cfg.held) % cfg.experts
        return {"scoring": "sigmoid", "scale": cfg.route_scale, "held": (first, cfg.held)}

    setattr_(trinity.TrinityConfig, "routing", property(routing))
    _retrace()


CONTROLS = {
    "kv_cache_in_8_bits": kv_cache_in_8_bits,
    "sliding_keys_not_rotated": sliding_keys_not_rotated,
    "attention_gate_left_out": attention_gate_left_out,
    "shared_expert_left_out": shared_expert_left_out,
    "route_scale_left_out": route_scale_left_out,
    "another_chips_share_computed": another_chips_share_computed,
}
