"""Multi-head latent attention (the DeepSeek-V3 family's), written once for the
architectures that use it (ops/joyai.py, ops/xing.py); each keeps its own
layer around it and hands in its rotation and its softmax's divisor.

    c_q = RMSNorm(u W_qa);  q = c_q W_qb, heads x (nope + rope)
    [c_kv | k_r] = u W_kva;  c = RMSNorm(c_kv);  k_rope = rope(k_r),
    ONE key for all heads;  [k_nope_h | v_h] = c W_kvb
    score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + rope(q_rope_h(t)) .
    k_rope(s)) / divisor;  causal softmax;  o_h = sum p v_h  (W_o is the
    caller's).  No bias.
    rope:  the interleaved pairs (2i, 2i+1) turned by pos x inv_i

The inverse frequencies `inv` [rope / 2] and the `divisor` are the model's,
computed once from its configuration: with no `rope_scaling`,
`plain_frequencies` theta^(-2i/d) and sqrt(nope + rope); with YaRN,
`yarn_frequencies` and that root over `yarn_mscale(factor, mscale_all_dim)`
squared.

The attention has two forms, the same function (tests/test_joyai.py): a
prefill computes it as written (`attend_written`: keys and values
decompressed for the bucket's positions); a one-token step ABSORBS W_kvb
(`attend_absorbed`: with W_kvb split a head into W_uk and W_uv, q'_h =
q_nope_h W_uk_h^T scores the cached latent itself and o_h = (sum p c)
W_uv_h, so nothing is decompressed a position).

Precision: weights in their stored dtype; the activations enter every
product in that dtype and accumulate in float32 (ops/decoder.py `dot`); the
norms, the softmax and the rotation are float32.
"""

from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp

from oryx_tpu.ops.decoder import dot, masked_softmax, rms_norm


# -- the rotation's frequencies and the softmax's divisor ---------------------------

def plain_frequencies(theta, d: int):
    """theta^(-2i/d) for the d/2 pairs, float32."""
    return theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def yarn_frequencies(theta: float, d: int, factor: float, original: int, beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies (DeepSeek-V3's form), float32 [d/2]: a pair
    that turns more than `beta_fast` times over the original window keeps
    its frequency, one that turns fewer than `beta_slow` times is divided by
    `factor`, and between the two bounds (floor and ceiling of the pair index
    where those counts fall) the two are blended along a linear ramp."""

    def pair_of(turns: float) -> float:
        return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    i = np.arange(d // 2, dtype=np.float64)
    kept = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)     # 1: the pair keeps its frequency
    base = theta ** (-2.0 * i / d)
    return (base * kept + base / factor * (1.0 - kept)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 x mscale x ln(factor) + 1 (1 at factor 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


# -- what a slot keeps ------------------------------------------------------------

def cache(cfg, slots: int, dtype) -> dict:
    """A layer's cache for `slots` requests and one scratch slot (the last:
    padding rows of a dispatch write there), one row a position: the
    normalised latent c and the rotated key."""
    s = slots + 1
    return {
        "latent": [jnp.zeros((s, cfg.positions, cfg.kv_rank), dtype) for _ in range(cfg.layers)],
        "rope_key": [jnp.zeros((s, cfg.positions, cfg.rope), dtype) for _ in range(cfg.layers)],
    }


def cache_bytes(cfg, slots: int, itemsize: int = 2) -> dict[str, int]:
    """Bytes of the slots' cache by its kind, both a row a position."""
    rows = cfg.layers * (slots + 1) * cfg.positions * itemsize
    return {"latent": rows * cfg.kv_rank, "rope_key": rows * cfg.rope}


# -- the layer's pieces ---------------------------------------------------------

def rope_interleaved(x, pos, inv):
    """x [..., d] float32, pos broadcastable to x's leading axes, inv [d/2]
    -> the pairs (2i, 2i+1) turned by pos x inv_i, in place."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[..., None] * inv                              # [..., d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def queries(cfg, p: dict, u, pos, inv):
    """u [..., H] float32 (normalised), pos [...] -> (q_nope [..., heads,
    nope], q_rope [..., heads, rope] rotated), float32."""
    q = dot(rms_norm(dot(u, p["wq_a"]), p["q_norm"], cfg.eps), p["wq_b"])
    q = q.reshape(*u.shape[:-1], cfg.heads, cfg.qk_dim)
    return q[..., : cfg.nope], rope_interleaved(q[..., cfg.nope:], pos[..., None], inv)


def latent(cfg, p: dict, u, pos, inv):
    """u [..., H] float32 (normalised), pos [...] -> what the cache keeps of
    each position: (c [..., kv_rank] normalised, k_rope [..., rope] rotated)."""
    ckv = dot(u, p["wkv_a"])
    c = rms_norm(ckv[..., : cfg.kv_rank], p["kv_norm"], cfg.eps)
    return c, rope_interleaved(ckv[..., cfg.kv_rank:], pos, inv)


def attend_written(cfg, p: dict, q_nope, q_rope, c, k_rope, allowed, divisor: float):
    """The attention as written, over a prefill's own positions: q_nope
    [R,T,heads,nope], q_rope [R,T,heads,rope], c [R,S,kv_rank], k_rope
    [R,S,rope], allowed [R,T,S] -> [R,T,heads * v_dim] float32. Keys and
    values are decompressed for every position."""
    f32 = jnp.float32
    dt = p["wkv_b"].dtype
    r, s_len = c.shape[0], c.shape[1]
    kv = dot(c, p["wkv_b"]).reshape(r, s_len, cfg.heads, cfg.nope + cfg.v_dim)
    k_nope, v = kv[..., : cfg.nope], kv[..., cfg.nope:]
    s = jnp.einsum("rthd,rshd->rhts", q_nope.astype(dt), k_nope.astype(dt), preferred_element_type=f32)
    s = s + jnp.einsum("rthd,rsd->rhts", q_rope.astype(dt), k_rope.astype(dt), preferred_element_type=f32)
    prob = masked_softmax(s / divisor, allowed[:, None, :, :])
    o = jnp.einsum("rhts,rshd->rthd", prob.astype(dt), v.astype(dt), preferred_element_type=f32)
    return o.reshape(r, q_nope.shape[1], cfg.heads * cfg.v_dim)


def attend_absorbed(cfg, p: dict, q_nope, q_rope, c, k_rope, allowed, divisor: float):
    """The same attention for ONE query a row over its slot's cache, W_kvb
    absorbed: q_nope [D,heads,nope], q_rope [D,heads,rope], c [D,S,kv_rank],
    k_rope [D,S,rope] (as the cache holds them), allowed [D,S] -> [D, heads *
    v_dim] float32. The latent is scored and summed as it lies."""
    f32 = jnp.float32
    dt = p["wkv_b"].dtype
    w = p["wkv_b"].reshape(cfg.kv_rank, cfg.heads, cfg.nope + cfg.v_dim)
    w_uk, w_uv = w[..., : cfg.nope], w[..., cfg.nope:]
    q_lat = jnp.einsum("dhn,chn->dhc", q_nope.astype(dt), w_uk, preferred_element_type=f32)
    s = jnp.einsum("dhc,dsc->dhs", q_lat.astype(dt), c.astype(dt), preferred_element_type=f32)
    s = s + jnp.einsum("dhr,dsr->dhs", q_rope.astype(dt), k_rope.astype(dt), preferred_element_type=f32)
    prob = masked_softmax(s / divisor, allowed[:, None, :])
    ctx = jnp.einsum("dhs,dsc->dhc", prob.astype(dt), c.astype(dt), preferred_element_type=f32)
    o = jnp.einsum("dhc,chv->dhv", ctx.astype(dt), w_uv, preferred_element_type=f32)
    return o.reshape(q_nope.shape[0], cfg.heads * cfg.v_dim)
