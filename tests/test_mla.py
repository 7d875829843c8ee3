"""The latent attention shared by JoyAI and Xing (ops/mla.py): JoyAI's
programs give the same bits through the shared pieces as through the pieces
ops/joyai.py wrote for itself before they were shared (kept below, as they
were, and patched in from outside), on seeded weights at the small size of
tests/test_joyai.py; the shared cache state is JoyAI's."""

from __future__ import annotations

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_joyai
from oryx_tpu.ops import joyai, mla
from oryx_tpu.ops.decoder import dot, masked_softmax, rms_norm

CFG = test_joyai.CFG


# -- ops/joyai.py's own pieces, as they were before they moved to ops/mla.py ------

def _own_rope_interleaved(x, pos, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _own_queries(cfg, p, u, pos):
    q = dot(rms_norm(dot(u, p["wq_a"]), p["q_norm"], cfg.eps), p["wq_b"])
    q = q.reshape(*u.shape[:-1], cfg.heads, cfg.qk_dim)
    return q[..., : cfg.nope], _own_rope_interleaved(q[..., cfg.nope:], pos[..., None], cfg.rope_theta)


def _own_latent(cfg, p, u, pos):
    ckv = dot(u, p["wkv_a"])
    c = rms_norm(ckv[..., : cfg.kv_rank], p["kv_norm"], cfg.eps)
    return c, _own_rope_interleaved(ckv[..., cfg.kv_rank:], pos, cfg.rope_theta)


def _own_attend_written(cfg, p, q_nope, q_rope, c, k_rope, allowed):
    f32 = jnp.float32
    dt = p["wkv_b"].dtype
    r, s_len = c.shape[0], c.shape[1]
    kv = dot(c, p["wkv_b"]).reshape(r, s_len, cfg.heads, cfg.nope + cfg.v_dim)
    k_nope, v = kv[..., : cfg.nope], kv[..., cfg.nope:]
    s = jnp.einsum("rthd,rshd->rhts", q_nope.astype(dt), k_nope.astype(dt), preferred_element_type=f32)
    s = s + jnp.einsum("rthd,rsd->rhts", q_rope.astype(dt), k_rope.astype(dt), preferred_element_type=f32)
    prob = masked_softmax(s / math.sqrt(cfg.qk_dim), allowed[:, None, :, :])
    o = jnp.einsum("rhts,rshd->rthd", prob.astype(dt), v.astype(dt), preferred_element_type=f32)
    return o.reshape(r, q_nope.shape[1], cfg.heads * cfg.v_dim)


def _own_attend_absorbed(cfg, p, q_nope, q_rope, c, k_rope, allowed):
    f32 = jnp.float32
    dt = p["wkv_b"].dtype
    w = p["wkv_b"].reshape(cfg.kv_rank, cfg.heads, cfg.nope + cfg.v_dim)
    w_uk, w_uv = w[..., : cfg.nope], w[..., cfg.nope:]
    q_lat = jnp.einsum("dhn,chn->dhc", q_nope.astype(dt), w_uk, preferred_element_type=f32)
    s = jnp.einsum("dhc,dsc->dhs", q_lat.astype(dt), c.astype(dt), preferred_element_type=f32)
    s = s + jnp.einsum("dhr,dsr->dhs", q_rope.astype(dt), k_rope.astype(dt), preferred_element_type=f32)
    prob = masked_softmax(s / math.sqrt(cfg.qk_dim), allowed[:, None, :])
    ctx = jnp.einsum("dhs,dsc->dhc", prob.astype(dt), c.astype(dt), preferred_element_type=f32)
    o = jnp.einsum("dhc,chv->dhv", ctx.astype(dt), w_uv, preferred_element_type=f32)
    return o.reshape(q_nope.shape[0], cfg.heads * cfg.v_dim)


OWN = {
    "_queries": _own_queries, "_latent": _own_latent,
    "_attend_written": _own_attend_written, "_attend_absorbed": _own_attend_absorbed,
}


def _run(dtype):
    """JoyAI's prefill and four steps over three sessions: every array the
    programs returned, on the host."""
    params, e, row_token = test_joyai._weights(dtype=dtype)
    enc = joyai.JoyaiEncoder(CFG, dtype)
    state = enc.init_state(enc.step_rows)
    sessions = test_joyai._sessions((13, 24, 2))
    state, hidden, tallied = enc.prefill(params, state, *enc.pack(sessions, 24, [0, 1, 2], enc.step_rows))
    got = {"hidden": np.asarray(hidden), "counts": np.asarray(tallied["counts"])}
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    lengths = np.zeros(enc.step_rows, np.int32)
    live = np.zeros(enc.step_rows, bool)
    for i, s in enumerate(sessions):
        slots[i], lengths[i], live[i] = i, enc.length(s), True
    for step in range(enc.steps):
        state, out = enc.step(params, state, (jnp.asarray(e, dtype), 300, row_token), slots, lengths, live,
                              np.full(enc.step_rows, step, np.int32))
        got.update({f"{k}{step}": np.asarray(v) for k, v in out.items() if k != "head_rows"})
    got.update({f"{k}{l}": np.asarray(a) for k in ("latent", "rope_key") for l, a in enumerate(state[k])})
    return got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_joyais_programs_give_the_same_bits_through_the_shared_pieces(dtype, monkeypatch):
    shared = _run(dtype)
    for name, own in OWN.items():
        monkeypatch.setattr(joyai, name, own)
    jax.clear_caches()
    try:
        own = _run(dtype)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert set(shared) == set(own)
    for k in shared:
        np.testing.assert_array_equal(shared[k], own[k], err_msg=k)
    assert np.abs(shared["z3"]).max() > 0 and shared["counts"][0] > 0


def test_the_shared_rotation_and_cache_are_joyais():
    """`rope_interleaved` at theta is the shared one at the plain frequencies,
    and the cache JoyAI's slots hold is ops/mla.py's, byte for byte."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    pos = jnp.arange(5)[None, :]
    np.testing.assert_array_equal(
        np.asarray(joyai.rope_interleaved(x, pos, 32e6)), np.asarray(_own_rope_interleaved(x, pos, 32e6))
    )
    state = joyai.init_state(CFG, 4, jnp.bfloat16)
    assert [a.shape for a in state["latent"]] == [(5, CFG.positions, CFG.kv_rank)] * CFG.layers
    assert [a.shape for a in state["rope_key"]] == [(5, CFG.positions, CFG.rope)] * CFG.layers
    assert joyai.state_bytes(CFG, 4) == mla.cache_bytes(CFG, 4) == {
        "latent": 3 * 5 * CFG.positions * CFG.kv_rank * 2, "rope_key": 3 * 5 * CFG.positions * CFG.rope * 2,
    }
