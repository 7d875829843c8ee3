"""The expert layer told which experts it holds (ops/moe.py `held`): the
shares add up to the whole layer, a pair sent elsewhere is counted as that
and never as dropped, and with every expert held the layer is what it was.
Small sizes on the CPU, seeded weights."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import moe

N_EXPERTS, SHARES, K, H, F = 16, 4, 4, 64, 32
HELD = N_EXPERTS // SHARES
RULES = {
    "softmax": {},
    "sigmoid_bias_scale": {"scoring": "sigmoid", "scale": 2.448, "bias": True},
}


def _weights(seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, shape, s=0.02: jax.random.normal(k, shape) * s  # noqa: E731
    return {
        "wr": normal(ks[0], (H, N_EXPERTS)), "wg": normal(ks[1], (N_EXPERTS, H, F)), "wu": normal(ks[2], (N_EXPERTS, H, F)),
        "wd": normal(ks[3], (N_EXPERTS, F, H)), "bias": normal(ks[4], (N_EXPERTS,), 0.1),
        "shared": (normal(ks[5], (H, F)), normal(ks[6], (H, F)), normal(ks[7], (F, H))),
    }


def _rule(name, w):
    kw = dict(RULES[name])
    if kw.pop("bias", False):
        kw["bias"] = w["bias"]
    return kw


def _share(w, s):
    lo = s * HELD
    return (w["wg"][lo:lo + HELD], w["wu"][lo:lo + HELD], w["wd"][lo:lo + HELD]), (lo, HELD)


def _shared_expert(w, u):
    wg, wu, wd = w["shared"]
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


@pytest.mark.parametrize("rule", list(RULES))
def test_the_shares_add_up_to_the_whole_layer(rule):
    """16 experts in 4 shares of 4: the four routed parts, plus the shared
    expert (which every chip computes alike) counted ONCE, are what the uncut
    reference gives for the whole layer; and every pair is computed on
    exactly one share."""
    w = _weights()
    kw = _rule(rule, w)
    u = jax.random.normal(jax.random.PRNGKey(1), (40, H))
    live = jnp.arange(40) < 33  # a padded step: seven tokens reach no expert on any share
    with jax.default_matmul_precision("highest"):
        whole = moe.moe_reference(u, w["wr"], w["wg"], w["wu"], w["wd"], K, **kw) + _shared_expert(w, u)
    total = _shared_expert(w, u)
    here = elsewhere = 0
    for s in range(SHARES):
        (wg, wu, wd), held = _share(w, s)
        part, counts = moe.moe_apply(u, w["wr"], wg, wu, wd, K, live, held=held, **kw)
        with jax.default_matmul_precision("highest"):
            ref = moe.moe_reference(u, w["wr"], wg, wu, wd, K, held=held, **kw)
        np.testing.assert_allclose(np.asarray(part[:33]), np.asarray(ref[:33]), atol=2e-6)
        assert np.asarray(part[33:]).max() == 0.0
        total = total + part
        routed, touched, busiest, away = np.asarray(counts).tolist()
        # a pair sent elsewhere counts as that, and the two are every real token's pairs: none dropped
        assert routed + away == 33 * K and 0 < touched <= HELD and busiest <= 33
        here, elsewhere = here + routed, elsewhere + away
    np.testing.assert_allclose(np.asarray(total[:33]), np.asarray(whole[:33]), atol=3e-6)
    assert here == 33 * K and elsewhere == (SHARES - 1) * 33 * K


@pytest.mark.parametrize("rule", list(RULES))
def test_a_share_routes_over_every_expert_and_computes_its_own(rule):
    w = _weights()
    kw = _rule(rule, w)
    u = jax.random.normal(jax.random.PRNGKey(2), (24, H))
    weights, experts = (np.asarray(a) for a in moe.route(u, w["wr"], K, **kw))
    np.testing.assert_allclose(weights.sum(-1), kw.get("scale", 1.0), rtol=1e-6)  # normalised over all four chosen
    (wg, wu, wd), held = _share(w, 2)
    part, counts = moe.moe_apply(u, w["wr"], wg, wu, wd, K, held=held, **kw)
    mine = (experts >= 8) & (experts < 12)
    sizes = np.bincount(experts[mine] - 8, minlength=HELD)
    assert np.asarray(counts).tolist() == [int(mine.sum()), int((sizes > 0).sum()), int(sizes.max()), int((~mine).sum())]
    # a token none of whose experts is here gets nothing from this share
    none_here = ~mine.any(-1)
    if none_here.any():
        assert np.abs(np.asarray(part)[none_here]).max() == 0.0
    # by hand: the token's weight for each of this share's experts times that expert's SwiGLU
    want = np.zeros((24, H), np.float32)
    with jax.default_matmul_precision("highest"):
        for t, j in zip(*np.nonzero(mine)):
            x = experts[t, j]
            y = (jax.nn.silu(u[t] @ w["wg"][x]) * (u[t] @ w["wu"][x])) @ w["wd"][x]
            want[t] += weights[t, j] * np.asarray(y)
    np.testing.assert_allclose(np.asarray(part), want, atol=2e-6)


@pytest.mark.parametrize("rule", list(RULES))
def test_with_every_expert_held_the_layer_is_what_it_was(rule):
    """Told that it holds all 16, the layer gives bit for bit what it gives
    told nothing (the path `sdar` and `joyai` take), the same three counts,
    and no pair sent elsewhere."""
    w = _weights()
    kw = _rule(rule, w)
    u = jax.random.normal(jax.random.PRNGKey(5), (40, H))
    live = jnp.arange(40) < 29
    plain, counts3 = moe.moe_apply(u, w["wr"], w["wg"], w["wu"], w["wd"], K, live, **kw)
    told, counts4 = moe.moe_apply(u, w["wr"], w["wg"], w["wu"], w["wd"], K, live, held=(0, N_EXPERTS), **kw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(told))
    assert np.asarray(counts3).shape == (3,) and np.asarray(counts4).tolist() == np.asarray(counts3).tolist() + [0]
    assert int(counts3[0]) == 29 * K
    with jax.default_matmul_precision("highest"):
        ref = moe.moe_reference(u, w["wr"], w["wg"], w["wu"], w["wd"], K, **kw)
        ref_told = moe.moe_reference(u, w["wr"], w["wg"], w["wu"], w["wd"], K, held=(0, N_EXPERTS), **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref_told))
    np.testing.assert_allclose(np.asarray(plain[:29]), np.asarray(ref[:29]), atol=2e-6)


def test_the_untold_layers_lowered_program_has_no_trace_of_a_share():
    """`held` is static: a layer told nothing lowers without the comparison
    against a share's bounds or a fourth count."""
    w = _weights()
    u = jax.ShapeDtypeStruct((40, H), jnp.float32)
    whole = lambda u: moe.moe_apply(u, w["wr"], w["wg"], w["wu"], w["wd"], K)  # noqa: E731
    part = lambda u: moe.moe_apply(u, w["wr"], w["wg"][4:12], w["wu"][4:12], w["wd"][4:12], K, held=(4, 8))  # noqa: E731
    assert jax.eval_shape(whole, u)[1].shape == (3,) and jax.eval_shape(part, u)[1].shape == (4,)
    text = jax.jit(whole).lower(u).as_text()
    assert "tensor<4xi32>" not in text and "tensor<4xi32>" in jax.jit(part).lower(u).as_text()
