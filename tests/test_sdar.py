"""The SDAR block (ops/sdar.py, ops/moe.py) against its plain reference, the
batched encoder step (serving/stepper.py) and the seq app's request path
through both, on the CPU at a small size: 2 layers, hidden 64, 8 experts of
width 32, 4 a token, 2 KV heads, 500 items, seeded weights.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import moe, sdar
from oryx_tpu.ops.seq import GruEncoder, encode_vectors, init_gru_params

CFG = sdar.SdarConfig(
    hidden=64, heads=4, kv_heads=2, head_dim=16, experts=8, expert_width=32,
    experts_per_token=4, layers=2, vocab=501, max_len=24,
)
N_ITEMS = 500
# float32 served form against the float32 reference: accumulation order
# alone. Logits are about 0.5 at these weights
F32_ATOL = 5e-6
# bfloat16 served form (weights and activations as published, float32
# accumulation) against the float32 reference on the same bf16 weights: the
# activations' rounding, 2^-9 relative at each of 2 layers x 7 products
BF16_ATOL = 2e-2


def _weights(seed=7, dtype=jnp.float32):
    params = sdar.init_params(CFG, seed, dtype)
    rng = np.random.default_rng(seed)
    e_out = (rng.standard_normal((512, CFG.hidden)) * 0.02).astype(np.float32)
    e_out[N_ITEMS:] = 0.0  # capacity rows
    e_out = np.asarray(jnp.asarray(e_out, jnp.bfloat16).astype(jnp.float32))
    return params, e_out


def _prefixes(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.choice(N_ITEMS, size=n, replace=False).astype(np.int32) for n in lengths]


def _generate(enc, params, view, prefixes, slots_of=None, fill=()):
    """Prefill + the encoder's steps through the slot cache for `prefixes`
    (and `fill`, more sessions sharing the dispatches); -> out of the last
    step and the per-step z of every row."""
    head = (view, N_ITEMS, jnp.arange(view.shape[0], dtype=jnp.int32))
    state = enc.init_state(enc.step_rows)
    everyone = list(prefixes) + list(fill)
    slots_of = slots_of or list(range(len(everyone)))
    for lo in range(0, len(everyone), enc.prefill_rows):
        group = everyone[lo:lo + enc.prefill_rows]
        bucket = min(b for b in enc.length_buckets if b >= max(len(p) for p in group))
        packed = enc.pack(group, bucket, slots_of[lo:lo + len(group)], enc.step_rows)
        state, _, _ = enc.prefill(params, state, *packed)
    slots = np.full(enc.step_rows, enc.step_rows, np.int32)
    lengths = np.zeros(enc.step_rows, np.int32)
    live = np.zeros(enc.step_rows, bool)
    for i, p in enumerate(everyone):
        slots[i], lengths[i], live[i] = slots_of[i], len(p), True
    outs = []
    for step in range(enc.steps):
        state, out = enc.step(
            params, state, head, slots, lengths, live, np.full(enc.step_rows, step, np.int32)
        )
        outs.append({k: np.asarray(v) for k, v in out.items()})
    return outs


# ---- the expert layer alone -------------------------------------------------

def _moe_weights(seed=3, n_experts=8, h=64, f=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    wr = jax.random.normal(ks[0], (h, n_experts)) * 0.02
    wg = jax.random.normal(ks[1], (n_experts, h, f)) * 0.02
    wu = jax.random.normal(ks[2], (n_experts, h, f)) * 0.02
    wd = jax.random.normal(ks[3], (n_experts, f, h)) * 0.02
    return wr, wg, wu, wd


@pytest.mark.parametrize("load", ["even", "one_expert_takes_most", "an_expert_takes_none"])
def test_expert_layer_against_the_plain_form(load):
    wr, wg, wu, wd = _moe_weights()
    u = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
    if load == "one_expert_takes_most":
        # every token's first choice is expert 5, whatever else it reaches
        wr = wr.at[:, 5].set(0.0)
        u = u.at[:, 0].set(30.0)
        wr = wr.at[0, 5].set(1.0)
    elif load == "an_expert_takes_none":
        u = u.at[:, 0].set(30.0)
        wr = wr.at[0, 2].set(-1.0)
    y, counts = jax.jit(lambda u: moe.moe_apply(u, wr, wg, wu, wd, 4))(u)
    with jax.default_matmul_precision("highest"):
        ref = moe.moe_reference(u, wr, wg, wu, wd, 4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)
    routed, touched, busiest = np.asarray(counts).tolist()
    assert routed == 40 * 4  # no token is dropped, whatever the load
    _, e = moe.route(u, wr, 4)
    sizes = np.bincount(np.asarray(e).ravel(), minlength=8)
    assert touched == int((sizes > 0).sum()) and busiest == int(sizes.max())
    if load == "one_expert_takes_most":
        assert busiest == 40
    if load == "an_expert_takes_none":
        assert sizes[2] == 0 and touched < 8


def test_padding_tokens_reach_no_expert():
    wr, wg, wu, wd = _moe_weights()
    u = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    live = jnp.arange(16) < 5
    y, counts = moe.moe_apply(u, wr, wg, wu, wd, 4, live)
    y5, counts5 = moe.moe_apply(u[:5], wr, wg, wu, wd, 4)
    np.testing.assert_allclose(np.asarray(y[:5]), np.asarray(y5), atol=1e-7)
    assert np.asarray(y[5:]).max() == 0.0
    assert np.asarray(counts).tolist() == np.asarray(counts5).tolist()


# ---- the block against the reference ---------------------------------------

def test_prefill_hidden_is_the_references_last_position():
    params, e_out = _weights()
    enc = sdar.SdarEncoder(CFG, jnp.float32)
    prefixes = _prefixes((5, 24, 11))
    state = enc.init_state(enc.step_rows)
    state, hidden, tallied = enc.prefill(params, state, *enc.pack(prefixes, 24, [0, 1, 2], enc.step_rows))
    counts = tallied["counts"]
    for i, p in enumerate(prefixes):
        tokens = np.zeros(CFG.positions, np.int32)
        tokens[:len(p)] = p
        # no block appended: the reference's causal prefix, before its final norm
        z = sdar.reference_forward(CFG, params, jnp.asarray(tokens), jnp.int32(CFG.positions))
        want = np.asarray(z[len(p) - 1])
        got = np.asarray(sdar.rms_norm(hidden[i], params["final_norm"], CFG.eps))
        np.testing.assert_allclose(got, want, atol=F32_ATOL * 20)  # normalised: O(1) entries
    assert int(counts[0]) == sum(len(p) for p in prefixes) * CFG.experts_per_token * CFG.layers


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, F32_ATOL), (jnp.bfloat16, BF16_ATOL)])
def test_cached_generation_against_the_references_full_forward(dtype, atol):
    """Prefill, then four denoising steps through the slot cache, against
    the reference's full forward over prefix + block at every step."""
    params, e_out = _weights(dtype=dtype)
    enc = sdar.SdarEncoder(CFG, dtype)
    view = jnp.asarray(e_out, dtype)
    prefixes = _prefixes((5, 24, 11))
    outs = _generate(enc, params, view, prefixes)
    for i, p in enumerate(prefixes):
        # the reference replays the served trajectory: same positions, same
        # items, at the same steps
        row, step_of = outs[-1]["row"][i], outs[-1]["step"][i]
        assert sorted(step_of.tolist()) == [0, 1, 2, 3]
        tok = np.full(CFG.block_length, CFG.mask_id, np.int32)
        for step in range(CFG.denoise_steps):
            tokens = np.zeros(CFG.positions, np.int32)
            tokens[:len(p)], tokens[len(p):len(p) + 4] = p, tok
            ref = np.asarray(sdar.reference_block_logits(
                CFG, params, jnp.asarray(e_out), jnp.asarray(tokens), jnp.int32(len(p)),
                jnp.int32(N_ITEMS),
            ))
            b = int(np.where(step_of == step)[0][0])
            served = e_out[:N_ITEMS] @ outs[step]["z"][i, b]
            np.testing.assert_allclose(served, ref[b, :N_ITEMS], atol=atol)
            # the item fixed is the reference's best, or within the tolerance of it
            assert ref[b, row[b]] >= ref[b, :N_ITEMS].max() - 2 * atol
            tok[b] = row[b]
    if dtype == jnp.float32:
        ref = sdar.reference_generate(CFG, params, jnp.asarray(e_out), prefixes[0], n_valid=N_ITEMS)
        assert ref["row"].tolist() == outs[-1]["row"][0].tolist()
        assert ref["step"].tolist() == outs[-1]["step"][0].tolist()


def test_block_mask_is_bidirectional_inside_and_causal_across(monkeypatch):
    """A block position's output changes when a LATER block position's
    token changes; a prefix position's does not."""
    params, _ = _weights()
    p = _prefixes((9,))[0]
    tokens = np.zeros(CFG.positions, np.int32)
    tokens[:9], tokens[9:13] = p, CFG.mask_id
    other = tokens.copy()
    other[12] = 17  # the block's last position
    z0 = np.asarray(sdar.reference_forward(CFG, params, jnp.asarray(tokens), jnp.int32(9)))
    z1 = np.asarray(sdar.reference_forward(CFG, params, jnp.asarray(other), jnp.int32(9)))
    assert np.abs(z0[9] - z1[9]).max() > 1e-4      # block position 0 sees position 3
    assert np.abs(z0[:9] - z1[:9]).max() == 0.0    # the prefix sees none of the block
    # and the served step agrees at every block position: a causal mask
    # inside the block (the benchmark's control) does not
    enc = sdar.SdarEncoder(CFG, jnp.float32)
    state = enc.init_state(enc.step_rows)
    state, _, _ = enc.prefill(params, state, *enc.pack([p], 24, [0], enc.step_rows))
    args = (jnp.asarray([0]), jnp.asarray([9]), jnp.asarray([True]))
    served, _ = sdar._block_hidden(CFG, params, state, *args)
    assert np.abs(np.asarray(served[0]) - z0[9:13]).max() < 1e-4
    sound = sdar._attend

    def causal_inside(cfg, q, k, v, allowed, dt):
        b = cfg.block_length
        inside = jnp.concatenate([jnp.ones((b, cfg.max_len), bool), jnp.tril(jnp.ones((b, b), bool))], -1)
        return sound(cfg, q, k, v, allowed & inside[None], dt)

    monkeypatch.setattr(sdar, "_attend", causal_inside)
    control, _ = sdar._block_hidden(CFG, params, state, *args)
    assert np.abs(np.asarray(control[0, :3]) - z0[9:12]).max() > 1e-3


def test_an_answer_does_not_depend_on_what_shared_its_dispatches():
    params, e_out = _weights()
    enc = sdar.SdarEncoder(CFG, jnp.float32)
    view = jnp.asarray(e_out)
    mine = _prefixes((13,))
    alone = _generate(enc, params, view, mine)
    fill = _prefixes([5 + (3 * j) % 20 for j in range(enc.step_rows - 1)], seed=5)
    # a full mixed dispatch, and another slot than before
    slots_of = [enc.step_rows - 1] + list(range(enc.step_rows - 1))
    full = _generate(enc, params, view, mine, slots_of=slots_of, fill=fill)
    for a, f in zip(alone, full):
        np.testing.assert_array_equal(a["row"][0], f["row"][0])
        np.testing.assert_array_equal(a["step"][0], f["step"][0])
        np.testing.assert_allclose(e_out @ a["z"][0].T, e_out @ f["z"][0].T, atol=F32_ATOL)


# ---- through the seam, the stepper and the app ------------------------------

def _sdar_message(seed=7):
    from oryx_tpu.common.artifact import ModelArtifact

    tensors = {k: np.asarray(v) for k, v in sdar.init_tensors(CFG, seed, jnp.float32).items()}
    _, e_out = _weights(seed)
    tensors["E"] = e_out[:N_ITEMS]
    art = ModelArtifact("seq", tensors=tensors)
    for k, v in CFG.to_extensions().items():
        art.set_extension(k, v)
    art.set_extension("encoder", "sdar")
    art.set_extension("dtype", "float32")
    art.set_extension("ItemIDs", [f"i{j}" for j in range(N_ITEMS)])
    return art.to_string()


def test_sdar_artifact_answers_recommend_next_end_to_end():
    """MODEL message -> apply_seq_update -> ServingLayer -> GET
    /recommend-next: through the seam, the batched encoder step and
    TopKBatcher, against the plain reference's generation."""
    from oryx_tpu.apps.seq.serving import SeqServingModelManager
    from oryx_tpu.apps.updates import vector_update_message
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    broker = "mem://sdar-e2e"
    cfg = load_config(overlay={
        "oryx.id": "sdar-e2e",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common", "oryx_tpu.serving.resources.seq",
        ],
    })
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = SeqServingModelManager(cfg)
    manager.consume_key_message("MODEL", _sdar_message())
    # an item that arrives by UP after the model: a head row, no input embedding
    _, up = vector_update_message("E", "late", np.full(CFG.hidden, 0.001, np.float32))
    manager.consume_key_message("UP", up)
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    try:
        base = f"http://127.0.0.1:{serving.port}"
        reg = get_registry()
        blocks0 = reg.counter("oryx_seq_blocks_total").value()
        prefix = [3, 141, 59, 26, 5, 358, 97]
        path = "/".join(f"i{j}" for j in prefix)

        def get(p):
            req = urllib.request.Request(f"{base}{p}", headers={"Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read())

        answer = get(f"/recommend-next/{path}?howMany=10")
        params, e_out = _weights()
        e_all = np.concatenate([e_out[:N_ITEMS], np.full((1, CFG.hidden), 0.001, np.float32)])
        row_token = np.concatenate([np.arange(N_ITEMS), [CFG.mask_id]])
        ref = sdar.reference_generate(
            CFG, params, jnp.asarray(e_all), np.asarray(prefix, np.int32), row_token=row_token,
        )
        assert len(answer) == CFG.block_length
        for b, entry in enumerate(answer):
            assert entry["item"] == f"i{ref['row'][b]}" and entry["step"] == ref["step"][b]
            logits = ref["logits"][ref["step"][b], b].copy()
            logits[prefix] = -np.inf
            want = np.argsort(-logits, kind="stable")[:10]
            names = [f"i{r}" if r < N_ITEMS else "late" for r in want]
            assert [i for i, _ in entry["next"]] == names
            np.testing.assert_allclose([s for _, s in entry["next"]], logits[want], atol=F32_ATOL)
        # the late item is skipped as context: the same answer with it in the path
        again = get(f"/recommend-next/late/{path}?howMany=10")
        assert [e["item"] for e in again] == [e["item"] for e in answer]
        assert reg.counter("oryx_seq_blocks_total").value() - blocks0 == 2
        # several at once share dispatches and give what they give alone
        results = {}

        def one(j):
            results[j] = get(f"/recommend-next/{path}?howMany=10")

        threads = [threading.Thread(target=one, args=(j,)) for j in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results[j] == answer for j in range(6))
        page = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        for name in (
            'oryx_seq_steps_total{kind="denoise"}', 'oryx_seq_step_tokens_total{kind="prefill",tokens="real"}',
            "oryx_seq_denoise_steps_total", "oryx_moe_routed_total", "oryx_moe_experts_touched_total",
            "oryx_moe_expert_tokens_max_total", 'oryx_request_phase_seconds_count{phase="encode"}',
            'oryx_seq_encode_stage_seconds_count{stage="denoise"}',
            'oryx_post_stage_seconds_count{stage="rerank"}',
        ):
            assert name in page, name
    finally:
        serving.close()


def test_gru_through_the_seam_is_bit_for_bit_the_direct_call():
    """The GRU behind the seam is `encode_vectors`, unchanged: bit for bit
    the direct call of the same shape (the stepper's dispatch is
    `prefill_rows` sessions; the parent's was one, and XLA's CPU dot rounds
    the last bit differently at another batch size, so against the one-row
    program the agreement is 1e-6 relative, not bitwise)."""
    from oryx_tpu.serving.stepper import Engine, SeqStepper

    dim, window = 8, 3
    params = {k: np.asarray(v) for k, v in init_gru_params(jax.random.PRNGKey(0), dim).items()}
    rng = np.random.default_rng(1)
    mat = np.zeros((window, dim), np.float32)
    mask = np.zeros((window,), np.float32)
    mat[1:] = rng.standard_normal((2, dim))
    mask[1:] = 1.0
    enc = GruEncoder(dim, window)
    assert enc.steps == 0 and enc.block == 1
    jp = enc.device_params(params)
    one_row = np.asarray(encode_vectors(jp, jnp.asarray(mat[None]), jnp.asarray(mask[None])))[0]
    np.testing.assert_array_equal(enc.encode_host(params, mat[None], mask[None])[0], one_row)
    mats, masks = enc.pack([(mat, mask)] * 3, window, None, 0)
    direct = np.asarray(encode_vectors(jp, jnp.asarray(mats), jnp.asarray(masks)))
    np.testing.assert_array_equal(direct[0], direct[2])
    np.testing.assert_allclose(direct[0], one_row, rtol=1e-6)
    engine = Engine(enc, params)
    stepper = SeqStepper()
    try:
        futs = [stepper.submit(engine, (mat, mask)) for _ in range(11)]  # two prefills
        for f in futs:
            got = f.result(timeout=60)
            assert got.rows is None and got.hidden.shape == (1, dim)
            np.testing.assert_array_equal(got.hidden[0], direct[0])
    finally:
        stepper.close()
