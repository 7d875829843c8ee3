"""What the four generating encoders share (ops/decoder.py): the seam's
class, the artifact's layout and the basket a step fills, once for each
architecture at its test file's tiny configuration. Each architecture's own
test file holds its served programs against its plain reference."""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_jamba
import test_joyai
import test_sdar
import test_trinity
from oryx_tpu.ops import jamba, joyai, sdar, trinity
from oryx_tpu.ops.seq import encoder_for


def _narrower(a):
    return a[..., :-1]


def _four_held(a):
    return np.concatenate([a] * 4)  # all 16 experts where 4 are held


# name -> (module, tiny configuration, its artifact, its label, (steps, block,
# step_tokens, step_kind, prefill_rows, unknown_token), its kinds of slot state,
# the artifact's tensors broken: {name: how (None: left out)})
ARCHS = {
    "sdar": (
        sdar, test_sdar.CFG, test_sdar._sdar_message, "SDAR", (4, 4, 4, "denoise", 8, 500), {"kv"},
        {"L0.wq": _narrower},
    ),
    "jamba": (
        jamba, test_jamba.CFG, test_jamba._jamba_message, "Jamba", (4, 4, 1, "decode", 4, None),
        {"recurrent", "kv"},
        {"L0.A_log": _narrower, "L1.wq": None},  # L1 is the attention layer: a Mamba layer has no wq
    ),
    "joyai": (
        joyai, test_joyai.CFG, test_joyai._joyai_message, "JoyAI", (4, 4, 1, "decode", 4, -1),
        {"latent", "rope_key"},
        {"L1.router_bias": _narrower, "L2.shared_wd": None},  # the expert layers': the dense one has none
    ),
    "trinity": (
        trinity, test_trinity.CFG, test_trinity._trinity_message, "Trinity", (4, 4, 1, "decode", 4, -1),
        {"window_kv", "full_kv"},
        {"L1.wg": _four_held, "L2.wgate": None},
    ),
}
DECODERS = ("jamba", "joyai", "trinity")


@pytest.mark.parametrize("arch", list(ARCHS))
def test_the_artifact_chooses_the_encoder(arch):
    from oryx_tpu.apps.seq.state import apply_seq_update
    from oryx_tpu.common.artifact import ModelArtifact

    _module, cfg, message, _label, seam, kinds, broken = ARCHS[arch]
    st = apply_seq_update(None, "MODEL", message())
    assert st.encoder.name == arch and st.encoder.cfg == cfg
    assert st.dim == cfg.hidden and st.token_of["i3"] == 3
    enc = encoder_for(arch, {k: str(v) for k, v in cfg.to_extensions().items()}.get)
    assert (enc.steps, enc.block, enc.step_tokens, enc.step_kind, enc.prefill_rows, enc.unknown_token) == seam
    assert enc.step_rows == 32 and set(enc.state_bytes(32)) == kinds
    assert encoder_for("gru", {"dim": "8", "window": "3"}.get).name == "gru"
    with pytest.raises(ValueError):
        encoder_for("lstm", {}.get)
    # a tensor of the wrong shape, or one the configuration states and the
    # artifact lacks, is refused against the extensions
    for name, how in broken.items():
        art = ModelArtifact.from_string(message())
        if how is None:
            del art.tensors[name]
        else:
            art.tensors[name] = how(art.tensors[name])
        with pytest.raises(ValueError):
            apply_seq_update(None, "MODEL", art.to_string())


@pytest.mark.parametrize("arch", list(ARCHS))
def test_params_of_refuses_a_missing_or_misshaped_tensor_by_the_models_name(arch):
    module, cfg, _message, label, *_ = ARCHS[arch]
    tensors = {k: np.asarray(v) for k, v in module.init_tensors(cfg, 7, jnp.float32).items()}
    assert module.params_of(cfg, tensors)["layers"][0]["ln1"].shape == (cfg.hidden,)
    missing = {k: v for k, v in tensors.items() if k != "L0.ln1"}
    with pytest.raises(ValueError, match=f"^{label} model lacks tensor 'L0.ln1'$"):
        module.params_of(cfg, missing)
    misshaped = dict(tensors, **{"L0.ln1": tensors["L0.ln1"][:-1]})
    said = f"{label} tensor 'L0.ln1' shaped (63,), the extensions say (64,)"
    with pytest.raises(ValueError, match=f"^{re.escape(said)}$"):
        module.params_of(cfg, misshaped)


def _random_state(enc, rng):
    """A slot cache in which every slot holds something, the scratch slot too."""

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(0, 100, a.shape).astype(np.int32))
        return jnp.asarray(rng.standard_normal(a.shape).astype(np.float32), a.dtype)

    return jax.tree.map(fill, enc.init_state(enc.step_rows))


@pytest.mark.parametrize("arch", DECODERS)
def test_a_steps_basket_advance_leaves_padding_rows_and_unnamed_slots_untouched(arch):
    """Three live rows and 29 padding rows on the scratch slot, over a cache
    whose every slot holds something: each live row files its hidden state
    and view row at its own basket position and nowhere else; the scratch
    slot's basket and every slot no row names stay what they were, to the bit."""
    module, cfg, *_ = ARCHS[arch]
    enc = encoder_for(arch, dict({k: str(v) for k, v in cfg.to_extensions().items()}, dtype="float32").get)
    rng = np.random.default_rng(11)
    params = module.init_params(cfg, 7, jnp.float32)
    view = jnp.asarray(rng.standard_normal((384, cfg.hidden)).astype(np.float32) * 0.02)
    row_token = jnp.asarray(np.where(np.arange(384) < 300, np.arange(384), -1).astype(np.int32))
    state = _random_state(enc, rng)
    before = jax.tree.map(np.asarray, state)
    scratch, named = enc.step_rows, [5, 17, 2]
    slots = np.full(enc.step_rows, scratch, np.int32)
    slots[:3] = named
    lengths = np.zeros(enc.step_rows, np.int32)
    lengths[:3] = (6, 20, 11)
    live = np.arange(enc.step_rows) < 3
    step = np.zeros(enc.step_rows, np.int32)
    step[:3] = (0, 3, 1)
    state, out = enc.step(params, state, (view, 300, row_token), slots, lengths, live, step)
    after = jax.tree.map(np.asarray, state)
    assert sum(out.pop("head_rows")) == 384
    unnamed = [s for s in range(enc.step_rows + 1) if s not in named + [scratch]]
    for was, now in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(now[unnamed], was[unnamed])
    for key in ("z", "row", "step"):
        np.testing.assert_array_equal(after[key][scratch], before[key][scratch])
        np.testing.assert_array_equal(out[key][3:], np.broadcast_to(before[key][scratch], out[key][3:].shape))
        for i, s in enumerate(named):
            np.testing.assert_array_equal(after[key][s], out[key][i])
            others = np.arange(cfg.basket) != step[i]
            np.testing.assert_array_equal(after[key][s][others], before[key][s][others])
    assert list(after["step"][named, step[:3]]) == list(step[:3])
    assert all(0 <= r < 300 for r in after["row"][named, step[:3]])
