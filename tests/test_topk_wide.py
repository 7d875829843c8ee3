"""The top-k kernel over a view wider than its scoped VMEM holds at the
smallest item block (ops/pallas_topk.py `scoped_vmem_limit`): at 3,584 bf16
features the working set of a 1,024-row item block and a 128-row query block
passes the 16 MiB the compiler gives a kernel that asks for none, so the
kernel asks for its working set; every narrower view asks for nothing, so
their programs are what they were. The chip's own compiler, without the chip,
refuses the wide kernel that asks for nothing."""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import pallas_topk as pt


@pytest.mark.parametrize(
    "features,itemsize,query_itemsize",
    [(256, 2, 2), (2048, 2, 2), (2560, 2, 2), (3072, 2, 2), (3584, 1, 4), (3072, 1, 4)],
    ids=["als_250", "sdar_joyai_2048", "jamba_2560", "trinity_3072", "int8_3584", "int8_3072"],
)
def test_a_view_whose_working_set_fits_asks_for_no_scoped_vmem(features, itemsize, query_itemsize):
    feat_pad = pt.lane_pad(features)
    block_b, block_i = pt.tuned_blocks(feat_pad, itemsize)
    assert pt.scoped_vmem_limit(block_b, block_i, feat_pad, itemsize, query_itemsize) is None


def test_the_widest_view_asks_for_its_working_set():
    block_b, block_i = pt.tuned_blocks(3584, 2)
    assert (block_b, block_i) == (128, 1024)  # the smallest item block the rule allows
    need = pt._working_set_bytes(block_b, block_i, 3584, 2, 2)
    assert need > 16 << 20
    limit = pt.scoped_vmem_limit(block_b, block_i, 3584, 2, 2)
    assert limit == need + (4 << 20) and limit < 32 << 20
    # the block rule itself is as it was: the query block sized at float32
    assert pt._working_set_bytes(block_b, block_i, 3584, 2) == pt._working_set_bytes(block_b, block_i, 3584, 2, 4)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("asks", [True, False], ids=["asks", "asks_for_nothing"])
def test_the_widest_kernel_compiles_for_a_v5e_when_it_asks(one_chip, asks, monkeypatch):
    """A 512-row dispatch over 163,840 x 3,584 bf16 rows (the served view of
    a 131,072-item catalog at that width): compiled by the chip's compiler it
    fits when the kernel asks for its working set, and runs out of scoped
    VMEM when it asks for none."""
    if not asks:
        monkeypatch.setattr(pt, "scoped_vmem_limit", lambda *a: None)
    jax.clear_caches()  # the kernel is traced again, with or without its ask
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    block_b, block_i = pt.tuned_blocks(3584, 2)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = pt._topk_pallas_jit.lower(
            sds((512, 3584), jnp.bfloat16), sds((163_840, 3584), jnp.bfloat16), None, sds((2,), jnp.int32),
            k=128, block_b=block_b, block_i=block_i, quantized=False, interpret=False,
        )
        if asks:
            assert "tpu_custom_call" in lowered.compile().as_text()
        else:
            with pytest.raises(Exception, match="vmem"):
                lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        monkeypatch.undo()
        jax.clear_caches()
