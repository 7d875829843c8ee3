"""Test harness bootstrap.

Mirrors the reference's test strategy (SURVEY.md §4): deterministic global
seed (OryxTest calls RandomManager.useTestSeed) and local stand-ins for the
distributed substrate — here a virtual 8-device CPU mesh via
xla_force_host_platform_device_count, the analogue of Spark master=local[3]
in AbstractLambdaIT.

The tests never need a chip: JAX_PLATFORMS=cpu is set here for this
process and every child it spawns, and repeated through jax.config.update
in case jax was imported before this file ran. XLA_FLAGS works via env
because the CPU client is created lazily on first backends() call. Pallas
kernels run with interpret=True. How the program runs on the chip is
chip_smoke.py's business (README "Running").
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from oryx_tpu.common.rng import RandomManager  # noqa: E402


@pytest.fixture(autouse=True)
def _deterministic_seed():
    RandomManager.use_test_seed(1234)
    yield
    RandomManager.clear_test_seed()
