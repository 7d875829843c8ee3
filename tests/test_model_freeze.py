"""The cyclic collector stops walking a loaded model (ISSUE 29): once a
model's id maps and known-item sets are loaded and its view is built, and
again after each generation, the serving process freezes them out of the
collector's sight (serving/viewsync.py freeze_loaded_model)."""

from __future__ import annotations

import gc
import time

import numpy as np
import pytest

from oryx_tpu.apps.als.serving import ALSServingModelManager
from oryx_tpu.common.artifact import ModelArtifact
from oryx_tpu.common.config import load_config

N_IDS = 200_000


@pytest.fixture(autouse=True)
def _thaw():
    yield
    gc.unfreeze()  # the rest of this worker's tests get their collector back


def _model_message(gen: int, n_items: int, features: int = 4) -> str:
    rng = np.random.default_rng(gen)
    n_users = 1000
    art = ModelArtifact(
        "als",
        extensions={"features": str(features), "implicit": "true"},
        content={"knownItems": {
            f"u{u}": [f"g{gen}i{j}" for j in range(u, u + 20)] for u in range(n_users)
        }},
        tensors={
            "X": rng.standard_normal((n_users, features), dtype=np.float32),
            "Y": rng.standard_normal((n_items, features), dtype=np.float32),
        },
    )
    art.set_extension("XIDs", [f"u{j}" for j in range(n_users)])
    art.set_extension("YIDs", [f"g{gen}i{j}" for j in range(n_items)])
    return art.to_string()


def _containers(state) -> dict:
    """The model's large containers that the collector tracks."""
    out = {
        "y._rev": state.y._rev, "expected_y": state.expected_y,
        "known_items": state.known_items, "x._rev": state.x._rev,
    }
    return {name: c for name, c in out.items() if gc.is_tracked(c)}


def _seen_by_collector(containers: dict) -> set:
    """Which of them a collection would visit: gc.get_objects() lists what
    the generations hold and leaves the permanent generation out."""
    ids = {id(c): name for name, c in containers.items()}
    return {ids[id(o)] for o in gc.get_objects() if id(o) in ids}


def _full_collection_s() -> float:
    t0 = time.perf_counter()
    gc.collect()
    return time.perf_counter() - t0


def test_loaded_model_is_frozen_out_of_the_collector_for_each_generation():
    manager = ALSServingModelManager(load_config(overlay={"oryx.id": "freeze"}))
    vec = np.ones(4, dtype=np.float32)
    manager.consume_key_message("MODEL", _model_message(1, N_IDS))
    model = manager.get_model()
    tracked = _containers(model.state)
    assert {"y._rev", "expected_y", "known_items"} <= set(tracked)
    # loaded, the view not built yet: the collector still walks all of it
    assert _seen_by_collector(tracked) == set(tracked)
    walked_s = min(_full_collection_s() for _ in range(3))

    assert len(model.top_n(vec, 5)) == 5  # the first request builds the view
    assert model.freeze_due is False
    assert gc.get_freeze_count() > N_IDS // 1000  # the heap alive at the build
    assert _seen_by_collector(tracked) == set()
    assert _seen_by_collector({"view ids": model._device_view[1]}) == set()
    frozen_s = min(_full_collection_s() for _ in range(3))
    # a forced full collection is no longer in proportion to the ids (the
    # lists and sets alone are over 400,000 entries to visit)
    assert frozen_s < walked_s, (frozen_s, walked_s)

    # ids the speed layer adds later enter the frozen containers unwalked
    manager.consume_key_message("UP", '["Y","late",[0.5,0.5,0.5,0.5]]')
    assert "late" in model.state.expected_y
    assert _seen_by_collector(_containers(model.state)) == set()

    # a generation of the same rank keeps the model and swaps the id maps:
    # what is left of the old generation is collected once, the new one frozen
    manager.consume_key_message("MODEL", _model_message(2, N_IDS // 2))
    assert manager.get_model() is model and model.freeze_due is True
    swapped = _containers(model.state)
    assert _seen_by_collector(swapped) >= {"y._rev", "expected_y"}
    # a query observes the drift and the resync thread rebuilds, then freezes
    # (the flag clears before the freeze, which thaws first: wait for its end)
    deadline = time.monotonic() + 60
    while (model.freeze_due or _seen_by_collector(swapped)) and time.monotonic() < deadline:
        model.top_n(vec, 5)
        time.sleep(0.05)
    assert model.freeze_due is False
    assert len(model._device_view[1]) == N_IDS // 2
    assert _seen_by_collector(swapped) == set()
    manager.close()


def test_seq_model_is_frozen_once_its_view_is_built():
    from oryx_tpu.apps.seq.serving import SeqServingModel
    from oryx_tpu.apps.seq.state import SeqState

    st = SeqState(8, 3)
    st.items.bulk_set(
        [f"i{j}" for j in range(5000)],
        np.random.default_rng(1).standard_normal((5000, 8)).astype(np.float32),
    )
    model = SeqServingModel(st)
    assert _seen_by_collector({"rev": st.items._rev}) == {"rev"}
    model._view()
    assert model.freeze_due is False
    assert _seen_by_collector({"rev": st.items._rev, "ids": model._device_view[1]}) == set()
