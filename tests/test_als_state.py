"""Unit tests for the shared ALS state/update-consumption logic and the
serving model's scoring variants (review regressions)."""

import numpy as np

from oryx_tpu.apps.als.serving import ALSServingModel
from oryx_tpu.apps.als.state import ALSState, apply_update_message
from oryx_tpu.common.artifact import ModelArtifact


def _model_message(features=2, implicit=True, xids=(), yids=()):
    art = ModelArtifact(app="als")
    art.set_extension("features", str(features))
    art.set_extension("implicit", "true" if implicit else "false")
    if xids:
        art.set_extension("XIDs", list(xids))
    if yids:
        art.set_extension("YIDs", list(yids))
    return art.to_string()


def test_apply_update_flips_implicit_without_discarding_vectors():
    st = apply_update_message(None, "MODEL", _model_message(implicit=True))
    st.x.set("u1", np.array([1.0, 0.0], dtype=np.float32))
    assert st.implicit is True
    st2 = apply_update_message(st, "MODEL", _model_message(implicit=False))
    assert st2 is st  # same rank: state retained
    assert st2.implicit is False  # but the feedback mode follows the model
    assert st2.x.get("u1") is not None


def test_apply_update_rank_change_resets_state():
    st = apply_update_message(None, "MODEL", _model_message(features=2))
    st2 = apply_update_message(st, "MODEL", _model_message(features=3))
    assert st2 is not st
    assert st2.features == 3


def test_apply_update_up_and_stale_rank_drop():
    st = apply_update_message(None, "MODEL", _model_message(features=2))
    st = apply_update_message(st, "UP", '["X","u9",[0.5,0.5]]')
    assert st.x.get("u9") is not None
    st = apply_update_message(st, "UP", '["X","u10",[0.5,0.5,0.5]]')  # rank 3
    assert st.x.get("u10") is None


def test_known_items_only_with_flag():
    st = apply_update_message(
        None, "MODEL", _model_message(), with_known_items=True
    )
    st = apply_update_message(
        st, "UP", '["X","u1",[1.0,0.0],["i1","i2"]]', with_known_items=True
    )
    assert st.get_known_items("u1") == {"i1", "i2"}
    st2 = apply_update_message(None, "MODEL", _model_message())
    st2 = apply_update_message(st2, "UP", '["X","u1",[1.0,0.0],["i1"]]')
    assert st2.get_known_items("u1") == set()


def test_top_n_cosine_ignores_norm():
    """/similarity must rank by direction, not raw dot: a huge-norm vector
    pointing elsewhere must lose to an aligned unit vector."""
    st = ALSState(2, True)
    st.y.set("aligned", np.array([0.9, 0.1], dtype=np.float32))
    st.y.set("big-off", np.array([0.0, 10.0], dtype=np.float32))
    model = ALSServingModel(st)
    q = np.array([1.0, 0.0], dtype=np.float32)
    dot_first = model.top_n(q, 2)[0][0]
    cos_first = model.top_n(q, 2, cosine=True)[0][0]
    assert dot_first in ("aligned", "big-off")  # dot may prefer the big norm
    assert cos_first == "aligned"


def test_corrupt_model_tensor_rejected_before_mutation():
    """A MODEL whose tensors disagree with its features extension must fail
    BEFORE retain/expected mutation — not leave a half-applied model."""
    import pytest
    from oryx_tpu.apps.als.state import apply_update_message as apply

    st = apply(None, "MODEL", _model_message(features=2, xids=("u1",), yids=("i1",)))
    st = apply(st, "UP", '["X","u1",[1.0,0.0]]')
    st = apply(st, "UP", '["Y","i1",[0.0,1.0]]')
    assert st.fraction_loaded() == 1.0

    import numpy as np
    from oryx_tpu.common.artifact import ModelArtifact
    bad = ModelArtifact(app="als", tensors={"Y": np.ones((2, 3), dtype=np.float32)})
    bad.set_extension("features", "2")  # claims rank 2, tensor is rank 3
    bad.set_extension("XIDs", [])
    bad.set_extension("YIDs", ["i1", "i2"])
    with pytest.raises(ValueError):
        apply(st, "MODEL", bad.to_string())
    # state untouched: still fully loaded with the old expectations
    assert st.fraction_loaded() == 1.0
    assert st.x.get("u1") is not None


def test_fraction_loaded_incremental_counters():
    """fraction_loaded must be O(1) and stay true under UP ingest, bulk
    loads, and model-swap retention (the gate runs per request)."""
    st = ALSState(2, implicit=True)
    assert st.fraction_loaded() == 0.0  # no model announced
    st.set_expected(["u1", "u2"], ["i1", "i2"])
    assert st.fraction_loaded() == 0.0
    st.set_x("u1", np.array([1.0, 0.0], dtype=np.float32))
    assert st.fraction_loaded() == 0.25
    st.set_x("u1", np.array([2.0, 0.0], dtype=np.float32))  # overwrite: no double count
    assert st.fraction_loaded() == 0.25
    st.set_y("i1", np.array([1.0, 0.0], dtype=np.float32))
    st.set_y("i2", np.array([0.0, 1.0], dtype=np.float32))
    assert st.fraction_loaded() == 0.75
    # unexpected id arriving via UP grows both have and total
    st.set_x("u3", np.array([0.5, 0.5], dtype=np.float32))
    assert abs(st.fraction_loaded() - 4 / 5) < 1e-9
    st.set_x("u2", np.array([0.5, 0.5], dtype=np.float32))
    assert st.fraction_loaded() == 1.0
    # swap retains a subset: counters recomputed
    st.set_expected(["u1"], ["i1"])
    st.retain_only({"u1"}, {"i1"})
    assert st.fraction_loaded() == 1.0


def test_bulk_set_matches_per_row_set():
    from oryx_tpu.apps.als.state import FactorStore

    rng = np.random.default_rng(0)
    m = rng.normal(size=(300, 4)).astype(np.float32)
    ids = [f"r{j}" for j in range(300)]
    a, b = FactorStore(4), FactorStore(4)
    for j, i in enumerate(ids):
        a.set(i, m[j])
    b.bulk_set(ids, m)
    ma, ia, _ = a.snapshot()
    mb, ib, _ = b.snapshot()
    assert ia == ib
    np.testing.assert_array_equal(ma, mb)
    # bulk overwrite of an existing subset
    b.bulk_set(["r5", "r7"], np.ones((2, 4), dtype=np.float32))
    assert b.get("r5").tolist() == [1, 1, 1, 1]
    assert len(b) == 300


def test_model_with_inline_tensors_counts_loaded():
    from oryx_tpu.common.artifact import ModelArtifact

    art = ModelArtifact(app="als")
    art.set_extension("features", "2")
    art.set_extension("implicit", "true")
    art.set_extension("XIDs", ["u1", "u2"])
    art.set_extension("YIDs", ["i1"])
    art.tensors = {
        "X": np.ones((2, 2), dtype=np.float32),
        "Y": np.ones((1, 2), dtype=np.float32),
    }
    st = apply_update_message(None, "MODEL", art.to_string())
    assert st.fraction_loaded() == 1.0


def test_nested_rescorer_query_does_not_deadlock_post_pool():
    """A rescorer that issues its own blocking top_n() runs on a post-pool
    thread; the nested query must not need the pool again (blocking top_n
    post-processes on the caller's thread) or a 1-thread pool deadlocks."""
    from concurrent.futures import ThreadPoolExecutor

    import oryx_tpu.serving.app as srv  # owns the shared post pool
    from oryx_tpu.apps.als.serving import ALSServingModel
    from oryx_tpu.apps.als.state import ALSState

    rng = np.random.default_rng(0)
    state = ALSState(4, implicit=True)
    state.y.bulk_set(
        [f"i{j}" for j in range(20)], rng.standard_normal((20, 4), dtype=np.float32)
    )
    state.x.bulk_set(["u0"], rng.standard_normal((1, 4), dtype=np.float32))
    state.set_expected(["u0"], [f"i{j}" for j in range(20)])
    model = ALSServingModel(state, sample_rate=1.0)

    class NestedRescorer:
        def __init__(self):
            self.nested_done = False

        def is_filtered(self, ident):
            return False

        def rescore(self, ident, score):
            if not self.nested_done:
                self.nested_done = True
                # nested blocking query from inside post-processing
                inner = model.top_n(np.ones(4, dtype=np.float32), 2)
                assert len(inner) == 2
            return score

    old = srv._POST_POOL
    srv._POST_POOL = ThreadPoolExecutor(max_workers=1, thread_name_prefix="t1")
    try:
        r = NestedRescorer()
        fut = model.top_n_async(
            np.ones(4, dtype=np.float32), 3, rescorer=r
        )
        pairs = fut.result(timeout=30)
        assert len(pairs) == 3 and r.nested_done
    finally:
        srv._POST_POOL.shutdown(wait=False)
        srv._POST_POOL = old


def test_batch_update_messages_byte_parity():
    """The batched UP-message builder must produce byte-identical payloads
    to the single-message path (the bus is a wire format; two encoders
    must not drift)."""
    from oryx_tpu.apps.als.common import (
        batch_update_messages,
        x_update_message,
        y_update_message,
    )

    rng = np.random.default_rng(12)
    v = rng.standard_normal((5, 7)) * np.array([1e-8, 1e-3, 1.0, 1e3, 1e7])[:, None]
    ids = [f"u{j}" for j in range(5)]
    known = [[f"i{j}", "i0"] for j in range(5)]
    assert batch_update_messages("X", ids, v, known) == [
        x_update_message(ids[j], v[j], known[j]) for j in range(5)
    ]
    assert batch_update_messages("Y", ids, v) == [
        y_update_message(ids[j], v[j]) for j in range(5)
    ]
    assert batch_update_messages("X", [], np.zeros((0, 3))) == []


def test_factor_store_get_many_matches_get():
    from oryx_tpu.apps.als.state import ALSState

    rng = np.random.default_rng(4)
    st = ALSState(3, implicit=True)
    st.x.bulk_set(["a", "b", "c"], rng.standard_normal((3, 3), dtype=np.float32))
    mat, present = st.x.get_many(["b", "nope", "a", "b"])
    assert present.tolist() == [True, False, True, True]
    np.testing.assert_array_equal(mat[0], st.x.get("b"))
    np.testing.assert_array_equal(mat[2], st.x.get("a"))
    np.testing.assert_array_equal(mat[3], st.x.get("b"))
    np.testing.assert_array_equal(mat[1], np.zeros(3, dtype=np.float32))
    # empty input
    mat, present = st.x.get_many([])
    assert mat.shape == (0, 3) and present.shape == (0,)


def test_chunked_device_view_serves_identically(monkeypatch):
    """Models above the chunking threshold serve through a ChunkedMatrix
    device view (bounded per-program shapes — a single (20M, 250) bf16
    operand is 10 GB of a 16 GB chip): /recommend and cosine
    /similarity results must be identical to the single-array view."""
    import numpy as np

    import oryx_tpu.ops.transfer as transfer
    from oryx_tpu.ops.transfer import ChunkedMatrix

    rng = np.random.default_rng(8)
    n, k = 300, 8

    def build():
        st = ALSState(k, True)
        for i in range(n):
            st.y.set(f"i{i}", rng.standard_normal(k).astype(np.float32))
        return ALSServingModel(st)

    rng = np.random.default_rng(8)
    plain = build()
    # materialize plain's views BEFORE lowering the thresholds: the view
    # builds lazily on first use, and a late build would silently make
    # this a chunked-vs-chunked self-comparison
    assert not isinstance(plain._y_view_full()[0], ChunkedMatrix)
    plain._y_unit_view()
    rng = np.random.default_rng(8)
    monkeypatch.setattr(transfer, "CHUNKED_OVER_BYTES", 1024)
    monkeypatch.setattr(transfer, "CHUNK_TARGET_BYTES", 2048)
    chunked = build()

    assert isinstance(chunked._y_view_full()[0], ChunkedMatrix)
    # 300 rows round up to their one item block (512), in chunks of 128
    # rows, each stored lane-padded: the kernel's shape
    assert chunked._y_view_full()[0].shape == (512, 128)
    assert [c.shape for c in chunked._y_view_full()[0].chunks] == [(128, 128)] * 4
    q = rng.standard_normal(k).astype(np.float32)
    assert chunked.top_n(q, 12) == plain.top_n(q, 12)
    assert chunked.top_n(q, 12, cosine=True) == plain.top_n(q, 12, cosine=True)
