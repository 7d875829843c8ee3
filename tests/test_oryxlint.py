"""oryxlint: per-rule positive/negative fixtures + the tier-1 whole-tree
gate (zero unsuppressed findings on the current tree).

Each checker is proven in both directions: a small fixture snippet that
MUST produce the finding, and the adjacent compliant form that must not.
The whole-tree run is the ratchet — new code that blocks an event loop,
touches guarded state without its lock, side-effects inside a jitted
function, or drifts config/metric/ratchet vocabulary fails tier-1.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.oryxlint.core import Project, run_lint  # noqa: E402
from tools.oryxlint.callgraph import ProjectIndex  # noqa: E402
from tools.oryxlint.checkers.eventloop import EventLoopChecker  # noqa: E402
from tools.oryxlint.checkers.jaxpurity import JaxPurityChecker  # noqa: E402
from tools.oryxlint.checkers.lockdiscipline import LockDisciplineChecker  # noqa: E402
from tools.oryxlint.checkers.lockorder import (  # noqa: E402
    LockOrderChecker, load_canonical_order,
)
from tools.oryxlint.checkers.paramflow import ParamFlowChecker  # noqa: E402
from tools.oryxlint.checkers.placement import PlacementChecker  # noqa: E402
from tools.oryxlint.checkers.shardtopology import ShardTopologyChecker  # noqa: E402


def _lint_fixture(tmp_path, source: str, checkers) -> tuple[list, list]:
    pkg = tmp_path / "oryx_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(textwrap.dedent(source), encoding="utf-8")
    return run_lint(tmp_path, checkers=checkers)


def _rules(findings) -> list[str]:
    return [f.rule for f in findings]


# -- event-loop blocking-call detector ---------------------------------------


def test_blocking_call_in_async_def_caught(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import time

        async def handler():
            time.sleep(1)
    """, [EventLoopChecker()])
    assert _rules(active) == ["blocking-call-on-loop"]
    assert "time.sleep" in active[0].message


def test_blocking_call_reached_transitively(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import subprocess

        def helper():
            subprocess.run(["true"])

        async def handler():
            helper()
    """, [EventLoopChecker()])
    assert _rules(active) == ["blocking-call-on-loop"]
    assert "handler -> helper" in active[0].message


def test_nonblocking_route_handler_is_a_root(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        def register(app):
            @app.route("GET", "/x", nonblocking=True)
            def handler(a, req):
                a.input_producer.send("k", "line")

            @app.route("POST", "/y")
            def worker_handler(a, req):
                a.input_producer.send("k", "line")  # worker pool: legal
    """, [EventLoopChecker()])
    assert len(active) == 1
    assert active[0].rule == "blocking-call-on-loop"
    assert "producer" in active[0].message


def test_offloop_annotation_honored(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import time

        def sampler():  # oryxlint: offloop (dedicated thread)
            time.sleep(2)

        async def handler():
            sampler()
    """, [EventLoopChecker()])
    assert active == []


# -- lock discipline ----------------------------------------------------------


_LOCK_FIXTURE = """
    import threading


    class Shared:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self.n = 0  # guarded-by: _lock
            self.view = None  # guarded-by: _lock (writes)

        def locked_write(self):
            with self._lock:
                self.n += 1

        def cond_alias_write(self):
            with self._cond:
                self.n += 1

        def lockfree_snapshot_read(self):
            return self.view

        def contract(self):  # oryxlint: holds=_lock
            return self.n
"""


def test_with_lock_and_alias_and_writes_qualifier_pass(tmp_path):
    active, _ = _lint_fixture(tmp_path, _LOCK_FIXTURE, [LockDisciplineChecker()])
    assert active == []


def test_guarded_by_violation_caught(tmp_path):
    active, _ = _lint_fixture(tmp_path, _LOCK_FIXTURE + """
        def racy(self):
            self.n += 1

    Shared.racy = racy
    """, [LockDisciplineChecker()])
    # note: module-level function attached post-hoc is outside the class —
    # the in-class violation form is what we assert on below
    active2, _ = _lint_fixture(tmp_path, _LOCK_FIXTURE.replace(
        "def contract(self):  # oryxlint: holds=_lock",
        "def racy(self):\n            self.n += 1\n\n        def contract(self):  # oryxlint: holds=_lock",
    ), [LockDisciplineChecker()])
    assert _rules(active2) == ["guarded-by"]
    assert "self.n" in active2[0].message


def test_closure_does_not_inherit_held_lock(tmp_path):
    active, _ = _lint_fixture(tmp_path, _LOCK_FIXTURE.replace(
        "def contract(self):  # oryxlint: holds=_lock",
        "def leak(self):\n"
        "            with self._lock:\n"
        "                return lambda: self.n\n\n"
        "        def contract(self):  # oryxlint: holds=_lock",
    ), [LockDisciplineChecker()])
    assert _rules(active) == ["guarded-by"]


def test_writes_qualifier_still_checks_stores(tmp_path):
    active, _ = _lint_fixture(tmp_path, _LOCK_FIXTURE.replace(
        "def contract(self):  # oryxlint: holds=_lock",
        "def unlocked_swap(self):\n            self.view = ()\n\n"
        "        def contract(self):  # oryxlint: holds=_lock",
    ), [LockDisciplineChecker()])
    assert _rules(active) == ["guarded-by"]
    assert "self.view" in active[0].message


# -- jax purity / donation ----------------------------------------------------


def test_jit_side_effect_caught(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import jax

        @jax.jit
        def impure(x):
            print("tracing")
            return x
    """, [JaxPurityChecker()])
    assert _rules(active) == ["jit-side-effect"]


def test_jit_closed_over_mutation_and_rng_caught(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import numpy as np
        import jax

        hits = []

        @jax.jit
        def impure(x):
            hits.append(1)
            return x + np.random.rand()
    """, [JaxPurityChecker()])
    assert sorted(_rules(active)) == ["jit-side-effect", "jit-side-effect"]


def test_pure_jit_and_pallas_kernel_pass(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("k",))
        def pure(x, k):
            local = []
            local.append(k)  # local mutation is fine
            return jnp.sum(x) + len(local)

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        def build(pl):
            return pl.pallas_call(_kernel)
    """, [JaxPurityChecker()])
    assert active == []


def test_donation_reuse_caught_and_rebind_allowed(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        from functools import partial

        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def donated(buf, row):
            return buf + row

        def bug(a, b):
            out = donated(a, b)
            return out + a

        def carry_ok(a, b):
            a = donated(a, b)
            return a + b
    """, [JaxPurityChecker()])
    assert _rules(active) == ["donation-reuse"]
    assert "'a'" in active[0].message


def test_donates_annotation_conditional_wrapper(tmp_path):
    """`donates=0 when donate` (the scatter_rows contract): reuse after a
    donate=True call is flagged; the non-donating form is free."""
    active, _ = _lint_fixture(tmp_path, """
        def scatter(buf, rows, *, donate=False):  # oryxlint: donates=0 when donate
            return buf

        def serving_path_bug(view, rows):
            out = scatter(view, rows, donate=True)
            return out, view  # in-flight dispatches read a deleted buffer

        def double_buffer_ok(view, rows):
            out = scatter(view, rows)
            return out, view
    """, [JaxPurityChecker()])
    assert _rules(active) == ["donation-reuse"]
    assert "'view'" in active[0].message


def test_donated_wrapper_assignment_form_detected(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        from functools import partial

        import jax

        def _train(x, y, carry):
            return carry + x + y

        train_donated = partial(jax.jit, donate_argnums=(2,))(_train)

        def bug(x, y, c):
            out = train_donated(x, y, c)
            return out + c
    """, [JaxPurityChecker()])
    assert _rules(active) == ["donation-reuse"]


# -- suppression syntax -------------------------------------------------------


def test_suppression_comment_honored(tmp_path):
    active, suppressed = _lint_fixture(tmp_path, """
        import time

        async def handler():
            time.sleep(1)  # oryxlint: disable=blocking-call-on-loop
    """, [EventLoopChecker()])
    assert active == []
    assert _rules(suppressed) == ["blocking-call-on-loop"]


def test_unknown_rule_suppression_rejected(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        x = 1  # oryxlint: disable=no-such-rule
    """, [EventLoopChecker()])
    assert _rules(active) == ["unknown-rule"]
    assert "no-such-rule" in active[0].message


def test_unknown_rule_finding_is_not_suppressible(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        x = 1  # oryxlint: disable=unknown-rule,bogus-rule
    """, [EventLoopChecker()])
    assert "unknown-rule" in _rules(active)


# -- consistency rules through oryxlint ---------------------------------------


def test_config_rule_catches_undeclared_key(tmp_path):
    from tools.oryxlint.checkers import consistency

    ref_dir = tmp_path / "oryx_tpu" / "common"
    ref_dir.mkdir(parents=True)
    (ref_dir / "reference.conf").write_text(
        "oryx { id = \"x\" }\n", encoding="utf-8"
    )
    (tmp_path / "oryx_tpu" / "mod.py").write_text(
        'v = config.get_int("oryx.not.declared", 1)\n', encoding="utf-8"
    )
    findings = consistency.config_findings(tmp_path)
    assert ["config-keys"] == [f.rule for f in findings]
    assert "oryx.not.declared" in findings[0].message


def test_metric_rule_catches_undocumented_name(tmp_path):
    from tools.oryxlint.checkers import consistency

    (tmp_path / "oryx_tpu").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "oryx_tpu" / "mod.py").write_text(
        'NAME = "oryx_undocumented_total"\n', encoding="utf-8"
    )
    (tmp_path / "docs" / "observability.md").write_text(
        "| `oryx_ghost_metric` | gone |\nscore_mode\n", encoding="utf-8"
    )
    findings = consistency.metric_findings(tmp_path)
    msgs = " | ".join(f.message for f in findings)
    assert "oryx_undocumented_total" in msgs  # code -> docs direction
    assert "oryx_ghost_metric" in msgs        # docs -> code reverse rule


# -- dataflow: param-dropped (the PR 11 dropped-shard_mesh class) -------------


def test_param_dropped_catches_resume_path_drop(tmp_path):
    """The ancestor bug: a checkpointed train path accepts the sharding
    config but forwards it only on the fresh path — the resume path
    silently trains unsharded."""
    active, _ = _lint_fixture(tmp_path, """
        def train_chunk(y, shard_mesh=None):
            return compute(y, shard_mesh)

        def train_checkpointed(data, config):
            shards = config.get_int("oryx.batch.train.shards", 1)
            if data.resume:
                y = load_ckpt()
                return train_chunk(y)  # drops shards on the resume path
            return train_chunk(data.y0, shard_mesh=shards)
    """, [ParamFlowChecker()])
    assert _rules(active) == ["param-dropped"]
    assert "oryx.batch.train.shards" in active[0].message
    assert "dropped on the path returning" in active[0].message


def test_param_dropped_interprocedural_callee_drop(tmp_path):
    """Handing the value to a wrapper does not launder it: the engine
    recurses into the callee's parameter with the same every-path rule."""
    active, _ = _lint_fixture(tmp_path, """
        def inner(y, shard_mesh=None):
            if y is None:
                return base(y)
            return base(y, shard_mesh)

        def outer(config, y):
            sm = config.get_int("oryx.batch.train.shards", 1)
            return inner(y, shard_mesh=sm)
    """, [ParamFlowChecker()])
    assert _rules(active) == ["param-dropped"]
    assert "inner" in active[0].message
    assert "does not reach a sink on every path" in active[0].message


def test_param_dropped_through_partial_rebind_offsets_positionals(tmp_path):
    """A call through a `partial(...)` alias binds positionals starting
    at the first UNBOUND callee parameter: `g = partial(train, data)`
    then `g(n)` reaches train's SECOND parameter — whose resume path
    drops it (flagged); the compliant callee stays clean."""
    active, _ = _lint_fixture(tmp_path, """
        from functools import partial

        def train(data, shards=1):
            if data is None:
                return fit(data)
            return fit(data, shards)

        def run(config, data):
            g = partial(train, data)
            n = config.get_int("oryx.batch.train.shards", 1)
            return g(n)

        def train_ok(data, shards=1):
            return fit(data, shards)

        def run_ok(config, data):
            h = partial(train_ok, data)
            n = config.get_int("oryx.batch.train.shards", 1)
            return h(n)
    """, [ParamFlowChecker()])
    assert _rules(active) == ["param-dropped"]
    assert "'shards'" in active[0].message and "train" in active[0].message


def test_param_dropped_compliant_forms_pass(tmp_path):
    """Guard-on-the-value returns, attribute stores, full threading, and
    the `# oryxlint: sink` terminal-read annotation are all clean."""
    active, _ = _lint_fixture(tmp_path, """
        class Layer:
            def adopt(self, config):
                n = config.get_int("oryx.batch.train.shards", 1)
                self.shards = n

        def guarded(data, config):
            shards = config.get_int("oryx.batch.train.shards", 1)
            if shards <= 1:
                return plain(data)
            return sharded(data, shards)

        def terminal(config):
            n = config.get_int("oryx.batch.train.shards", 1)  # oryxlint: sink
            return 0
    """, [ParamFlowChecker()])
    assert active == []


def test_param_dropped_never_consumed_flagged_at_read(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        def dead_read(config):
            n = config.get_int("oryx.fleet.replica.count", 2)
            return 0
    """, [ParamFlowChecker()])
    assert _rules(active) == ["param-dropped"]
    assert "never reaches a sink" in active[0].message


# -- dataflow: device-placement (the PR 11 uncommitted-device_put class) ------


def test_device_placement_uncommitted_store_caught(tmp_path):
    """The ancestor bug: shards staged under a default_device context
    only — uncommitted buffers silently migrate to device 0 on first
    use, recreating the multi-chip OOM sharding exists to prevent."""
    active, _ = _lint_fixture(tmp_path, """
        import jax

        class ShardedView:
            def __init__(self, host, dev):
                with jax.default_device(dev):
                    staged = jax.device_put(host)  # no explicit device
                self.view = staged

        class CommittedView:
            def __init__(self, host, dev):
                self.view = jax.device_put(host, dev)
    """, [PlacementChecker()])
    assert _rules(active) == ["device-placement"]
    assert "uncommitted" in active[0].message
    assert "self.view" in active[0].message


def test_device_placement_tracks_through_helper_returns(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import jax

        def stage(host):
            return jax.device_put(host)

        class View:
            def __init__(self, host):
                self.y = stage(host)
    """, [PlacementChecker()])
    assert _rules(active) == ["device-placement"]


def test_device_placement_mesh_shard_mesh_pair_caught(tmp_path):
    """Both layouts constructed and passed to one train call: the loud
    runtime raise PR 11 added, now caught before runtime. Wrapper
    forwarding and the conditional-exclusivity idiom stay clean."""
    active, _ = _lint_fixture(tmp_path, """
        def pair_bug(data, make_mesh, make_shard):
            mesh = make_mesh(2)
            sm = make_shard(2)
            return train_als(data, mesh=mesh, shard_mesh=sm)

        def wrapper_ok(data, mesh=None, shard_mesh=None):
            return train_als(data, mesh=mesh, shard_mesh=shard_mesh)

        def conditional_ok(data, make_mesh, shard_mesh=None):
            return train_als_warm(
                data,
                mesh=None if shard_mesh is not None else make_mesh(),
                shard_mesh=shard_mesh,
            )
    """, [PlacementChecker()])
    assert _rules(active) == ["device-placement"]
    assert "mutually exclusive" in active[0].message


# -- dataflow: lock-order (the PR 11 convention-only multi-lock class) --------


_INVERTED_LOCKS = """
    import threading

    class Batcher:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    return 1

        def inverted(self):
            with self._b:
                with self._a:
                    return 2
"""


def test_lock_order_inverted_pair_caught(tmp_path):
    active, _ = _lint_fixture(tmp_path, _INVERTED_LOCKS, [LockOrderChecker()])
    assert _rules(active) == ["lock-order"]
    assert "inverted lock pair" in active[0].message
    assert "deadlock" in active[0].message


def test_lock_order_consistent_nesting_passes(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        import threading

        class Batcher:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        return 1

            def also_forward(self):
                with self._a:
                    return self._under_a()

            def _under_a(self):  # oryxlint: holds=_a
                with self._b:
                    return 2
    """, [LockOrderChecker()])
    assert active == []


def test_lock_order_transitive_edge_through_call(tmp_path):
    """The acquisition graph crosses function boundaries: holding A and
    calling a helper that takes B in the opposite order elsewhere is the
    same deadlock, invisible to any single-function review."""
    active, _ = _lint_fixture(tmp_path, """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    return self.helper_b()

            def helper_b(self):
                with self._b:
                    return 1

            def other_thread(self):
                with self._b:
                    with self._a:
                        return 2
    """, [LockOrderChecker()])
    assert _rules(active) == ["lock-order"]


def test_lock_order_canonical_order_violation(tmp_path):
    """An edge going backwards against lockorder.toml fails even before
    the inverse edge lands — the second half of a deadlock should never
    get written."""
    order = tmp_path / "lockorder.toml"
    order.write_text(
        'order = [\n  "Batcher._a",\n  "Batcher._b",\n]\n', encoding="utf-8"
    )
    active, _ = _lint_fixture(tmp_path, """
        import threading

        class Batcher:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def backwards(self):
                with self._b:
                    with self._a:
                        return 1
    """, [LockOrderChecker(order_file=order)])
    assert _rules(active) == ["lock-order"]
    assert "canonical order" in active[0].message


def test_committed_lockorder_toml_is_nonempty_and_ordered():
    """The committed canonical order exists and ends leaf-ward: shared
    observability locks (the metrics registry) come after the domain
    locks that call into them."""
    order = load_canonical_order()
    assert "MetricsRegistry._lock" in order
    assert order.index("MetricsRegistry._lock") == len(order) - 1
    for domain in ("ALSServingModel._sync_lock", "TopKBatcher._lock"):
        assert order.index(domain) < order.index("MetricsRegistry._lock")


# -- dataflow: shard-topology (the PR 11 half-wired-surface class) ------------


def test_shard_topology_new_key_flagged(tmp_path):
    active, _ = _lint_fixture(tmp_path, """
        def build(config):
            n = config.get_int("oryx.pod.shards", 1)
            return n
    """, [ShardTopologyChecker()])
    assert any(
        f.rule == "shard-topology" and "oryx.pod.shards" in f.message
        for f in active
    )


def test_shard_topology_half_wired_healthz_flagged(tmp_path):
    """The healthz resource reads the shard count but never emits the
    `shards` field — the front can no longer vet replica topology."""
    res = tmp_path / "oryx_tpu" / "serving" / "resources"
    res.mkdir(parents=True)
    (res / "common.py").write_text(textwrap.dedent("""
        def healthz(a):
            n = a.config.get_int("oryx.serving.api.sync.shard-count", 1)
            body = {"ok": True}
            return encode(body, n)
    """), encoding="utf-8")
    active, _ = run_lint(tmp_path, checkers=[ShardTopologyChecker()])
    assert any(
        f.rule == "shard-topology" and '"shards"' in f.message
        for f in active
    )


def test_shard_topology_fully_wired_fixture_passes(tmp_path):
    res = tmp_path / "oryx_tpu" / "serving" / "resources"
    res.mkdir(parents=True)
    (res / "common.py").write_text(textwrap.dedent("""
        def healthz(a):
            n = a.config.get_int("oryx.serving.api.sync.shard-count", 1)
            return {"ok": True, "shards": n}
    """), encoding="utf-8")
    fleet = tmp_path / "oryx_tpu" / "fleet"
    fleet.mkdir(parents=True)
    (fleet / "supervisor.py").write_text(textwrap.dedent("""
        def overlays(config):
            shards = config.get_int("oryx.fleet.shards", 1)
            return {"oryx.serving.api.sync.shard-count": shards}
    """), encoding="utf-8")
    (fleet / "front.py").write_text(textwrap.dedent("""
        class ReplicaInfo:
            def __init__(self):
                self.shards = None

        def probe(r, body):
            r.shards = body.get("shards")
    """), encoding="utf-8")
    (tmp_path / "oryx_tpu" / "batch.py").write_text(
        'def b(config):\n'
        '    n = config.get_int("oryx.batch.train.shards", 1)\n'
        '    return n\n',
        encoding="utf-8",
    )
    active, _ = run_lint(tmp_path, checkers=[ShardTopologyChecker()])
    assert active == []


# -- callgraph edge cases (PR 12 satellites) ----------------------------------


def _index(tmp_path, source: str) -> ProjectIndex:
    pkg = tmp_path / "oryx_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(textwrap.dedent(source), encoding="utf-8")
    return ProjectIndex(Project.load(tmp_path))


def test_callgraph_double_partial_resolves(tmp_path):
    idx = _index(tmp_path, """
        from functools import partial

        def base(a, b, c):
            return a

        once = partial(base, 1)
        twice = partial(partial(base, 1), 2)

        def caller():
            return twice(3) + once(2, 3)
    """)
    caller = idx.top_level[("oryx_tpu/mod.py", "caller")]
    import ast as _ast

    calls = [n for n in _ast.walk(caller.node) if isinstance(n, _ast.Call)]
    resolved = {t.name for c in calls for t in idx.resolve_call(caller, c)}
    assert resolved == {"base"}
    assert len(idx.partial_aliases) == 2


def test_callgraph_property_typed_receiver_resolves(tmp_path):
    """`self.store.refresh_view()` resolves through the @property's
    return annotation even when two classes define the method name (the
    unique-definition fallback cannot apply)."""
    idx = _index(tmp_path, """
        class Store:
            def refresh_view(self):
                return 1

        class Decoy:
            def refresh_view(self):
                return 2

        class Owner:
            def __init__(self, s: Store):
                self._s = s

            @property
            def store(self) -> Store:
                return self._s

            def go(self):
                return self.store.refresh_view()
    """)
    go = idx.classes["Owner"].methods["go"]
    import ast as _ast

    calls = [n for n in _ast.walk(go.node) if isinstance(n, _ast.Call)]
    targets = [t for c in calls for t in idx.resolve_call(go, c)]
    assert [t.cls for t in targets] == ["Store"]


def test_callgraph_lambda_call_sites_counted(tmp_path):
    idx = _index(tmp_path, """
        def g():
            return (lambda x: x)(3)
    """)
    g = idx.top_level[("oryx_tpu/mod.py", "g")]
    import ast as _ast

    for c in [n for n in _ast.walk(g.node) if isinstance(n, _ast.Call)]:
        idx.resolve_call(g, c)
    assert idx.stats["lambda_sites"] == 1
    assert idx.stats["call_sites"] >= 1


def test_cli_stats_prints_resolution_rate():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.oryxlint", "--stats"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "resolved" in proc.stdout and "lambda call site" in proc.stdout


# -- scope --------------------------------------------------------------------


def test_scope_names_only_what_the_tree_has():
    """A top-level file or a directory in the default scope that is gone
    would be skipped in silence and its rules with it."""
    from tools.oryxlint import core

    for name in core.SCOPE_TOP_FILES:
        assert (ROOT / name).is_file(), name
    for name in core.SCOPE_DIRS:
        assert (ROOT / name).is_dir(), name
    assert list(ROOT.glob(core.SCOPE_TOOL_GLOB))


# -- CLI ----------------------------------------------------------------------


def test_cli_json_and_changed_modes():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.oryxlint", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []
    assert "blocking-call-on-loop" in doc["rules"]

    proc = subprocess.run(
        [sys.executable, "-m", "tools.oryxlint", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    for rule in ("guarded-by", "jit-side-effect", "donation-reuse",
                 "config-keys", "metric-docs", "flight-events",
                 "param-dropped", "device-placement", "lock-order",
                 "shard-topology"):
        assert rule in proc.stdout
    assert "ratchet" not in proc.stdout


def test_json_findings_carry_severity_and_fix_hint(tmp_path):
    """The stable --json per-finding schema: path/line/rule/severity/
    fix_hint/message (tools/precommit.sh groups on these fields)."""
    active, _ = _lint_fixture(tmp_path, """
        def dead_read(config):
            n = config.get_int("oryx.fleet.replica.count", 2)
            return 0
    """, [ParamFlowChecker()])
    assert len(active) == 1
    d = active[0].as_dict()
    assert set(d) == {"path", "line", "rule", "severity", "fix_hint", "message"}
    assert d["rule"] == "param-dropped"
    assert d["severity"] == "error"
    assert "sink" in d["fix_hint"]


def test_precommit_script_clean_exit():
    """tools/precommit.sh consumes the --json schema and exits 0 on a
    clean (or unchanged) tree, with ruff optional."""
    proc = subprocess.run(
        ["sh", str(ROOT / "tools" / "precommit.sh")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "precommit:" in proc.stdout


# -- flight-events vocabulary rule (ISSUE 14) --------------------------------


def _flight_fixture(tmp_path, source: str, extra_doc_rows: str = ""):
    """Fixture tree for the flight-events rule: a module + a docs catalog
    that (by default) documents every registered kind."""
    import textwrap as _tw

    from oryx_tpu.common.flightrec import EVENT_KINDS
    from tools.oryxlint.checkers.consistency import flight_findings

    pkg = tmp_path / "oryx_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(_tw.dedent(source), encoding="utf-8")
    docs = tmp_path / "docs"
    docs.mkdir(exist_ok=True)
    rows = "\n".join(f"| `{k}` | x | x |" for k in sorted(EVENT_KINDS))
    (docs / "observability.md").write_text(
        "# Observability\n\n### Flight-recorder event catalog\n\n"
        "| Kind | Recorded by | Meaning |\n|---|---|---|\n"
        + rows + "\n" + extra_doc_rows + "\n\n## Next section\n",
        encoding="utf-8",
    )
    project = Project.load(tmp_path)
    return flight_findings(tmp_path, project)


def test_flight_unregistered_kind_at_call_site_caught(tmp_path):
    findings = _flight_fixture(tmp_path, """
        from oryx_tpu.common.flightrec import get_flightrec

        def f():
            get_flightrec().record(kind="ejectoin", replica="r0")
    """)
    assert [f.rule for f in findings] == ["flight-events"]
    assert "'ejectoin'" in findings[0].message
    assert findings[0].path == "oryx_tpu/mod.py"


def test_flight_registered_kind_passes(tmp_path):
    findings = _flight_fixture(tmp_path, """
        from oryx_tpu.common.flightrec import get_flightrec

        def f():
            get_flightrec().record(kind="ejection", replica="r0", port=1)
    """)
    assert findings == []


def test_flight_non_literal_kind_skipped(tmp_path):
    # confident-only, like the dataflow checkers: a kind that arrives
    # through a variable is not flagged
    findings = _flight_fixture(tmp_path, """
        from oryx_tpu.common.flightrec import get_flightrec

        def f(kind):
            get_flightrec().record(kind=kind)
    """)
    assert findings == []


def test_flight_doc_row_without_registered_kind_caught(tmp_path):
    findings = _flight_fixture(
        tmp_path, "x = 1\n", extra_doc_rows="| `ghost-kind` | x | x |"
    )
    assert len(findings) == 1
    assert "ghost-kind" in findings[0].message
    assert findings[0].path == "docs/observability.md"


def test_flight_missing_doc_row_caught(tmp_path):
    import textwrap as _tw

    from tools.oryxlint.checkers.consistency import flight_findings

    pkg = tmp_path / "oryx_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text("x = 1\n", encoding="utf-8")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(_tw.dedent("""
        ### Flight-recorder event catalog

        | Kind | x |
        |---|---|
        | `ejection` | x |
    """), encoding="utf-8")
    findings = flight_findings(tmp_path, Project.load(tmp_path))
    # every registered kind except `ejection` lacks a docs row
    from oryx_tpu.common.flightrec import EVENT_KINDS

    assert len(findings) == len(EVENT_KINDS) - 1
    assert all(f.rule == "flight-events" for f in findings)


def test_flight_catalog_and_docs_agree_on_the_real_tree():
    """Both directions on the committed tree: every registered kind has a
    docs row and vice versa (the whole-tree gate would catch this too —
    this pins the section parser itself against doc refactors)."""
    from oryx_tpu.common.flightrec import EVENT_KINDS
    from tools.oryxlint.checkers.consistency import flight_doc_kinds

    assert flight_doc_kinds(ROOT / "docs" / "observability.md") == set(
        EVENT_KINDS
    )


# -- the tier-1 whole-tree gate ----------------------------------------------


def test_whole_tree_is_clean():
    """`python -m tools.oryxlint` on the tree: zero unsuppressed findings.

    This is the ratchet the new checkers hold: event-loop discipline,
    guarded-by lock discipline, jit purity/donation, and the
    config/metric/ratchet consistency contracts, all at once. Suppressed
    findings are allowed (each carries an in-source justification), but
    every suppression must name a real rule (unknown-rule is active)."""
    active, suppressed = run_lint(ROOT)
    rendered = "\n".join(f.render() for f in active)
    assert active == [], f"oryxlint findings on the tree:\n{rendered}"
    # the tree currently carries a known, justified suppression budget;
    # growing it should be a conscious review decision, not drift
    assert len(suppressed) <= 8, [f.render() for f in suppressed]


def test_production_annotations_are_load_bearing():
    """The annotation seeding is real, not decorative: the threaded core
    declares guarded attributes, holds-contracts, and offloop proofs the
    checkers actually consume."""
    project = Project.load(ROOT)
    by_path = {m.relpath: m for m in project.modules}
    guarded_files = [
        "oryx_tpu/common/metrics.py",
        "oryx_tpu/common/perfstats.py",
        "oryx_tpu/common/tracing.py",
        "oryx_tpu/serving/batcher.py",
        "oryx_tpu/fleet/front.py",
        "oryx_tpu/fleet/supervisor.py",
        "oryx_tpu/apps/als/serving.py",
    ]
    for rel in guarded_files:
        assert by_path[rel].guarded_lines, f"{rel}: no guarded-by seeds"
    assert by_path["oryx_tpu/serving/server.py"].offloop_lines, (
        "the lag-sampler offloop proof (PR 7 bug class) is gone"
    )
    assert by_path["oryx_tpu/apps/als/serving.py"].holds_lines, (
        "the 'call under _sync_lock' contracts lost their holds= form"
    )
