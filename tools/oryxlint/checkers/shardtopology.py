"""Shard-topology vocabulary checker (rule ``shard-topology``).

PR 11 wired the shard count through four surfaces — the serving view
(``oryx.serving.api.sync.shard-count``), the fleet overlay
(``oryx.fleet.shards``), the train mesh (``oryx.batch.train.shards``),
and the ``/healthz`` ``shards`` field the front's prober reads into
``ReplicaInfo.shards`` (mis-sharded replicas get ejected). Each of those
was hand-checked in review; a new shard-bearing surface that wires only
some of them ships a replica the front cannot vet.

The rule pins the vocabulary both ways:

- every **known** shard surface must still be present at its expected
  site (config key read somewhere + declared; healthz emits ``shards``
  next to its shard-count read; ``ReplicaInfo`` declares ``shards``;
  the front parses the probe body's ``shards``; the supervisor overlay
  carries the sync key) — a half-unwired removal is as broken as a
  half-wired addition;
- every shard-shaped config key read anywhere (``*.shards`` /
  ``*.shard-count``) must be one of the known keys — a NEW shard
  surface fails loudly here until it is added to ``KNOWN_SHARD_KEYS``
  *and* wired through the same vocabulary.

Site checks apply only when their file exists (fixture trees exercise
single surfaces); the key-vocabulary check applies to any tree.
"""

from __future__ import annotations

import re

from tools.oryxlint.core import Checker, Finding, Project

# every config key that carries a shard count, with the wiring it rides
KNOWN_SHARD_KEYS = (
    "oryx.serving.api.sync.shard-count",
    "oryx.fleet.shards",
    "oryx.batch.train.shards",
)

# a Config accessor read of a shard-shaped key
SHARD_KEY_READ = re.compile(
    r"\.(?:get|get_string|get_int|get_float|get_bool|get_list|get_config|has)"
    r"\(\s*[bru]?[\"'](oryx\.[A-Za-z0-9_.\-]*(?:\.shards|shard-count))[\"']"
)

HEALTHZ_FILE = "oryx_tpu/serving/resources/common.py"
FRONT_FILE = "oryx_tpu/fleet/front.py"
SUPERVISOR_FILE = "oryx_tpu/fleet/supervisor.py"


class ShardTopologyChecker(Checker):
    name = "shardtopology"
    rules = {
        "shard-topology": (
            "a shard-count surface is half-wired: a new shard config key "
            "outside the known vocabulary, or a known surface (healthz "
            "shards field, ReplicaInfo.shards, front probe parse, "
            "supervisor overlay) has gone missing"
        ),
    }
    severities = {"shard-topology": "error"}
    fix_hints = {
        "shard-topology": (
            "wire the surface end to end — config key, /healthz shards, "
            "ReplicaInfo.shards + front probe, supervisor overlay — and "
            "register the key in checkers/shardtopology.py KNOWN_SHARD_KEYS"
        ),
    }

    def check(self, project: Project) -> list[Finding]:
        findings: list[Finding] = []
        mods = {m.relpath: m for m in project.modules}
        texts = {m.relpath: m.text for m in project.modules}

        # 1) no shard-shaped key outside the known vocabulary
        reads: dict[str, tuple[str, int]] = {}
        for rel, text in sorted(texts.items()):
            if not rel.startswith("oryx_tpu"):
                continue
            for m in SHARD_KEY_READ.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                reads.setdefault(m.group(1), (rel, line))
                if m.group(1) not in KNOWN_SHARD_KEYS:
                    findings.append(Finding(
                        rel, line, "shard-topology",
                        f"{m.group(1)}: shard-bearing config key outside "
                        "the known vocabulary — a new shard surface must "
                        "wire /healthz shards, ReplicaInfo.shards and the "
                        "supervisor overlay, then register in "
                        "KNOWN_SHARD_KEYS",
                    ))

        # 2) known keys must still be read somewhere (only when the tree
        # has any shard vocabulary at all — a fixture tree with zero
        # shard reads is not a regressed fleet)
        if reads:
            for key in KNOWN_SHARD_KEYS:
                if key not in reads:
                    findings.append(Finding(
                        "oryx_tpu", 1, "shard-topology",
                        f"{key}: known shard surface no longer read by any "
                        "Config accessor — the fleet/serving/train shard "
                        "wiring lost a leg",
                    ))

        # 3) per-site wiring, checked when the site file exists
        hz = mods.get(HEALTHZ_FILE)
        if hz is not None and "shard-count" in hz.text:
            if '"shards"' not in hz.text:
                findings.append(Finding(
                    HEALTHZ_FILE, 1, "shard-topology",
                    "reads the sync shard-count but never emits the "
                    '/healthz "shards" field — the front cannot vet this '
                    "replica's topology (mis-sharded replicas route)",
                ))
        front = mods.get(FRONT_FILE)
        if front is not None:
            if not _class_has_attr(front, "ReplicaInfo", "shards"):
                findings.append(Finding(
                    FRONT_FILE, 1, "shard-topology",
                    "ReplicaInfo no longer carries `shards` — the probe "
                    "cannot record replica topology, so shard-topology "
                    "ejection is dead",
                ))
            if '"shards"' not in front.text:
                findings.append(Finding(
                    FRONT_FILE, 1, "shard-topology",
                    'the front never parses the probe body\'s "shards" '
                    "field — ReplicaInfo.shards can never be populated",
                ))
        sup = mods.get(SUPERVISOR_FILE)
        if sup is not None and "oryx.fleet.shards" in sup.text:
            if "oryx.serving.api.sync.shard-count" not in sup.text:
                findings.append(Finding(
                    SUPERVISOR_FILE, 1, "shard-topology",
                    "reads oryx.fleet.shards but never overlays "
                    "oryx.serving.api.sync.shard-count onto replicas — "
                    "the fleet knob would be a silent no-op on every child",
                ))
        return findings


def _class_has_attr(mod, cls_name: str, attr: str) -> bool:
    import ast

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef) and node.name == cls_name:
            for sub in ast.walk(node):
                if isinstance(sub, ast.AnnAssign):
                    t = sub.target
                    if isinstance(t, ast.Name) and t.id == attr:
                        return True
                    if (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                        and t.attr == attr
                    ):
                        return True
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, ast.Name) and t.id == attr:
                            return True
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and t.attr == attr
                        ):
                            return True
    return False
