"""Config-value dataflow checker (rule ``param-dropped``).

A config key read through a Config accessor into a variable represents
an operator's intent; a path that silently drops the value is the PR 11
``shard_mesh``-on-resume bug class — ``train_als_checkpointed`` accepted
``shard_mesh`` and forwarded it on the fresh path but not through its
resume chunks, so exactly the restarted long trains lost their sharding.

The rule: every ``x = config.get_*("oryx....")`` read must reach a sink
(call argument, attribute store, returned value, or control-flow use)
on **every** path of its function — and when it is handed to a project
function as a direct argument, the dataflow engine recurses into that
parameter with the same every-path requirement, so a wrapper in the
middle of the chain cannot absorb the value. ``# oryxlint: sink`` on a
use line declares an intentional terminal read.

Scope: modules under ``oryx_tpu/`` (tools read config through ad hoc
plumbing that is not long-lived wiring).
"""

from __future__ import annotations

import ast

from tools.oryxlint.callgraph import shared_index
from tools.oryxlint.core import Checker, Finding, Project
from tools.oryxlint.dataflow import Dataflow

ACCESSOR_NAMES = frozenset({
    "get", "get_string", "get_int", "get_float", "get_bool", "get_list",
    "get_config", "has",
})


def _accessor_key(node: ast.AST) -> str | None:
    """The literal oryx.* key of a Config accessor call, if this node is
    one."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
        return None
    if node.func.attr not in ACCESSOR_NAMES or not node.args:
        return None
    k = node.args[0]
    if isinstance(k, ast.Constant) and isinstance(k.value, str) and (
        k.value.startswith("oryx.")
    ):
        return k.value
    return None


def _own_nodes(fn):
    """Nodes at this function's own level — nested defs are their own
    FunctionInfo and report their own reads."""
    stack = list(fn.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


class ParamFlowChecker(Checker):
    name = "paramflow"
    rules = {
        "param-dropped": (
            "a config value read into a variable fails to reach a sink "
            "(call arg, attribute store, return) on every path of its "
            "function or of a callee it is handed to"
        ),
    }
    severities = {"param-dropped": "error"}
    fix_hints = {
        "param-dropped": (
            "thread the value through the dropping path (or annotate an "
            "intentional terminal read with `# oryxlint: sink`)"
        ),
    }

    def check(self, project: Project) -> list[Finding]:
        idx = shared_index(project)
        flow = Dataflow(idx)
        findings: list[Finding] = []
        seen: set[tuple[str, int, str]] = set()
        for fi in idx.functions:
            if not fi.module.relpath.startswith("oryx_tpu"):
                continue
            for stmt in _own_nodes(fi.node):
                if not (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                ):
                    continue
                key = None
                for sub in ast.walk(stmt.value):
                    key = _accessor_key(sub)
                    if key is not None:
                        break
                if key is None:
                    continue
                if stmt.lineno in fi.module.sink_lines:
                    continue  # annotated intentional terminal read
                name = stmt.targets[0].id
                for drop in flow.drops(fi, name, stmt.lineno):
                    site = (fi.module.relpath, drop.line, drop.reason)
                    if site in seen:
                        continue
                    seen.add(site)
                    findings.append(Finding(
                        fi.module.relpath, drop.line, "param-dropped",
                        f"config value of {key!r} (read at "
                        f"{fi.module.relpath}:{stmt.lineno} in "
                        f"{fi.qualname}): {drop.reason}",
                    ))
        return findings
