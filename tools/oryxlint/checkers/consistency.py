"""Project-consistency checkers (rules ``config-keys``, ``metric-docs``,
``flight-events``).

These absorb the one-off tools this repo grew over PRs 4-8 into the
checker SPI — the old entry points (tools/check_config.py,
tools/check_metrics.py) remain as thin CLI wrappers:

- ``config-keys``: every ``oryx.*`` key read through a Config accessor
  is declared in common/reference.conf, and every key declared under a
  strict robustness block (faults/retry/quarantine/shed) is read
  somewhere — a dead recovery knob misleads operators.
- ``metric-docs``: every ``oryx_*`` metric name in code matches the
  naming contract and has a row in docs/observability.md, and every
  documented row still exists in code (the reverse docs rule) — plus the
  label names the docs must keep.
- ``flight-events``: every flight-recorder ``record(kind="...")`` call
  site uses a kind registered in the ``EVENT_KINDS`` catalog
  (oryx_tpu/common/flightrec.py), and every cataloged kind has a row in
  docs/observability.md's flight-recorder event catalog (both
  directions) — the config-key/metric-docs pattern applied to the black
  box, so the event schema cannot drift silently.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from tools.oryxlint.core import Checker, Finding, Project

# A Config accessor taking a literal oryx.* key as its first argument.
# \s* spans newlines, so wrapped call sites resolve too. Keys containing
# "{" are f-string compositions and excluded by the character class.
ACCESSOR = re.compile(
    r"\.(?:get|get_string|get_int|get_float|get_bool|get_list|get_config|has)"
    r"\(\s*[bru]?[\"'](oryx\.[A-Za-z0-9_.\-]+)[\"']"
)

# Blocks whose declared keys must each be READ by code (reverse check).
STRICT_BLOCKS = (
    "oryx.monitoring.faults",
    "oryx.monitoring.retry",
    "oryx.monitoring.quarantine",
    "oryx.serving.api.shed",
)

VALID_METRIC_NAME = re.compile(r"^oryx_[a-z0-9_]+$")
# A whole string literal that is an oryx_-prefixed identifier. Literals
# with any other characters (spaces, braces, dots) are scrape patterns or
# prose, not metric registrations, and are skipped on purpose.
METRIC_LITERAL = re.compile(r"""["'](oryx_[A-Za-z0-9_]+)["']""")
# A reference-table row whose first cell is the backticked metric name.
DOC_ROW = re.compile(r"^\|\s*`(oryx_[^`]+)`", re.M)

# Not metrics: the package's own name appears as a string in a few places.
METRIC_IGNORE = {"oryx_tpu"}

# Label names the metric reference must keep: the batcher's dispatch
# records, the per-shard sync, the SLO signal, the request phase and the
# compile cause are keyed on them.
REQUIRED_DOC_TOKENS = ("score_mode", "shard", "signal", "phase", "cause")

# Hot-path latency-attribution vocabulary (ISSUE 17): the perfattr
# families (common/perfattr.py) must stay BOTH registered in code and
# documented — dashboards, `oryx perf`, and the latency-budget runbook
# all key on these exact names, so a rename must fail tier-1 loudly
# rather than silently orphan them.
REQUIRED_PERFATTR_FAMILIES = (
    "oryx_request_phase_seconds",
    "oryx_device_idle_gap_seconds",
    "oryx_xla_compile_seconds",
    "oryx_xla_compiles_total",
    # the parts of `serialize` on the deferred top-n path (ISSUE 25); the
    # benchmark's post_*_ms_per_req readers key on it
    "oryx_post_stage_seconds",
    # how often the fused top-k kernel's threshold gate lets a chunk
    # through to its sort network (ISSUE 26): a benchmark share of the
    # two is the next per-layer metric of the top-k kernel
    "oryx_topk_chunks_folded",
    "oryx_topk_chunks",
    # how many of a dispatch's row blocks the kernel did not walk because
    # they hold no request (ISSUE 30); a benchmark share of the two waits
    # for a `benchmark` PR
    "oryx_topk_row_blocks",
    "oryx_topk_row_blocks_skipped",
    # the sublane tiles the kernel's folds sorted (ISSUE 32): over 16 x
    # oryx_topk_chunks_folded, how much of a whole-block fold a dispatch's
    # real rows still cost; the share waits for a `benchmark` PR too
    "oryx_topk_fold_tiles",
    # the fired chunks the kernel placed without a sort (ISSUE 35): over
    # oryx_topk_chunks_folded, the share of the single-entrant path that
    # engages; that share waits for a `benchmark` PR as well
    "oryx_topk_chunks_inserted",
    # the chunks of a view's capacity a walked row block left alone, since
    # the kernel is told how many of the view's rows are items (ISSUE 40)
    "oryx_topk_item_chunks_skipped",
    # the batched encoder step of the seq app (ISSUE 33): its dispatches
    # and their real and padded tokens, and the expert layer's load counted
    # on the device; the benchmark's seq_step_ms / step_tokens /
    # step_pad_share / moe_load_peak / moe_roofline readers key on them
    "oryx_seq_encode_stage_seconds",
    "oryx_seq_steps_total",
    "oryx_seq_step_tokens_total",
    "oryx_seq_blocks_total",
    "oryx_seq_denoise_steps_total",
    "oryx_seq_slots_in_use",
    "oryx_moe_routed_total",
    "oryx_moe_experts_touched_total",
    "oryx_moe_expert_tokens_max_total",
    # the cache slots' bytes by kind of state (ISSUE 37; `latent` and
    # `rope_key` since ISSUE 41): the encoder kinds' runs report it, and the
    # oryx_moe_* counters above are fed by a decode step too
    # (joyai_moe_roofline / joyai_experts_touched / joyai_moe_load_peak)
    "oryx_seq_slot_state_bytes",
    # what every thread on the serving path is doing (ISSUE 39): the
    # regions' always-on counters (common/tracing.py), the event loops'
    # heartbeat and the stall witness; the benchmark's launch_*_ms,
    # *_offcpu_share, *_idle_share, loop_lag_ms and stall_share readers
    # key on them
    "oryx_region_seconds_total",
    "oryx_region_cpu_seconds_total",
    "oryx_regions_total",
    "oryx_http_loop_lag_seconds",
    "oryx_stall_seconds_total",
    "oryx_stalls_total",
)


# -- collectors (shared with the thin CLI wrappers) --------------------------


def _package_texts(
    package: Path, root: Path, texts: dict[str, str] | None
) -> list[tuple[str, str]]:
    """(relpath, source) pairs under oryx_tpu/, from an already-loaded
    text cache (the lint run's Project) or from disk (the CLI wrappers)."""
    prefix = str(package.relative_to(root))
    if texts is not None:
        return sorted(
            (rel, t) for rel, t in texts.items()
            if rel.startswith(prefix + "/") or rel.startswith(prefix + "\\")
        )
    return [
        (str(py.relative_to(root)), py.read_text(encoding="utf-8"))
        for py in sorted(package.rglob("*.py"))
    ]


def code_config_keys(
    package: Path, root: Path, texts: dict[str, str] | None = None
) -> dict[str, tuple[str, int]]:
    """key -> (relpath, line) of the first literal oryx.* accessor read."""
    keys: dict[str, tuple[str, int]] = {}
    for rel, text in _package_texts(package, root, texts):
        for m in ACCESSOR.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            keys.setdefault(m.group(1), (rel, line))
    return keys


def code_metric_names(
    package: Path, root: Path, texts: dict[str, str] | None = None
) -> dict[str, tuple[str, int]]:
    """name -> (relpath, line) of the first metric-shaped literal."""
    names: dict[str, tuple[str, int]] = {}
    for rel, text in _package_texts(package, root, texts):
        for m in METRIC_LITERAL.finditer(text):
            name = m.group(1)
            if name not in METRIC_IGNORE:
                line = text.count("\n", 0, m.start()) + 1
                names.setdefault(name, (rel, line))
    return names


def doc_metric_names(doc: Path) -> set[str]:
    return set(DOC_ROW.findall(doc.read_text(encoding="utf-8")))


def reference_config(reference: Path):
    from oryx_tpu.common.config import parse_config

    return parse_config(reference.read_text(encoding="utf-8"))


# -- problem builders ---------------------------------------------------------


def config_problems(code: dict[str, str], ref) -> list[str]:
    """Key-level drift messages from a key->where map and a parsed
    reference config — the shared core the thin CLI wrapper
    (tools/check_config.py) and the rule both render from."""
    problems: list[str] = []
    for key in sorted(code):
        if not ref.has(key):
            problems.append(
                f"{key} ({code[key]}): read in code but not declared in "
                "common/reference.conf"
            )
    flat = ref.flatten()
    for block in STRICT_BLOCKS:
        for key in sorted(k for k in flat if k.startswith(block + ".")):
            if key not in code:
                problems.append(
                    f"{key}: declared in common/reference.conf but never "
                    "read by any Config accessor — a dead robustness knob "
                    "misleads operators about what recovery is configured"
                )
    return problems


def metric_doc_problems(
    code: dict[str, str], doc_names: set[str]
) -> list[str]:
    """Name-level drift messages from a name->where map and the doc-table
    names — shared by tools/check_metrics.py and the rule."""
    problems: list[str] = []
    for name in sorted(code):
        where = code[name]
        if not VALID_METRIC_NAME.match(name):
            problems.append(
                f"{name} ({where}): does not match ^oryx_[a-z0-9_]+$"
            )
        elif name not in doc_names:
            problems.append(
                f"{name} ({where}): missing from the docs/observability.md "
                "metric reference table"
            )
    for name in sorted(doc_names - set(code)):
        problems.append(
            f"{name}: documented in docs/observability.md but not found "
            "anywhere under oryx_tpu/"
        )
    problems.extend(perfattr_family_problems(set(code), doc_names))
    return problems


def perfattr_family_problems(
    code_names: set[str], doc_names: set[str]
) -> list[str]:
    """The latency-attribution families must exist on both sides — the
    generic drift checks only see names that exist SOMEWHERE, so a family
    deleted from both code and docs would otherwise pass silently."""
    problems: list[str] = []
    for name in REQUIRED_PERFATTR_FAMILIES:
        if name not in code_names:
            problems.append(
                f"{name}: required latency-attribution family not "
                "registered anywhere under oryx_tpu/ (common/perfattr.py)"
            )
        if name not in doc_names:
            problems.append(
                f"{name}: required latency-attribution family missing "
                "from the docs/observability.md metric reference table"
            )
    return problems


def config_findings(
    root: Path, texts: dict[str, str] | None = None
) -> list[Finding]:
    package = root / "oryx_tpu"
    reference = package / "common" / "reference.conf"
    ref_rel = str(reference.relative_to(root))
    if not reference.exists():
        return [Finding(ref_rel, 1, "config-keys", "missing reference.conf")]
    ref = reference_config(reference)
    code = code_config_keys(package, root, texts)
    out: list[Finding] = []
    for key in sorted(code):
        where, line = code[key]
        if not ref.has(key):
            out.append(Finding(
                where, line, "config-keys",
                f"{key} read in code but not declared in {ref_rel}",
            ))
    flat = ref.flatten()
    for block in STRICT_BLOCKS:
        for key in sorted(k for k in flat if k.startswith(block + ".")):
            if key not in code:
                out.append(Finding(
                    ref_rel, 1, "config-keys",
                    f"{key} declared in {ref_rel} but never read by any "
                    "Config accessor — a dead robustness knob misleads "
                    "operators about what recovery is configured",
                ))
    return out


def metric_findings(
    root: Path, texts: dict[str, str] | None = None
) -> list[Finding]:
    package = root / "oryx_tpu"
    doc = root / "docs" / "observability.md"
    doc_rel = str(doc.relative_to(root))
    if not doc.exists():
        return [Finding(doc_rel, 1, "metric-docs", "missing observability.md")]
    code = code_metric_names(package, root, texts)
    doc_names = doc_metric_names(doc)
    out: list[Finding] = []
    for name in sorted(code):
        where, line = code[name]
        if not VALID_METRIC_NAME.match(name):
            out.append(Finding(
                where, line, "metric-docs",
                f"{name} does not match ^oryx_[a-z0-9_]+$",
            ))
        elif name not in doc_names:
            out.append(Finding(
                where, line, "metric-docs",
                f"{name} missing from the {doc_rel} metric reference table",
            ))
    for name in sorted(doc_names - set(code)):
        out.append(Finding(
            doc_rel, 1, "metric-docs",
            f"{name} documented in {doc_rel} but not found anywhere under "
            "oryx_tpu/",
        ))
    for problem in perfattr_family_problems(set(code), doc_names):
        out.append(Finding(doc_rel, 1, "metric-docs", problem))
    doc_text = doc.read_text(encoding="utf-8")
    for tok in REQUIRED_DOC_TOKENS:
        if tok not in doc_text:
            out.append(Finding(
                doc_rel, 1, "metric-docs",
                f"{tok}: required label name missing from {doc_rel}",
            ))
    return out


# Heading of the docs table the flight-event catalog must mirror; rows
# under it are parsed until the next heading.
FLIGHT_DOC_HEADING = "### Flight-recorder event catalog"
FLIGHT_DOC_ROW = re.compile(r"^\|\s*`([a-z0-9\-]+)`\s*\|")


def flight_doc_kinds(doc: Path) -> set[str]:
    """Event kinds documented in the flight-recorder catalog table (the
    section between its heading and the next heading)."""
    kinds: set[str] = set()
    in_section = False
    for line in doc.read_text(encoding="utf-8").splitlines():
        if line.strip().startswith("#"):
            in_section = line.strip() == FLIGHT_DOC_HEADING
            continue
        if in_section:
            m = FLIGHT_DOC_ROW.match(line)
            if m:
                kinds.add(m.group(1))
    return kinds


def _flight_call_kinds(tree: ast.AST) -> list[tuple[int, str]]:
    """(line, kind) for every ``<recv>.record(kind="literal", ...)`` call
    in a module. The ``kind=`` keyword with a string-literal value is the
    flight recorder's signature shape (the method makes it keyword-only);
    non-literal kinds are skipped — confident-only, like the dataflow
    checkers."""
    out: list[tuple[int, str]] = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "record"
        ):
            continue
        for kw in node.keywords:
            if (
                kw.arg == "kind"
                and isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, str)
            ):
                out.append((node.lineno, kw.value.value))
    return out


def flight_findings(root: Path, project: Project | None = None) -> list[Finding]:
    from oryx_tpu.common.flightrec import EVENT_KINDS

    doc = root / "docs" / "observability.md"
    doc_rel = str(doc.relative_to(root))
    if not doc.exists():
        return [Finding(doc_rel, 1, "flight-events", "missing observability.md")]
    out: list[Finding] = []
    modules = project.modules if project is not None else []
    for mod in modules:
        for line, kind in _flight_call_kinds(mod.tree):
            if kind not in EVENT_KINDS:
                out.append(Finding(
                    mod.relpath, line, "flight-events",
                    f"{kind!r} is not a registered flight-event kind — add "
                    "it to EVENT_KINDS (oryx_tpu/common/flightrec.py) and "
                    f"the {doc_rel} event catalog, or fix the typo",
                ))
    doc_kinds = flight_doc_kinds(doc)
    for kind in sorted(set(EVENT_KINDS) - doc_kinds):
        out.append(Finding(
            doc_rel, 1, "flight-events",
            f"{kind}: registered in EVENT_KINDS but missing from the "
            f"{doc_rel} flight-recorder event catalog",
        ))
    for kind in sorted(doc_kinds - set(EVENT_KINDS)):
        out.append(Finding(
            doc_rel, 1, "flight-events",
            f"{kind}: documented in the {doc_rel} flight-recorder event "
            "catalog but not registered in EVENT_KINDS",
        ))
    return out


class ConsistencyChecker(Checker):
    name = "consistency"
    rules = {
        "config-keys": (
            "oryx.* config keys read in code must be declared in "
            "reference.conf; robustness-block keys must be read somewhere"
        ),
        "metric-docs": (
            "oryx_* metric names must match the naming contract and stay "
            "in lockstep with docs/observability.md (both directions)"
        ),
        "flight-events": (
            "flight-recorder record(kind=...) call sites must use a kind "
            "registered in EVENT_KINDS, and the docs event catalog must "
            "match the registry in both directions"
        ),
    }
    severities = {
        "metric-docs": "warning",
        "flight-events": "warning",
    }
    fix_hints = {
        "config-keys": (
            "declare the key in common/reference.conf (or read/remove the "
            "dead robustness knob)"
        ),
        "metric-docs": (
            "add/remove the row in docs/observability.md so code and docs "
            "agree in both directions"
        ),
        "flight-events": (
            "register the kind in EVENT_KINDS "
            "(oryx_tpu/common/flightrec.py) and add/remove its row in the "
            "docs/observability.md flight-recorder event catalog"
        ),
    }

    def check(self, project: Project) -> list[Finding]:
        root = project.root
        # reuse the lint run's already-loaded sources instead of a second
        # and third full-tree read
        texts = {m.relpath: m.text for m in project.modules}
        out: list[Finding] = []
        out.extend(config_findings(root, texts))
        out.extend(metric_findings(root, texts))
        out.extend(flight_findings(root, project))
        return out
