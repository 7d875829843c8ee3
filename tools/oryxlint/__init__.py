"""oryxlint — project-aware static analysis for the oryx_tpu tree.

The framework is a small checker SPI (tools/oryxlint/core.py): each
checker visits the parsed module ASTs of the whole project through
shared resolution helpers (tools/oryxlint/callgraph.py) and emits
findings with file:line and a rule id. Findings are suppressible with a
trailing comment naming the rule; functions carry machine-readable
annotations the checkers honor (off-loop proofs, lock-held contracts,
guarded attributes).

Checkers shipped (tools/oryxlint/checkers/):

- ``blocking-call-on-loop``  broker/file/subprocess I/O reachable from
  an event-loop root (async defs, nonblocking route handlers)
- ``guarded-by``             reads/writes of lock-annotated shared
  attributes outside their lock
- ``jit-side-effect``        Python side effects inside jax.jit / pjit /
  Pallas-traced functions
- ``donation-reuse``         use of a buffer after it was passed at a
  ``donate_argnums`` position
- ``config-keys``            oryx.* config keys vs common/reference.conf
  (both directions; absorbed tools/check_config.py)
- ``metric-docs``            oryx_* metric names vs docs/observability.md
  (both directions; absorbed tools/check_metrics.py)
- ``param-dropped``          a config value read into a variable must
  reach a sink on every path, interprocedurally
  (tools/oryxlint/dataflow.py value-flow engine)
- ``device-placement``       uncommitted device_put results flowing into
  long-lived stores; mesh + shard_mesh at one train_als call site
- ``lock-order``             inverted lock-acquisition pairs and
  violations of the canonical order in tools/oryxlint/lockorder.toml
- ``shard-topology``         half-wired shard-count surfaces (config
  keys vs /healthz, ReplicaInfo, supervisor overlay)

Run ``python -m tools.oryxlint`` (``--changed`` for a git-diff-scoped
fast pass, ``--json`` for machine consumption — each finding carries
stable rule/severity/fix_hint fields, ``--stats`` for the call-graph
resolution rate). tools/precommit.sh wraps the --changed mode for
pre-commit hooks. The whole-tree run is wired as a tier-1 test
(tests/test_oryxlint.py); docs/development.md documents the rule
catalog and annotation syntax.
"""

from tools.oryxlint.core import Finding, Project, run_lint  # noqa: F401
