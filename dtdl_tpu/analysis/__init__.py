"""Static analysis & program audits for the dtdl_tpu stack.

Two engines and one gate (ISSUE 15):

* **Repo linter** (:mod:`dtdl_tpu.analysis.lint` +
  :mod:`dtdl_tpu.analysis.rules`) — AST-based, repo-specific rules:
  the hot-path host-sync ban, the one-spelling jax.shard_map rule,
  donation on state-threading jits, trace hygiene (wall clocks / host
  RNG inside traced functions), and cross-file catalog consistency
  (ServeMetrics counters vs ``_WINDOW_COUNTERS``, emitted event names
  vs ``EVENT_CATALOG``).  Pure ``ast`` — sub-second over the package.
* **Program auditor** (:mod:`~dtdl_tpu.analysis.jaxpr_audit` /
  :mod:`~dtdl_tpu.analysis.hlo_audit`) — given any jitted callable +
  example args, walk the traced jaxpr and the lowered/compiled XLA
  module: host callbacks and transfers, donation aliasing, oversized
  closure constants, and the collective census (counts + bytes) that
  :mod:`~dtdl_tpu.analysis.contracts` pins for the real train/megatron/
  decode/verify programs against ``baselines.json``.
* **Gate** — ``scripts/audit.py`` (CLI report, nonzero exit on
  unsuppressed findings, inline ``# audit: ok[rule-id] reason``
  suppressions) and tests/test_analysis_gate.py inside tier-1.
"""

from dtdl_tpu.analysis.findings import (Finding, Suppression,  # noqa: F401
                                        apply_suppressions, render_report,
                                        scan_suppressions)
from dtdl_tpu.analysis.lint import lint_paths, rule_docs  # noqa: F401
from dtdl_tpu.analysis.jaxpr_audit import (JaxprAudit,  # noqa: F401
                                           audit_jaxpr, census_jaxpr)
from dtdl_tpu.analysis.hlo_audit import (HloAudit,  # noqa: F401
                                         arg_leaf_indices, audit_compiled,
                                         collective_census, donated_args,
                                         host_transfers)

__all__ = [
    "Finding", "Suppression", "apply_suppressions", "render_report",
    "scan_suppressions", "lint_paths", "rule_docs", "JaxprAudit",
    "audit_jaxpr", "census_jaxpr", "HloAudit", "arg_leaf_indices",
    "audit_compiled", "collective_census", "donated_args",
    "host_transfers",
]
