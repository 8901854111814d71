"""One spelling per API: ``jax.shard_map``.

The installed jax still resolves ``jax.experimental.shard_map`` — as a
deprecation stub whose ``check_rep``-era keyword surface differs from
``jax.shard_map``'s vma-typed one — so a second spelling can still be
written and would fork semantics between call sites.  These rules keep
the package on the one public spelling.

* ``compat-shard-map`` — any import or attribute reference to
  ``jax.experimental.shard_map``.
* ``compat-maps``     — the removed ``jax.experimental.maps`` /
  ``xmap`` namespace.
"""

from __future__ import annotations

import ast

from dtdl_tpu.analysis.findings import Finding
from dtdl_tpu.analysis.rules import dotted

RULES = {
    "compat-shard-map": "deprecated jax.experimental.shard_map "
                        "referenced (use jax.shard_map)",
    "compat-maps": "removed jax.experimental.maps/xmap namespace "
                   "referenced",
}


def check(mod) -> list[Finding]:
    out = []
    for node in ast.walk(mod.tree):
        ref = None
        if isinstance(node, ast.ImportFrom):
            ref = node.module or ""
            if ref == "jax.experimental" and any(
                    a.name == "shard_map" for a in node.names):
                ref = "jax.experimental.shard_map"
        elif isinstance(node, ast.Import):
            hit = next((a.name for a in node.names
                        if a.name.startswith("jax.experimental.shard_map")
                        or a.name.startswith("jax.experimental.maps")),
                       None)
            ref = hit or ""
        elif isinstance(node, ast.Attribute):
            ref = dotted(node)
        if not ref:
            continue
        if ref.startswith("jax.experimental.shard_map"):
            out.append(Finding(
                "compat-shard-map", mod.path, node.lineno,
                "deprecated spelling — call jax.shard_map (its vma-typed "
                "autodiff is what the step factories are verified on)"))
        elif ref.startswith("jax.experimental.maps"):
            out.append(Finding(
                "compat-maps", mod.path, node.lineno,
                f"{ref} was removed upstream"))
    return out
