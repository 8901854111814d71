"""Program auditor, trace level: walk a jaxpr and report what the
program *actually contains*.

The linter (lint.py) sees spellings; this module sees the traced
program — the ground truth after Python control flow, closures, and
library layers have resolved.  Given any callable + example args it
recursively walks the jaxpr (through pjit/scan/while/cond sub-jaxprs)
and reports:

* ``jaxpr-callback``      — host callbacks inside the program
  (``pure_callback`` / ``io_callback`` / ``debug_callback``): each one
  is a device→host→device round-trip per execution, i.e. exactly the
  per-step sync PR 1 removed.  (``jax.debug.print`` compiles to one.)
* ``jaxpr-const-capture`` — large constants captured by closure instead
  of passed as arguments.  A closed-over params tree is baked into the
  executable: it bloats the program, defeats donation, and silently
  pins stale weights.
* the **collective census** — per-primitive counts and bytes for the
  manual-SPMD collectives (``psum`` / ``all_gather`` / ``ppermute`` /
  ``all_to_all`` / ``psum_scatter``), the shard_map half of the
  program-shape receipt.  GSPMD-inserted collectives do not exist at
  jaxpr level — those come from the compiled HLO
  (dtdl_tpu/analysis/hlo_audit.py); contract tests census both.
* ``bf16_to_f32_casts`` (census field, not a finding) — the count of
  bf16→f32 ``convert_element_type`` ops: a jump against the baseline
  means an implicit weak-type upcast snuck an f32 path into a bf16
  program (the deliberate casts — logits, loss — are in the baseline).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.extend.core as jex_core
import numpy as np

from dtdl_tpu.analysis.findings import Finding

#: manual-SPMD collective primitives (what shard_map code emits);
#: pmean traces to psum + div, so psum covers it
COLLECTIVE_PRIMS = ("psum", "all_gather", "ppermute", "all_to_all",
                    "psum_scatter", "pmax", "pmin")
_CENSUS_PRIMS = frozenset(COLLECTIVE_PRIMS)
#: under vma-typed shard_map a collective whose result is replicated
#: traces as ``<name>_invariant`` — the same data movement, censused
#: under the plain name
_INVARIANT = "_invariant"

CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                            "debug_callback", "callback", "outfeed",
                            "infeed"})

#: closure-captured constants above this are a finding (default 1 MiB —
#: rope tables and masks sit well under it, a params tree well over)
CONST_LIMIT_BYTES = 1 << 20


@dataclasses.dataclass
class JaxprAudit:
    """Findings + census of one traced program."""

    name: str
    findings: list
    census: dict


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:        # tokens / abstract refs carry no bytes
        return 0


def walk_eqns(jaxpr):
    """Every eqn of ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    scan/while/cond branches, custom_* calls), depth-first, each eqn
    exactly once."""
    yield from _iter_all_eqns(jaxpr)


def _iter_all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _jaxprs_in(v):
                yield from _iter_all_eqns(sub)


def _jaxprs_in(value):
    """Jaxpr objects inside one eqn param value (handles ClosedJaxpr,
    raw Jaxpr, and tuples/lists of either — scan carries 'jaxpr',
    cond carries 'branches', custom_vjp carries callables we skip)."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v


def census_jaxpr(closed) -> dict:
    """Counts/bytes census of a ClosedJaxpr (see module docstring)."""
    coll: dict[str, dict] = {}
    n_callbacks = 0
    n_bf16_f32 = 0
    for eqn in _iter_all_eqns(closed.jaxpr):
        name = eqn.primitive.name.removesuffix(_INVARIANT)
        if name in _CENSUS_PRIMS:
            ent = coll.setdefault(name, {"count": 0, "bytes": 0})
            ent["count"] += 1
            ent["bytes"] += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        elif name in CALLBACK_PRIMS:
            n_callbacks += 1
        elif name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (getattr(src, "dtype", None) == jax.numpy.bfloat16
                    and getattr(dst, "dtype", None) == np.float32):
                n_bf16_f32 += 1
    const_bytes = sum(_aval_bytes(jax.typeof(c))
                      for c in closed.consts)
    return {"collectives": {k: coll[k] for k in sorted(coll)},
            "callbacks": n_callbacks,
            "bf16_to_f32_casts": n_bf16_f32,
            "const_bytes": int(const_bytes),
            "n_eqns": sum(1 for _ in _iter_all_eqns(closed.jaxpr))}


def audit_jaxpr(fn, *args, name: str = "program",
                const_limit: int = CONST_LIMIT_BYTES,
                **kwargs) -> JaxprAudit:
    """Trace ``fn(*args, **kwargs)`` and audit the jaxpr.

    ``fn`` may be any traceable callable (jitted or not — a jitted
    wrapper is traced through; the audit sees the same program).  Args
    may be concrete arrays or ``jax.ShapeDtypeStruct``s: tracing never
    executes the program.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    census = census_jaxpr(closed)
    findings = []
    for eqn in _iter_all_eqns(closed.jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMS:
            findings.append(Finding(
                "jaxpr-callback", name, 0,
                f"host callback '{eqn.primitive.name}' inside the "
                f"program — a device->host round-trip every execution"))
    for c in closed.consts:
        nbytes = _aval_bytes(jax.typeof(c))
        if nbytes > const_limit:
            shape = getattr(c, "shape", ())
            dtype = getattr(c, "dtype", "?")
            findings.append(Finding(
                "jaxpr-const-capture", name, 0,
                f"closure captured a {nbytes/2**20:.1f} MiB constant "
                f"({dtype}{list(shape)}) — pass it as an argument so "
                f"it can shard/donate",
                detail={"bytes": int(nbytes)}))
    return JaxprAudit(name=name, findings=findings, census=census)
