"""Program auditor, compiled level: donation aliasing + collective
census + host transfers out of the lowered/compiled XLA module.

The jaxpr shows what was traced; the compiled HLO shows what XLA made
of it — GSPMD-inserted collectives that exist in no jaxpr, the actual
input→output buffer aliasing behind ``donate_argnums``, and the
custom-calls a host callback compiles into.  This module parses both
artifacts (``lowered.as_text()`` StableHLO for the per-arg donation
attributes, ``compiled.as_text()`` optimized HLO for ops) — text
parsing on purpose: it needs no private jax APIs and the same two
strings are what a human debugging a program dump would read.

Checks:

* ``hlo-undonated``    — a flat input argument the caller expected
  donated (``expect_donated``) that is absent from the optimized
  module's ``input_output_alias`` map: the ``donate_argnums`` was lost,
  or XLA could not pair the buffer with an output — either way that
  buffer is copied every call.
* ``hlo-host-transfer`` — host callback custom-calls
  (``xla_python_cpu_callback`` & friends), infeed/outfeed, host
  send/recv in the *optimized* module: whatever the source looked
  like, the compiled program talks to the host.
* the **collective census** — counts + bytes per collective op
  (all-reduce / all-gather / reduce-scatter / collective-permute /
  all-to-all, sync or async-start form) parsed from the optimized HLO:
  a GSPMD resharding that sneaks an all-gather into the step shows up
  as a named diff against the checked-in baseline.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np

from dtdl_tpu.analysis.findings import Finding

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

#: optimized-HLO collective op names (async forms end in -start; the
#: matching -done is not counted separately)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# the shape class admits XLA's ``/*index=5*/`` position comments, which
# it writes inside long tuple shapes (a combined all-reduce's result)
_OP_RE = re.compile(
    r"=\s+(?P<shape>\(?[a-z0-9\[\],{}: /*=]*?\)?)\s+"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?P<start>-start)?\(")

_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+[0-9]*)\[(?P<dims>[0-9,]*)\]")

_HOST_CALL_RE = re.compile(
    r'custom_call_target="(?P<target>[^"]*'
    r'(?:callback|host_callback|HostCallback)[^"]*)"')

_TRANSFER_OPS = ("infeed", "outfeed", "send", "send-done", "recv",
                 "recv-done")

# one entry-arg declaration: '%argN: tensor<...>' with ITS OWN optional
# attribute dict attached — anchored so one arg's attributes can never
# be read as a neighbor's (tensor types contain no '{' or ',').  The
# attrs body allows quoted strings with braces inside: mhlo.sharding
# values look like "{maximal device=0}" and must not truncate the dict
# before a later tf.aliasing_output entry; shardy values nest one level
# of bare braces (sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {}]>).
_ALIAS_ARG_RE = re.compile(
    r"%arg(?P<idx>\d+):\s*[^{,)]*?"
    r"\{(?P<attrs>(?:[^{}\"]|\"[^\"]*\"|\{(?:[^{}\"]|\"[^\"]*\")*\})*)\}")

_IO_ALIAS_ENTRY_RE = re.compile(r"\(\s*(?P<param>\d+)\s*,")


@dataclasses.dataclass
class HloAudit:
    """Findings + census of one compiled program."""

    name: str
    findings: list
    census: dict


def shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string (``f32[4,8]{1,0}`` or a tuple of
    them); unknown dtypes count zero rather than guessing."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = _DTYPE_BYTES.get(m.group("dt"))
        if dt is None:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * dt
    return total


def collective_census(hlo_text: str) -> dict:
    """``{op: {count, bytes}}`` over the optimized HLO text."""
    out: dict[str, dict] = {}
    for m in _OP_RE.finditer(hlo_text):
        op = m.group("op")
        ent = out.setdefault(op, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += shape_bytes(m.group("shape"))
    return {k: out[k] for k in sorted(out)}


def host_transfers(hlo_text: str) -> list[str]:
    """Host-transfer sites in the optimized HLO: callback custom-call
    targets plus infeed/outfeed/send/recv op names, in order."""
    hits = [m.group("target") for m in _HOST_CALL_RE.finditer(hlo_text)]
    op_re = re.compile(r"=\s+\(?[a-z0-9\[\],{}: ]*?\)?\s+"
                       r"(" + "|".join(_TRANSFER_OPS) + r")\(")
    hits += [m.group(1) for m in op_re.finditer(hlo_text)]
    return hits


def donated_args(lowered_text: str) -> set[int]:
    """Flat input-arg indices the trace OFFERED for donation — args
    carrying ``tf.aliasing_output`` (aliasing already proven at
    lowering) or ``jax.buffer_donor`` (left for XLA to pair) in the
    StableHLO entry function."""
    return {int(m.group("idx"))
            for m in _ALIAS_ARG_RE.finditer(lowered_text)
            if "tf.aliasing_output" in m.group("attrs")
            or "jax.buffer_donor" in m.group("attrs")}


def aliased_params(compiled_text: str) -> set[int]:
    """Parameter numbers XLA actually aliased to an output — the
    ``input_output_alias={ {0}: (20, {}, may-alias), ... }`` header of
    the optimized module.  This is the donation ground truth: an
    offered donation the compiler could not pair still copies."""
    start = compiled_text.find("input_output_alias={")
    if start < 0:
        return set()
    # walk the balanced-brace body (entries contain nested {} indices)
    i = start + len("input_output_alias={")
    depth, end = 1, i
    while end < len(compiled_text) and depth:
        depth += {"{": 1, "}": -1}.get(compiled_text[end], 0)
        end += 1
    body = compiled_text[i:end - 1]
    return {int(e.group("param"))
            for e in _IO_ALIAS_ENTRY_RE.finditer(body)}


def arg_leaf_indices(args: tuple, argnums) -> set[int]:
    """The flat input-arg indices covered by positional ``argnums`` —
    what ``expect_donated`` should be for "these whole subtrees are
    donated" (mirrors jax's donate_argnums flattening)."""
    idx, out = 0, set()
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in argnums:
            out.update(range(idx, idx + n))
        idx += n
    return out


def audit_compiled(fn, *args, name: str = "program",
                   expect_donated=None, **kwargs) -> HloAudit:
    """Lower + compile ``fn(*args)`` and audit the XLA module.

    ``fn`` is a jitted callable (anything with ``.lower``); plain
    callables are wrapped in ``jax.jit`` (which donates nothing — pass
    the real jitted program to audit its donation).  ``expect_donated``
    is a set of flat input-arg indices (see :func:`arg_leaf_indices`)
    that MUST be aliased; None skips the donation check.  Compiling is
    the expensive step (~the program's normal first-call cost); nothing
    is executed.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*args, **kwargs)
    compiled = lowered.compile()
    low_text = lowered.as_text()
    hlo_text = compiled.as_text()
    offered = donated_args(low_text)
    donated = aliased_params(hlo_text)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {"alias_bytes": int(ma.alias_size_in_bytes),
               "argument_bytes": int(ma.argument_size_in_bytes),
               "output_bytes": int(ma.output_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes)}
    except Exception:       # pragma: no cover - backend without stats
        pass
    transfers = host_transfers(hlo_text)
    findings = []
    if expect_donated is not None:
        missing = sorted(set(expect_donated) - donated)
        if missing:
            findings.append(Finding(
                "hlo-undonated", name, 0,
                f"{len(missing)} expected-donated input buffer(s) not "
                f"aliased to any output (flat arg indices {missing}) — "
                f"each is a fresh copy every call",
                detail={"missing": missing}))
    for t in transfers:
        findings.append(Finding(
            "hlo-host-transfer", name, 0,
            f"compiled program transfers to host via '{t}'"))
    census = {"collectives": collective_census(hlo_text),
              "host_transfers": len(transfers),
              "donated_args": sorted(donated),
              "donor_args": sorted(offered),
              "memory": mem}
    return HloAudit(name=name, findings=findings, census=census)
