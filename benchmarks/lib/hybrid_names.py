"""What the hybrid blocks' device work is called in a trace, and how the
readers of ``gdn.*`` and ``moe_gmm.*`` find it.

**The grouped matmuls.**  ``dtdl_tpu/ops/grouped_matmul.py`` passes
``name="moe_gmm"`` and ``name="moe_tgmm"`` to its two ``pallas_call``s, and
XLA takes the instruction's name from it as it does for the flash kernels
(``lib/program_names.py``): an ``XLA Ops`` event reads ``%moe_gmm.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"``.  Anchored on the
whole instruction name and the Mosaic target.

**The delta rule.**  ``dtdl_tpu/ops/gated_delta.py`` is plain JAX: batched
chunk-local matmuls, then a ``lax.scan`` over the chunks that carries the
state ``S`` of ``[rows, value heads, key dim, value dim]`` float32.  The
scan compiles to ``while`` instructions (forward, and its transpose in the
backward pass; under ``remat`` the forward runs again), and an ``XLA Ops``
event of a loop spans all its iterations: ``%while.<n> = (s32[],
f32[2,32,128,128]{...}, ...) while(...)``.  :func:`gdn_event` matches a
``while`` whose carried tuple holds a float32 array of the configuration's
state shape, at the rows a chip has, or a Mosaic call named ``gdn_*`` (none
yet: for the kernel that replaces the loops).  What it covers is the part
of the operator that is sequential in the chunks: ``U~ = U - W S``, ``O``
and the state's update; the chunk-local part before the loop (``A``, ``T``,
``W``, ``U``: fusions and batched matmuls with no name of their own on the
``XLA Ops`` line) is outside it, and is in ``tools/scope_dump.py``'s
``gdn`` row.  The ops inside a loop's body are events of their own on the
same line, nested in the loop's event: the readers take the union of the
matching intervals, never their sum.
"""

from __future__ import annotations

import re

from . import xplane


def _kernel_event(name: str) -> str:
    return (rf'^%{name}(\.\d+)? = '
            r'.*custom_call_target="tpu_custom_call"')


MOE_GMM_EVENT = _kernel_event("moe_t?gmm")


def gdn_event(cfg: dict, rows_per_chip: int) -> str:
    """The pattern of the delta rule's loops for a configuration's state
    shape at ``rows_per_chip`` rows, or of a Mosaic call named ``gdn_*``."""
    state = "f32\\[{},{},{},{}\\]".format(
        rows_per_chip, cfg.get("linear_num_value_heads"),
        cfg.get("linear_key_head_dim"), cfg.get("linear_value_head_dim"))
    return (rf'^%while[\w.\-]* = \(.*?{state}.*? while\('
            rf'|^%gdn_\w+(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def union_ms_per_step(trace, pattern: str):
    """Device milliseconds a traced step covered by the events matching
    ``pattern`` (the union of their intervals, mean over the chips), or
    None where there is nothing to read."""
    if not trace or not trace.get("devices") or not trace.get("steps"):
        return None
    rx = re.compile(pattern)
    per_chip = [xplane.busy_union_ns([e for e in ev if rx.search(e[0])])
                for ev in trace["devices"].values()]
    ns = sum(per_chip) / len(per_chip)
    return ns / 1e6 / trace["steps"] if ns > 0 else None


def gdn_ms(record):
    """``gdn.ms``: the delta rule's loops in one traced step; None where
    the configuration has no linear layers or the trace none of them."""
    cfg = record["config"]
    if "linear_num_value_heads" not in cfg:
        return None
    return union_ms_per_step(
        record.get("trace"),
        gdn_event(cfg, record["cell"]["batch_per_chip"]))


def moe_gmm_ms(record):
    """``moe_gmm.ms``: the grouped-matmul kernels in one traced step."""
    return union_ms_per_step(record.get("trace"), MOE_GMM_EVENT)


def family_work(record, name: str):
    """``{"flops", "bytes"}`` of one step from the family's function
    ``name``, or None where the configuration's family has none."""
    from . import modules
    work = getattr(modules.family_of(record["config"]), name, None)
    cell = record["cell"]
    return work and work(record["config"], cell["batch_per_chip"],
                         cell["row_tokens"] - 1)


def roofline_pct(record, ms, work_name: str):
    """100 x the least time for the family's ``work_name`` work of a step /
    ``ms``; None where either is missing."""
    from . import flops, peaks
    work = ms and family_work(record, work_name)
    if not work:
        return None
    least, _ = flops.roofline_seconds(
        work, peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least / (ms / 1e3)
