"""How the program's kernels are named in a device trace today.

The Pallas calls in ``dtdl_tpu/ops/attention.py`` carry no ``name=``.  On
the chip (jax 0.9.0, my trace of ``olmo1b-train-b4s2048``, PR 24) an event
of the ``XLA Ops`` line is named by its whole HLO text, and each flash
kernel (forward, the forward again under ``remat``, the two backward
kernels) is an instruction that XLA calls ``%attn.<n>`` after the flax
module it sits in, with ``custom_call_target="tpu_custom_call"``: 32 of
them a step at 8 layers.  A kernel that a later PR names (``name=`` on the
``pallas_call``) keeps matching while its HLO name holds ``attn`` or
``flash``; PERF.md says so to the tracing issue.
"""

from . import xplane

FLASH_EVENT = (r'^%[\w\-]*(attn|flash)[\w\-.]* = '
               r'.*custom_call_target="tpu_custom_call"')


def flash_seconds(trace) -> float:
    """Device seconds of the flash kernels' events in a run record's
    ``trace``, averaged over the chips; 0.0 where there is nothing to read."""
    if not trace or not trace.get("devices"):
        return 0.0
    per_chip = [xplane.time_matching(ev, FLASH_EVENT)
                for ev in trace["devices"].values()]
    return sum(per_chip) / len(per_chip) / 1e9
