"""The flash kernels' events in a device trace, as one lump.

On the chip (jax 0.9.0) an event of the ``XLA Ops`` line is named by its
whole HLO text.  Until PR 25 the Pallas calls in
``dtdl_tpu/ops/attention.py`` carried no ``name=`` and XLA called each
``%attn.<n>`` after the flax module it sits in; since then they are
``%flash_fwd.<n>``, ``%flash_bwd_dq.<n>`` and ``%flash_bwd_dkv.<n>``
(``lib/program_names.py`` reads each alone), always with
``custom_call_target="tpu_custom_call"``.  The pattern takes both: any
Mosaic call whose instruction name holds ``attn`` or ``flash``.  A step of
8 layers holds 24 of them where the checkpoint plan keeps ``flash_out``
on every block (PR 26 on), and 8 more forward calls where it keeps none.
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
