"""Device time of the full-causal flash backward in one traced step,
whichever implementation ran: the one kernel that gives dq, dk and dv
(``flash_bwd``, where the row's dq fits its VMEM) or the pair
(``flash_bwd_dq`` and ``flash_bwd_dkv``), so a program with either reads
its own.  The windowed calls (``flash_swa_*``) are ``swa.ms``'s; every
flash call is ``lib/kernels.py:FLASH_EVENT``'s."""

from lib import program_names

BWD_EVENT = (r'^%flash_bwd(_dq|_dkv)?(\.\d+)? = '
             r'.*custom_call_target="tpu_custom_call"')


def read(record):
    return program_names.kernel_ms_per_step(record.get("trace"), BWD_EVENT)
