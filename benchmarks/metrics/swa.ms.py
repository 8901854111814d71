"""Device time of the windowed flash kernels in one traced step: the events
``%flash_swa_fwd.<n>``, ``%flash_swa_bwd_dq.<n>`` and
``%flash_swa_bwd_dkv.<n>`` with ``tpu_custom_call``
(``dtdl_tpu/ops/attention.py``'s ``pallas_call(name=)`` for a call with a
``window``; the full-causal calls keep ``flash_fwd`` ..., which
``flash.fwd_ms`` ... read alone, and ``lib/kernels.py:FLASH_EVENT`` reads
both).  Nothing to read where the program has no such kernel (the parent of
the PR that added them) or the configuration no windowed layer."""

from lib import program_names

SWA_EVENT = (r'^%flash_swa_\w+?(\.\d+)? = '
             r'.*custom_call_target="tpu_custom_call"')


def read(record):
    return program_names.kernel_ms_per_step(record.get("trace"), SWA_EVENT)
