"""Device time of the flash forward kernel (``flash_fwd``: once a layer in
the forward pass, and once more in the backward pass of each block whose
checkpoint plan keeps no ``flash_out``; PERF.md section 3) in one traced
step."""

from lib import program_names


def read(record):
    return program_names.kernel_ms_per_step(record.get("trace"),
                                            program_names.FLASH_FWD_EVENT)
