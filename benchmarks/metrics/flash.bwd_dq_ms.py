"""Device time of the flash backward kernel that gives dq
(``flash_bwd_dq``) in one traced step."""

from lib import program_names


def read(record):
    return program_names.kernel_ms_per_step(record.get("trace"),
                                            program_names.FLASH_BWD_DQ_EVENT)
