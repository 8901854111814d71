"""Seconds of set-up in XLA's backend compile of the step's program or in
loading its cached executable, from the program's compile account: the
``backend_compile_duration`` rows of the step's function before the measured
window (jax's event spans the cache look-up, so a retrieval lies inside it
and counts once)."""

from lib import program_names


def read(record):
    return program_names.step_seconds(record, ("compile_backend_s",))
