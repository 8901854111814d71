"""Seconds of set-up that jax spent tracing, lowering, compiling or loading
every program of the process but the step's, from the program's compile
account.  In a cell these are the benchmark's own: the weights' generator,
the plan's ``eval_shape`` and the readings that decide ``correct``."""

from lib import program_names


def read(record):
    return program_names.other_seconds(record, program_names.DURATIONS)
