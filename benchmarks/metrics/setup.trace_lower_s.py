"""Seconds of set-up that jax spent tracing the step's program and lowering
it to MLIR (the model's Python, Pallas -> Mosaic included; no cache serves
it), from the program's compile account: ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` rows of the step's function before the
measured window."""

from lib import program_names


def read(record):
    return program_names.step_seconds(
        record, ("compile_trace_s", "compile_lower_s"))
