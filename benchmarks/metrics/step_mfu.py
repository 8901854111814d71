"""Whole step's share of the chip's bf16 peak, over the measured window.

Model FLOPs of the steps completed (the family's ``train_flops``: forward
+ 2x backward, recompute never credited) / window / (chips x peak)."""

from lib import modules, peaks


def read(record):
    device, cell, window = record["device"], record["cell"], record["window"]
    if device["platform"] != "tpu" or not window["steps"]:
        return None
    rows = cell["batch_per_chip"] * cell["chips"]
    work = modules.family_of(record["config"]).train_flops(
        record["config"], rows, cell["row_tokens"])
    peak = peaks.peaks_for(device["kind"])["bf16_flops_per_s"]
    return 100.0 * work * window["steps"] / window["seconds"] / (
        cell["chips"] * peak)
