"""Least time the chip could take for the attention the traced steps need
/ device time of the flash kernels' events.

The work is reckoned from shapes (the family's ``attention_work``: for the
dense family the causal half, backward twice the forward, recompute not
credited), the larger of FLOPs / peak and bytes / HBM peak; it does not
depend on which kernel ran."""

from lib import flops, kernels, modules, peaks


def read(record):
    trace, cell = record.get("trace"), record["cell"]
    kernel_s = kernels.flash_seconds(trace)
    if kernel_s <= 0:
        return None
    work = modules.family_of(record["config"]).attention_work(
        record["config"], cell["batch_per_chip"], cell["row_tokens"] - 1)
    least, _ = flops.roofline_seconds(
        work, peaks.peaks_for(record["device"]["kind"]))
    return 100.0 * least * trace["steps"] / kernel_s
