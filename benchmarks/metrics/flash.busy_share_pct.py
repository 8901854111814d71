"""Flash kernels' device time / device busy time in the traced window."""

from lib import kernels


def read(record):
    trace = record.get("trace")
    kernel_s = kernels.flash_seconds(trace)
    if kernel_s <= 0 or not trace.get("busy_s"):
        return None
    return 100.0 * kernel_s / trace["busy_s"]
