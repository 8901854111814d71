"""Share of the traced window in which no op ran on the device (mean over
the chips used), from the ``.xplane.pb``."""


def read(record):
    trace = record.get("trace")
    if not trace or "busy_s" not in trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
