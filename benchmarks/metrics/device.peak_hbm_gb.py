"""Peak HBM of the fullest device after the window, before the reference
runs: ``peak_bytes_in_use`` (live buffers: the state and the batches) plus
``peak_bytes_reserved`` (what the loaded programs hold for their
temporaries, which ``peak_bytes_in_use`` and so ``memory_peak_bytes`` leave
out on a TPU; PERF.md section 4)."""


def read(record):
    stats = record["memory_stats"]
    peak = stats.get("peak_bytes_in_use", 0) + stats.get(
        "peak_bytes_reserved", 0)
    return peak / 1e9 if peak else None
