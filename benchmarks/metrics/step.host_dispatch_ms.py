"""Mean host time of one ``shard_batch`` + step call in the measured
window (both return before the device finishes), from the benchmark's own
spans around the two calls."""


def read(record):
    since = record["window"]["start"]
    total = {"feed": [0.0, 0], "dispatch": [0.0, 0]}
    for name, start, seconds in record["spans"]:
        if name in total and start >= since:
            total[name][0] += seconds
            total[name][1] += 1
    if not total["dispatch"][1]:
        return None
    return 1e3 * (total["feed"][0] + total["dispatch"][0]) / total["dispatch"][1]
