"""Seconds of set-up in which the Python collector held the process, from the
program's own pause rows (``lib/host_pauses.py``): the union of the pauses'
intervals before ``record["window"]["start"]``, cut there.  It lies inside
the ``setup.*`` seconds of the compile account (a collection during tracing
and lowering is counted there too) and inside the benchmark's own set-up."""

from lib import host_pauses


def read(record):
    rows = host_pauses.pauses(whole=True)
    start = (record.get("window") or {}).get("start")
    if rows is None or start is None:
        return None
    return host_pauses.covered(rows, float("-inf"), start)
