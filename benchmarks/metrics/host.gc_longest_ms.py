"""The longest single pause of the Python collector that overlaps the
measured window, in milliseconds, cut to the window, from the program's own
pause rows (``lib/host_pauses.py``).  A full collection of a set-up's heap
that lands in the window reads hundreds of milliseconds here."""

from lib import host_pauses


def read(record):
    rows, w = host_pauses.pauses(), host_pauses.window(record)
    if rows is None or w is None:
        return None
    cut = host_pauses.clipped(rows, w[0], w[1])
    return 1e3 * max((r.value for r in cut), default=0.0)
