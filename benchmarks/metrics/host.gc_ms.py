"""Milliseconds a step in which the Python collector held the process during
the measured window, from the program's own pause rows
(``lib/host_pauses.py``): the union of the pauses' intervals cut to
``[window.start, window.start + window.seconds]``, divided by the window's
steps.  Near zero where a slow window is not the collector's doing."""

from lib import host_pauses


def read(record):
    rows, w = host_pauses.pauses(), host_pauses.window(record)
    if rows is None or w is None or not w[2]:
        return None
    start, end, steps = w
    return 1e3 * host_pauses.covered(rows, start, end) / steps
