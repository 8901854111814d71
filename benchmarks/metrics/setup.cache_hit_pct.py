"""Share of set-up's persistent-cache look-ups that were served from the
cache, from the program's compile account: ``cache_hits`` / (``cache_hits``
+ ``cache_misses``) of the rows before the measured window, every program
of the process alike (a count carries no function's name).  jax counts a
miss when it writes the entry; the runner sets both of the cache's
thresholds to nothing, so every miss is written."""

from lib import program_names


def read(record):
    hits, misses = (
        len(program_names.setup_rows(record, (key,)) or ())
        for key in ("compile_cache_hits", "compile_cache_misses"))
    return 100.0 * hits / (hits + misses) if hits + misses else None
