"""Test harness: force an 8-device virtual CPU platform.

SURVEY §4: the reference has no tests; its CPU fallback paths (``naive``
communicator, cpu device pick) are the pattern we formalize — every
distributed code path runs on a fake multi-device CPU backend so DP/DDP
semantics are checked without a TPU pod.

The platform is pinned before jax is imported (env) and again through
jax.config, so a stray ``JAX_PLATFORMS`` in the caller's shell cannot
send the suite to an accelerator.

Compile cache: tests are uncached unless ``JAX_COMPILATION_CACHE_DIR``
is set, in which case jax reads it itself (see
dtdl_tpu/runtime/compile_cache.py — the same variable places the cache
for the examples and chip_smoke.py).
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# tests never hit the network for datasets (fixture file:// URLs only)
os.environ.setdefault("DTDL_OFFLINE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


# ---------------------------------------------------------------------------
# Budget discipline (round 16): an unmarked compile-heavy test eats the
# suite's time limit (1,470 s on six workers today, pytest.ini), so
# this check flags every test that ran slower than
# DTDL_BUDGET_SLOW_S (default 10s) WITHOUT a `slow` mark, as a loud
# terminal section — new observability/serve tests get slow-marked
# instead of silently eating the remaining headroom.  Set
# DTDL_BUDGET_STRICT=1 to turn the flag into a session failure.
# ---------------------------------------------------------------------------

_SLOW_MARKED: set = set()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") is not None:
            _SLOW_MARKED.add(item.nodeid)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    threshold = float(os.environ.get("DTDL_BUDGET_SLOW_S", "10"))
    offenders = []
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if (getattr(rep, "when", None) == "call"
                    and getattr(rep, "duration", 0.0) > threshold
                    and rep.nodeid not in _SLOW_MARKED):
                offenders.append((rep.duration, rep.nodeid))
    if not offenders:
        return
    tr = terminalreporter
    tr.section("budget discipline", sep="=")
    tr.write_line(
        f"{len(offenders)} unmarked test(s) slower than {threshold:.0f}s "
        f"— mark them @pytest.mark.slow or make them cheaper "
        f"(tier-1 runs under a hard 870s budget):")
    for dur, nodeid in sorted(offenders, reverse=True):
        tr.write_line(f"  {dur:7.1f}s  {nodeid}")
    if os.environ.get("DTDL_BUDGET_STRICT"):
        pytest.exit("budget discipline violated (DTDL_BUDGET_STRICT=1)",
                    returncode=1)
