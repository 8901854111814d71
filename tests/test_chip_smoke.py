"""chip_smoke.py and the device-selection rules it leans on.

The script's real run needs a TPU; what can be pinned on CPU is that it
REFUSES to run anywhere else (and says what it found), that its
``--rehearse`` walk of the same phases passes at 'tiny' width, and that
the pieces on its path never pick a device or a cache location quietly:
the compile-cache helper, the Pallas interpret switch, the chip-peak
table.
"""

import os
import subprocess
import sys
import types

import jax
import pytest

from dtdl_tpu.obs import goodput
from dtdl_tpu.ops import attention
from dtdl_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke_runs():
    """Both subprocess runs, started together: {name: (rc, output)}."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)      # one CPU device: no four-chip phase
    procs = {name: subprocess.Popen(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for name, args in (("chip", []), ("rehearse", ["--rehearse"]))}
    out = {}
    for name, proc in procs.items():
        text, _ = proc.communicate(timeout=300)
        out[name] = (proc.returncode, text)
    return out


def test_refuses_a_platform_that_is_not_tpu(smoke_runs):
    rc, text = smoke_runs["chip"]
    assert rc != 0, text
    assert "JAX found 'cpu'" in text
    assert '"ok"' not in text          # no result line on a refusal


def test_rehearsal_passes_and_says_what_it_is(smoke_runs):
    rc, text = smoke_runs["rehearse"]
    assert rc == 0, text
    assert "REHEARSAL — says nothing about the chip" in text
    last = text.strip().splitlines()[-1]
    assert '"ok": true' in last and '"rehearsal": true' in last


@pytest.fixture
def cache_config():
    """Hand the test jax's cache-dir setting and put it back after."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_the_environment_is_left_to_jax(
        monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_the_checkout_from_any_cwd(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    seen = []
    for cwd in (REPO, tmp_path):
        monkeypatch.chdir(cwd)
        seen.append(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == want
    assert seen == [want, want]


def test_interpret_only_on_cpu_and_never_a_third_platform(monkeypatch):
    assert attention._use_interpret() is True            # the test platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        attention._use_interpret()


def test_peak_table_is_exact_match_and_unknown_chips_raise(monkeypatch):
    def devices_of(platform, kind):
        return lambda: [types.SimpleNamespace(platform=platform,
                                              device_kind=kind)]

    assert goodput.peak_flops_per_chip() is None         # cpu
    monkeypatch.setattr(jax, "devices", devices_of("tpu", "TPU v5 lite"))
    assert goodput.peak_flops_per_chip() == 197e12
    # the old substring match gave this the v5p peak
    monkeypatch.setattr(jax, "devices", devices_of("tpu", "TPU v5 mega"))
    with pytest.raises(ValueError, match="TPU v5 mega"):
        goodput.peak_flops_per_chip()
