"""Launcher + real multi-process rendezvous tests (SURVEY §4: 'multi-process
rendezvous tested by spawning N local processes with the launcher')."""

import os
import re
import subprocess
import sys

import pytest

from dtdl_tpu.launch.tpu_vm import build_commands, discover_workers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_local_launcher_two_process_ddp(capfd):
    """2 processes x 2 CPU devices: rendezvous, train, identical params."""
    from dtdl_tpu.launch.local import launch_local
    rc = launch_local(
        [os.path.join(REPO, "tests", "_rendezvous_script.py")],
        nproc=2, port=12411, devices_per_proc=2, timeout=300)
    out = capfd.readouterr().out
    assert rc == 0, out
    results = re.findall(
        r"RESULT process=(\d) replicas=(\d) loss=([\d.]+) digest=([\d.]+)",
        out)
    assert len(results) == 2, out
    assert {r[0] for r in results} == {"0", "1"}
    assert all(r[1] == "4" for r in results)  # 2 hosts x 2 devices
    # cross-host determinism: same loss, same params digest
    assert results[0][2] == results[1][2]
    assert results[0][3] == results[1][3]


def test_local_launcher_refuses_sibling_processes_on_one_tpu(monkeypatch):
    """Several children that would each open the host's TPU are refused
    by name before anything spawns; CPU worlds and single processes are
    not the launcher's business."""
    from dtdl_tpu.launch import local

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="one.*process"):
        local.launch_local(["-c", "raise SystemExit(0)"], nproc=2)
    # jax picks the TPU itself when nothing is named and chips exist
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(local, "_host_has_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=''"):
        local.launch_local(["-c", "raise SystemExit(0)"], nproc=2)
    # one process, a carved CPU world, and a CPU-only env all go through
    local._refuse_tpu_siblings(1, None, {})
    local._refuse_tpu_siblings(2, 2, {})
    local._refuse_tpu_siblings(2, None, {"JAX_PLATFORMS": "cpu"})


def test_local_launcher_fail_fast():
    """A dying rank must terminate the job, not hang it (SURVEY §5.3)."""
    from dtdl_tpu.launch.local import launch_local
    rc = launch_local(
        ["-c", "import sys; sys.exit(3)"],
        nproc=2, port=12412, timeout=60)
    assert rc != 0


def test_tpu_vm_command_builder():
    cmds = build_commands(["h1", "h2"], ["train.py", "--lr", "0.1"],
                          port=1234)
    assert cmds[0][:4] == ["ssh", "-o", "BatchMode=yes", "h1"]
    assert "--coordinator h1:1234" in cmds[0][-1]
    assert "--process-id 1" in cmds[1][-1]
    # gcloud flavor
    g = build_commands(["h1", "h2"], ["t.py"], 1234, gcloud_name="pod",
                       zone="us-central2-b")
    assert g[1][:6] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh", "pod"]
    assert "--worker=1" in g[1]


def test_discover_workers_env(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "a,b,c")
    assert discover_workers() == ["a", "b", "c"]
    assert discover_workers("x,y") == ["x", "y"]
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES")
    assert discover_workers() == ["localhost"]


def test_initialize_retries_transient_rendezvous_failures(monkeypatch):
    """A restarted worker racing the coordinator retries the rendezvous
    with bounded backoff (ISSUE 12) — and a permanently absent
    coordinator still fails with the original error, loudly."""
    from dtdl_tpu.runtime import bootstrap
    calls = {"n": 0}

    def flaky(**kw):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("connection refused")

    monkeypatch.setattr(bootstrap, "_initialized", False)
    monkeypatch.setattr(bootstrap.jax.distributed, "initialize", flaky)
    monkeypatch.setattr(bootstrap.atexit, "register", lambda fn: None)
    bootstrap.initialize("127.0.0.1:1", 2, 0, retries=4, backoff_s=0.001)
    assert calls["n"] == 3
    # bounded: the budget exhausts into the underlying error
    monkeypatch.setattr(bootstrap, "_initialized", False)
    calls["n"] = -100                      # always fails within budget
    with pytest.raises(RuntimeError, match="connection refused"):
        bootstrap.initialize("127.0.0.1:1", 2, 0, retries=2,
                             backoff_s=0.001)
    monkeypatch.setattr(bootstrap, "_initialized", False)


def test_local_launcher_elastic_restart(tmp_path, capfd):
    """max_restarts relaunches the whole world after a failure; the retry
    succeeds (checkpoint-restart elasticity beyond the reference's
    hang-forever static world, SURVEY §5.3)."""
    from dtdl_tpu.launch.local import launch_local
    marker = tmp_path / "crashed_once"
    prog = (
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(7)  # first attempt: rank dies\n"
        "print('recovered ok')\n"
    )
    rc = launch_local(["-c", prog], nproc=2, port=12413, timeout=60,
                      max_restarts=2)
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "relaunching all 2 ranks" in out
    assert "recovered ok" in out


def test_local_launcher_restart_budget_exhausted(tmp_path):
    """A permanently failing job still fails after the restart budget."""
    from dtdl_tpu.launch.local import launch_local
    rc = launch_local(["-c", "import sys; sys.exit(5)"],
                      nproc=2, port=12414, timeout=60, max_restarts=1)
    assert rc == 5


def test_local_launcher_threads_store_addr(capfd, monkeypatch):
    """ISSUE 13 address threading is honest: an explicit store_port
    exports DTDL_STORE_ADDR to every child; with no store configured
    the children see whatever the environment inherits (an external
    coordinator) or NOTHING — never an address nothing listens on."""
    from dtdl_tpu.launch.local import launch_local
    prog = ("import os; "
            "print('ADDR=' + os.environ.get('DTDL_STORE_ADDR', 'unset'))")
    monkeypatch.delenv("DTDL_STORE_ADDR", raising=False)
    rc = launch_local(["-c", prog], nproc=2, port=12421,
                      store_port=12422, timeout=60)
    out = capfd.readouterr().out
    assert rc == 0, out
    assert out.count("ADDR=127.0.0.1:12422") == 2
    # no store configured: nothing is advertised...
    rc = launch_local(["-c", prog], nproc=1, port=12423, timeout=60)
    out = capfd.readouterr().out
    assert rc == 0 and "ADDR=unset" in out
    # ...and an inherited external coordinator flows through untouched
    monkeypatch.setenv("DTDL_STORE_ADDR", "coordhost:12801")
    rc = launch_local(["-c", prog], nproc=1, port=12424, timeout=60)
    out = capfd.readouterr().out
    assert rc == 0 and "ADDR=coordhost:12801" in out


def test_local_launcher_serves_store_for_children(capfd):
    """serve_store=True hosts the TCP coordinator in the launcher
    process; two child PROCESSES coordinate through it (an add each,
    then a blocking wait on the key the second arrival sets)."""
    from dtdl_tpu.launch.local import launch_local
    # membership via per-process SET keys, not add(): the overwrite
    # verbs are exactly-once under the retry facade (see connect())
    prog = (
        "import os, time\n"
        "from dtdl_tpu.parallel.tcpstore import connect\n"
        "rs = connect(retries=5)\n"
        "rs.set(f'join/{os.getpid()}', True)\n"
        "deadline = time.time() + 60\n"
        "while len(rs.keys('join/')) < 2:\n"
        "    assert time.time() < deadline\n"
        "    time.sleep(0.01)\n"
        "rs.set('both', True)\n"
        "rs.wait('both', timeout_s=60)\n"
        "print('STORE-OK')\n"
    )
    rc = launch_local(["-c", prog], nproc=2, port=12425,
                      serve_store=True, timeout=120)
    out = capfd.readouterr().out
    assert rc == 0, out
    assert out.count("STORE-OK") == 2


def test_initialize_publishes_store_addr(monkeypatch):
    """runtime.initialize(store_addr=...) publishes DTDL_STORE_ADDR
    even for a single-process run — the control plane outlives any one
    JAX world."""
    from dtdl_tpu.runtime import bootstrap
    monkeypatch.setenv("DTDL_STORE_ADDR", "stale:1")
    bootstrap.initialize(store_addr="127.0.0.1:9999")
    assert os.environ["DTDL_STORE_ADDR"] == "127.0.0.1:9999"


def test_tpu_vm_run_elastic_restart(tmp_path, capsys):
    """tpu_vm.run with max_restarts relaunches the slice after a failure."""
    from dtdl_tpu.launch.tpu_vm import run
    marker = tmp_path / "crashed_once"
    prog = (
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(9)\n"
        "print('slice recovered')\n"
    )
    cmds = [[sys.executable, "-c", prog] for _ in range(2)]
    rc = run(["h0", "h1"], cmds, poll_interval=0.1, max_restarts=1,
             restart_delay=0.1)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "relaunching 2 workers" in out
    assert "slice recovered" in out


@pytest.mark.slow
def test_local_launcher_two_process_4d(capfd):
    """2 processes x 4 CPU devices: the FULL 4D step (interleaved 1F1B +
    routed MoE) with the 'data' axis spanning the process (DCN) boundary —
    grad reduction crosses hosts, pipe/tensor collectives stay local.
    (slow: ~70 s — two fresh interpreters compile the 4D program)"""
    from dtdl_tpu.launch.local import launch_local
    rc = launch_local(
        [os.path.join(REPO, "tests", "_rendezvous_4d_script.py")],
        nproc=2, port=12415, devices_per_proc=4, timeout=420)
    out = capfd.readouterr().out
    assert rc == 0, out
    results = re.findall(
        r"RESULT4D process=(\d) loss=([\d.]+) dropped=([\d.]+) "
        r"digest=([\d.]+)", out)
    assert len(results) == 2, out
    assert {r[0] for r in results} == {"0", "1"}
    # the loss/metrics are fully psummed and params replicated over 'data':
    # both hosts must agree exactly
    assert results[0][1] == results[1][1]
    assert results[0][2] == results[1][2]
    assert results[0][3] == results[1][3]
