"""THE subprocess elastic drills (ISSUE 13 acceptance): real OS
processes, real sockets, real signals.

PR 12 proved the elastic machine over threads sharing a dict; this
file converts those claims into multi-process ones:

1. **kill-one-of-four, for real** — 4 subprocess workers rendezvous
   through a TCP store; rank 2 is SIGKILLed by the kernel at the top
   of step 5 (mid-epoch: no atexit, no flush, its sockets just die).
   Survivors detect via TCP-side lease expiry (the store stamps beats
   on ITS clock), re-form a generation-fenced world of 3, restore the
   last committed snapshot, and finish **bitwise equal** to a
   fault-free shrunken oracle run in-process over ``HostKVStore`` from
   the same snapshot — one problem, two hosting models AND two store
   backends agreeing to the last bit.  The zero-lost/zero-dup audit
   reads per-step journals flushed by every worker INCLUDING the
   victim's pre-crash lines (a SIGKILL preserves what was flushed).
2. **kill the coordinator, for real** (slow) — the store itself runs
   as a subprocess; the parent SIGKILLs it mid-run and restarts it
   from its WAL.  Workers ride the outage inside their transport
   budgets: nobody is declared dead (recovery re-stamps leases), the
   world never shrinks, and the sample accounting stays exact.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import _elastic_worker_script as ws
from dtdl_tpu.parallel.kvstore import HostKVStore, RetryingStore
from dtdl_tpu.parallel.tcpstore import TCPStoreServer
from dtdl_tpu.resil import ElasticWorker, run_workers

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = os.path.join(HERE, "_elastic_worker_script.py")


def child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


def spawn_worker(rank, addr, ckpt_dir, out_dir, die_at=None,
                 steps=ws.STEPS):
    cmd = [sys.executable, SCRIPT, "--store-addr", addr,
           "--rank", str(rank), "--ckpt-dir", ckpt_dir,
           "--out-dir", out_dir, "--steps", str(steps)]
    if die_at is not None:
        cmd += ["--die-at", str(die_at)]
    return subprocess.Popen(cmd, env=child_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def read_result(out_dir, rank):
    with open(os.path.join(out_dir, f"result_{rank}.json")) as f:
        return json.load(f)


def effective_from_journals(out_dir, ranks):
    """The surviving timeline rebuilt from the per-rank durable
    journals — the subprocess twin of ``effective_sample_log`` (which
    needs in-memory worker objects a SIGKILL destroys)."""
    top, logs = {}, {}
    for r in ranks:
        path = os.path.join(out_dir, f"samples_{r}.jsonl")
        if not os.path.exists(path):
            continue
        for line in open(path):
            rec = json.loads(line)
            logs[(r, rec["gen"], rec["step"])] = rec["idx"]
            top[rec["step"]] = max(top.get(rec["step"], rec["gen"]),
                                   rec["gen"])
    eff = {}
    for step, gen in top.items():
        shards = [logs[(r, gen, step)] for r in ranks
                  if (r, gen, step) in logs]
        eff[step] = np.sort(np.concatenate(
            [np.asarray(s, int) for s in shards]))
    return eff


def assert_zero_lost_zero_dup(eff, steps):
    sampler = ws.mk_sampler()
    assert sorted(eff) == list(range(steps))
    for step, consumed in eff.items():
        np.testing.assert_array_equal(
            consumed, np.sort(sampler.batch_indices(step)))


# ---------------------------------------------------------------------------
# 1. SIGKILL a real worker process mid-epoch (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.subprocess
@pytest.mark.elastic
@pytest.mark.faults
def test_subprocess_sigkill_one_worker_shrinks_bitwise_exact(tmp_path):
    wal = str(tmp_path / "wal")
    ck = str(tmp_path / "ck")
    out = str(tmp_path / "out")
    for d in (ck, out):
        os.makedirs(d)
    srv = TCPStoreServer(wal_dir=wal).start()
    try:
        procs = {r: spawn_worker(r, srv.addr, ck, out,
                                 die_at=5 if r == 2 else None)
                 for r in (0, 1, 2, 3)}
        rcs = {r: p.wait(timeout=120) for r, p in procs.items()}
        logs = {r: p.stdout.read() for r, p in procs.items()}
        # the victim died BY SIGNAL — a kernel kill, not a python exit
        assert rcs[2] == -signal.SIGKILL, logs[2]
        for r in (0, 1, 3):
            assert rcs[r] == 0, f"rank {r}:\n{logs[r]}"
    finally:
        srv.stop()

    results = {r: read_result(out, r) for r in (0, 1, 3)}
    named = set()
    for r, res in results.items():
        assert res["done"] and res["error"] is None
        # survivors re-formed a generation-fenced world of 3
        assert res["generation"] == 1 and res["ranks"] == [0, 1, 3]
        named |= set(res["lost"])
    # TCP-side lease expiry NAMED the dead rank (detection was
    # lease-driven: the 0.6s watchdog, not the 20s step deadline —
    # the whole 4-process drill finishing inside the 120s cap while
    # every survivor restored and re-trained pins that arithmetic)
    assert named == {2}
    restored = {res["restored_step"] for res in results.values()}
    assert len(restored) == 1
    restored = restored.pop()
    assert 0 < restored < ws.STEPS

    # zero lost / zero double-counted across a REAL process death:
    # journals include the victim's flushed pre-crash consumption
    eff = effective_from_journals(out, (0, 1, 2, 3))
    assert_zero_lost_zero_dup(eff, ws.STEPS)

    # bitwise-equal to the fault-free shrunken oracle: the same
    # problem, hosted in-process over HostKVStore, restored from the
    # SAME committed snapshot the subprocess leader wrote
    path = os.path.join(ck, f"elastic_{restored:06d}.msgpack")
    assert os.path.exists(path)
    store_b = HostKVStore()
    store_b.set("ckpt/committed", {"step": restored, "path": path})
    oracle = [ElasticWorker(RetryingStore(store_b), r,
                            init_fn=ws.init_fn, grad_fn=ws.grad_fn,
                            apply_fn=ws.apply_fn, batch_fn=ws.batch_fn,
                            sampler=ws.mk_sampler(),
                            total_steps=ws.STEPS, cfg=ws.mk_cfg())
              for r in (0, 1, 3)]
    run_workers(oracle, timeout_s=60)
    for w in oracle:
        assert w.done
        want = np.asarray(w.state["w"]).tolist()
        for r in (0, 1, 3):
            assert results[r]["params_w"] == want, (
                f"rank {r} diverged from the shrunken oracle")


# ---------------------------------------------------------------------------
# 2. SIGKILL the real coordinator process mid-run, restart from WAL
# ---------------------------------------------------------------------------

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn_store(port, wal):
    p = subprocess.Popen(
        [sys.executable, "-m", "dtdl_tpu.parallel.tcpstore",
         "--port", str(port), "--wal-dir", wal],
        env=child_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    line = p.stdout.readline()          # blocks until "STORE ready ..."
    assert "STORE ready" in line, line
    return p, line


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.elastic
@pytest.mark.faults
def test_subprocess_coordinator_sigkill_and_wal_restart(tmp_path):
    """The heaviest drill: coordinator AND workers are all real
    processes; the coordinator is SIGKILLed mid-run and restarted from
    its WAL.  Synchronization is event-driven throughout: the kill
    waits for journal lines proving training started, the restart
    waits for the new server's ready line — no sleeps as ordering."""
    wal = str(tmp_path / "wal")
    ck = str(tmp_path / "ck")
    out = str(tmp_path / "out")
    for d in (ck, out):
        os.makedirs(d)
    port = free_port()
    store_proc, _ = spawn_store(port, wal)
    addr = f"127.0.0.1:{port}"
    workers = {r: spawn_worker(r, addr, ck, out) for r in (0, 1, 2)}
    try:
        # wait until some worker has APPLIED step >= 2 (journal lines
        # are flushed per applied step) — the run is provably mid-epoch
        deadline = time.monotonic() + 60.0
        j0 = os.path.join(out, "samples_0.jsonl")
        while True:
            lines = open(j0).readlines() if os.path.exists(j0) else []
            if len(lines) >= 2:
                break
            assert time.monotonic() < deadline, "no training progress"
            time.sleep(0.02)
        # the kernel kills the coordinator, mid-whatever
        store_proc.kill()
        assert store_proc.wait(timeout=10) == -signal.SIGKILL
        # ... and it comes back from its WAL on the same port
        store_proc, ready = spawn_store(port, wal)
        assert "recovered=True" in ready
        rcs = {r: p.wait(timeout=180) for r, p in workers.items()}
        logs = {r: p.stdout.read() for r, p in workers.items()}
        for r in (0, 1, 2):
            assert rcs[r] == 0, f"rank {r}:\n{logs[r]}"
    finally:
        for p in workers.values():
            if p.poll() is None:
                p.kill()
        store_proc.kill()
        store_proc.wait(timeout=10)

    results = {r: read_result(out, r) for r in (0, 1, 2)}
    reconnects = 0
    for r, res in results.items():
        assert res["done"] and res["error"] is None
        # coordinator downtime is NOT peer death: the bootstrap world
        # survives intact — no shrink, no fence, generation 0
        assert res["generation"] == 0 and res["ranks"] == [0, 1, 2]
        reconnects += res["reconnects"]
    assert reconnects >= 1              # the outage really happened
    assert_zero_lost_zero_dup(effective_from_journals(out, (0, 1, 2)),
                              ws.STEPS)
