"""Local multi-process launcher.

The TPU analogue of ``torch.multiprocessing.spawn`` (reference
pytorch/distributed_data_parallel.py:53-56) and the reference's manual
one-shell-per-rank launch procedure (reference pytorch/README.md:69-113,
which literally asks the user to open four terminals): spawn N processes of a
training script on this host, each told the shared coordinator address and
its process id, with rank-prefixed log streaming and fail-fast on a dead rank
(the reference's jobs simply hang when a rank dies — SURVEY §5.3).

Used for multi-process worlds on CPU (``--devices-per-proc N`` gives each
process its own virtual CPU devices; ``JAX_PLATFORMS=cpu`` in the
environment does the same with one each) and for one process per host.

It does NOT put several processes on one TPU host: a chip belongs to one
process.  Seen on a four-chip v5e host with two children: one opens every
chip, the other aborts in backend start-up ("libtpu multi-process
lockfile"), neither exits, and the job hangs until the timeout.  On one
TPU host ONE process drives all chips (``--strategy ddp``, or the 4D
mesh), so a launch that would start more than one TPU-opening child is
refused by name before anything is spawned
(:func:`_refuse_tpu_siblings`).

CLI:  python -m dtdl_tpu.launch.local --nproc 2 [--port 12355] -- script.py --flags
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import threading
import time


def _host_has_tpu() -> bool:
    """TPU chips on this host, read from their device files (v2-v4
    expose /dev/accel*, v5e and later /dev/vfio/<n>) — the launcher must
    never import a JAX backend itself: a parent that touched JAX holds
    the chip its children need."""
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _refuse_tpu_siblings(nproc: int, devices_per_proc: int | None,
                         env: dict) -> None:
    """Raise unless at most one child would open this host's TPU.

    Children take the TPU when ``JAX_PLATFORMS`` names it, or names
    nothing on a host that has chips (jax then picks the TPU itself).
    """
    if nproc <= 1 or devices_per_proc is not None:
        return
    platforms = [p for p in env.get("JAX_PLATFORMS", "").split(",") if p]
    if "tpu" in platforms or (not platforms and _host_has_tpu()):
        raise RuntimeError(
            f"launch.local: refusing to start {nproc} processes that "
            f"would each open this host's TPU (JAX_PLATFORMS="
            f"{env.get('JAX_PLATFORMS', '')!r}): a chip belongs to one "
            f"process, so the siblings abort or hang at start-up.  On one "
            f"TPU host run ONE process over all chips (e.g. "
            f"`python examples/train_lm.py --strategy ddp`); for a CPU "
            f"world pass --devices-per-proc N or set JAX_PLATFORMS=cpu; "
            f"for a multi-host job start one process per host.")


def launch_local(script_args: list[str], nproc: int = 2, port: int = 12355,
                 env_extra: dict | None = None, timeout: float = 600.0,
                 devices_per_proc: int | None = None,
                 max_restarts: int = 0, store_port: int | None = None,
                 serve_store: bool = False,
                 store_wal_dir: str | None = None) -> int:
    """Spawn ``nproc`` processes of a script; non-zero if any rank failed.

    ``max_restarts`` adds elastic recovery beyond the reference (whose jobs
    hang forever on a dead rank, SURVEY §5.3): after a failed attempt the
    WHOLE world is relaunched — ranks resume from their latest checkpoint
    (Trainer/Estimator/Solver all restore from their output directory), the
    standard checkpoint-restart model for synchronous SPMD where a lost
    participant invalidates the collective world.

    Each child receives ``--coordinator 127.0.0.1:port --num-processes nproc
    --process-id i`` appended to its argv (the script is expected to pass
    them to `dtdl_tpu.runtime.initialize`).  Output is streamed line-by-line
    with a ``[rank i]`` prefix (the reference prints rank-prefixed lines from
    each DDP worker, pytorch/distributed_data_parallel.py:144-148).  If any
    process dies — non-zero exit *or* a signal — the rest are terminated and
    the dying rank's code is returned: fail fast instead of the reference's
    silent hang.

    The elastic control-plane store (ISSUE 13): ``serve_store=True``
    hosts the :class:`~dtdl_tpu.parallel.tcpstore.TCPStoreServer` *in
    the launcher process* (the coordinator host, which outlives any
    worker; optional WAL dir for crash recovery — the server spans
    restart attempts exactly like a real coordinator spans a worker
    relaunch) and threads its address to every child as
    ``DTDL_STORE_ADDR`` (``127.0.0.1:{store_port}``, defaulting to the
    coordinator port + 1), so worker scripts reach it with
    ``dtdl_tpu.parallel.tcpstore.connect()`` and no extra flags.  An
    explicit ``store_port`` exports the address without serving (the
    operator runs the server); otherwise the variable is only what the
    children inherit from the environment — an address is never
    advertised unless something actually listens there.
    """
    _refuse_tpu_siblings(nproc, devices_per_proc,
                         {**os.environ, **(env_extra or {})})
    # DTDL_STORE_ADDR is exported to children ONLY when a store
    # actually exists: serve_store / an explicit store_port (operator
    # intent: "my server is there"), or an inherited env value (an
    # external coordinator — flows through dict(os.environ) untouched).
    # Advertising the derived default with nothing listening would
    # turn the crisp "no store address" error into a slow
    # retry-to-death against a dead port.
    explicit = store_port is not None or serve_store
    store_port = store_port if store_port is not None else port + 1
    store_addr = f"127.0.0.1:{store_port}" if explicit else None
    server = None
    if serve_store:
        from dtdl_tpu.parallel.tcpstore import TCPStoreServer
        server = TCPStoreServer(port=store_port,
                                wal_dir=store_wal_dir).start()
    try:
        attempt = 0
        while True:
            rc = _launch_once(script_args, nproc, port, env_extra,
                              timeout, devices_per_proc, store_addr)
            if rc == 0 or attempt >= max_restarts:
                return rc
            attempt += 1
            print(f"[launcher] attempt {attempt}/{max_restarts}: "
                  f"relaunching all {nproc} ranks (resume from latest "
                  f"checkpoint)", flush=True)
    finally:
        if server is not None:
            server.stop()


def _launch_once(script_args: list[str], nproc: int, port: int,
                 env_extra: dict | None, timeout: float,
                 devices_per_proc: int | None,
                 store_addr: str | None = None) -> int:
    procs: list[subprocess.Popen] = []
    coordinator = f"127.0.0.1:{port}"
    for i in range(nproc):
        env = dict(os.environ)
        if store_addr:
            env["DTDL_STORE_ADDR"] = store_addr
        if env_extra:
            env.update(env_extra)
        if devices_per_proc is not None:
            # carve CPU devices per process for single-host rendezvous tests
            env["JAX_PLATFORMS"] = "cpu"
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{devices_per_proc}").strip()
        cmd = [sys.executable, *script_args,
               "--coordinator", coordinator,
               "--num-processes", str(nproc),
               "--process-id", str(i)]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1))

    def pump(i: int, p: subprocess.Popen):
        for line in p.stdout:  # blocking per-thread read; no buffer stalls
            print(f"[rank {i}] {line}", end="", flush=True)

    threads = [threading.Thread(target=pump, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    for t in threads:
        t.start()

    deadline = time.time() + timeout
    first_failure = 0
    failed = False
    while any(p.poll() is None for p in procs):
        if time.time() > deadline:
            print(f"[launcher] timeout after {timeout}s; killing", flush=True)
            for p in procs:
                if p.poll() is None:
                    p.kill()
            first_failure = first_failure or 124
            break
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and rc != 0 and not failed:
                failed = True
                first_failure = rc
                print(f"[launcher] rank {i} exited with {rc}; "
                      "terminating remaining ranks", flush=True)
                for q in procs:
                    if q.poll() is None:
                        q.terminate()
        time.sleep(0.2)
    rcs = [p.wait() for p in procs]
    for t in threads:
        t.join(timeout=5)
    if first_failure:
        return first_failure
    return next((rc for rc in rcs if rc != 0), 0)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    nproc, port, devices, restarts = 2, 12355, None, 0
    store_port, serve_store, store_wal = None, False, None
    while argv and argv[0] != "--":
        if argv[0] == "--nproc":
            nproc = int(argv[1]); argv = argv[2:]
        elif argv[0] == "--port":
            port = int(argv[1]); argv = argv[2:]
        elif argv[0] == "--devices-per-proc":
            devices = int(argv[1]); argv = argv[2:]
        elif argv[0] == "--max-restarts":
            restarts = int(argv[1]); argv = argv[2:]
        elif argv[0] == "--store-port":
            store_port = int(argv[1]); argv = argv[2:]
        elif argv[0] == "--serve-store":
            serve_store = True; argv = argv[1:]
        elif argv[0] == "--store-wal-dir":
            store_wal = argv[1]; argv = argv[2:]
        else:
            raise SystemExit(f"unknown launcher flag {argv[0]} "
                             "(use: --nproc N --port P -- script.py ...)")
    if argv and argv[0] == "--":
        argv = argv[1:]
    if not argv:
        raise SystemExit("no script given; usage: "
                         "python -m dtdl_tpu.launch.local --nproc 2 -- script.py")
    return launch_local(argv, nproc=nproc, port=port,
                        devices_per_proc=devices, max_restarts=restarts,
                        store_port=store_port, serve_store=serve_store,
                        store_wal_dir=store_wal)


if __name__ == "__main__":
    raise SystemExit(main())
