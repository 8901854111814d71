"""End-to-end example-script smoke tests (SURVEY §4: 'integration-test each
example end-to-end for loss decrease on MNIST subsets').

Each reference-parity script runs as a real subprocess on the fake-CPU
platform with a truncated synthetic dataset.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "examples")

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    "PYTHONPATH": REPO,
    # tests must never hit the network (or hang on a blackholed one)
    # for a throwaway tmp dataset dir — synthetic fallback is the point
    "DTDL_OFFLINE": "1",
}


def run_example(script, *args, timeout=420):
    proc = subprocess.run(
        [sys.executable, os.path.join(EX, script), *args],
        capture_output=True, text=True, timeout=timeout, env=CPU_ENV,
        cwd=EX)
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_mnist_single_example(tmp_path):
    out = run_example(
        "mnist_single.py", "--batch_size", "64", "--epochs", "4",
        "--learning_rate", "0.1", "--momentum", "0.9",
        "--limit-train", "512", "--limit-test", "256",
        "--dataset-dir", str(tmp_path / "none"),
        "--train_dir", str(tmp_path / "td"))
    m = re.search(r"Eval loss: ([\d.]+), Eval Accuracy: ([\d.]+)", out)
    assert m, out
    assert float(m.group(2)) > 0.5  # learns the synthetic task
    assert (tmp_path / "td" / "weights_epoch_0003.msgpack").exists()


@pytest.mark.slow
def test_mnist_mirror_strategy_example(tmp_path):
    out = run_example(
        "mnist_mirror_strategy.py", "--batch_size", "64", "--epochs", "1",
        "--limit-train", "512", "--limit-test", "256",
        "--dataset-dir", str(tmp_path / "none"),
        "--train_dir", str(tmp_path / "td"))
    assert "Mirrored DP over 4 local device(s)" in out


@pytest.mark.slow
def test_train_mnist_example_with_resume(tmp_path):
    out_dir = str(tmp_path / "result")
    common = ["-b", "100", "-u", "64", "--limit-train", "500",
              "--limit-test", "200", "--dataset-dir", str(tmp_path / "none"),
              "-o", out_dir]
    out = run_example("train_mnist.py", "-e", "2", *common)
    assert "val_accuracy" in out
    # snapshot dirs only — snapshot_N.meta.json sidecars are not resumable
    snaps = [d for d in os.listdir(out_dir)
             if re.fullmatch(r"snapshot_\d+", d)]
    assert snaps, os.listdir(out_dir)
    latest = max(snaps, key=lambda d: int(d.split("_")[1]))
    # resume from the snapshot into a longer run
    out2 = run_example("train_mnist.py", "-e", "3", "-r",
                       os.path.join(out_dir, latest), *common)
    assert "val_accuracy" in out2


@pytest.mark.slow
def test_train_mnist_gpu_example(tmp_path):
    out = run_example(
        "train_mnist_gpu.py", "-b", "100", "-e", "1", "-u", "32",
        "--limit-train", "400", "--limit-test", "200",
        "--dataset-dir", str(tmp_path / "none"),
        "-o", str(tmp_path / "result"))
    assert "DP over 4 local device(s)" in out


@pytest.mark.slow
def test_train_mnist_multi_example_two_processes(tmp_path):
    """ChainerMN-parity script through the local launcher, 2 procs."""
    proc = subprocess.run(
        [sys.executable, "-m", "dtdl_tpu.launch.local",
         "--nproc", "2", "--port", "12455", "--devices-per-proc", "2", "--",
         os.path.join(EX, "train_mnist_multi.py"),
         "-b", "80", "-e", "1", "-u", "32",
         "--limit-train", "400", "--limit-test", "160",
         "--dataset-dir", str(tmp_path / "none"),
         "-o", str(tmp_path / "result")],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": REPO}, cwd=EX)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Num process (COMM_WORLD): 2" in proc.stdout
    assert "val_accuracy" in proc.stdout


@pytest.mark.slow
def test_single_device_example_tiny(tmp_path):
    """PyramidNet path compiles are heavy on CPU; use 300 examples, 1 epoch
    of a few steps to exercise the script end-to-end."""
    out = run_example(
        "single_device.py", "--batch-size", "100", "--epochs", "1",
        "--limit-train", "300", "--limit-test", "100",
        "--dataset-dir", str(tmp_path / "none"),
        "--out", str(tmp_path / "out"), "--dtype", "float32",
        timeout=900)
    assert "Epoch [0]" in out
    assert (tmp_path / "out" / "pyramidnet_final.msgpack").exists()


@pytest.mark.slow
def test_mxnet_kvstore_example(tmp_path):
    """MXNet-idiom Module.fit over a dist_sync KVStore (4 fake devices)."""
    out = run_example(
        "mxnet_kvstore.py", "--kv-store", "dist_sync", "--batch-size", "64",
        "--num-epochs", "1", "--limit-train", "512", "--limit-test", "256",
        "--dataset-dir", str(tmp_path / "none"), "--out", str(tmp_path / "o"))
    assert "kvstore: kind=dist_sync rank=0 num_workers=1 width=4" in out
    m = re.search(r"Validation-accuracy=([\d.]+)", out)
    assert m, out
    assert (tmp_path / "o" / "mxnet_cnn.msgpack").exists()


@pytest.mark.slow
def test_train_lm_example(tmp_path):
    """DP causal-LM training decreases loss on the Markov synthetic task."""
    out = run_example(
        "train_lm.py", "--epochs", "1", "--batch-size", "32",
        "--seq-len", "64", "--model-size", "tiny",
        "--out", str(tmp_path / "out"))
    losses = [float(m) for m in re.findall(r"loss: ([\d.]+)", out)]
    assert len(losses) >= 3, out
    assert losses[-1] < losses[0], losses
    assert (tmp_path / "out" / "lm_final.msgpack").exists()


@pytest.mark.slow
def test_train_lm_4d_example(tmp_path):
    """Full dp/sp/pp/tp+ep step over a 1,2,2,1 mesh (4 fake devices),
    with periodic held-out validation on the same mesh (the 4D eval
    step: reference evaluate-parity, tensorflow2/mnist_single.py:88-92)."""
    out = run_example(
        "train_lm_4d.py", "--steps", "3", "--batch-size", "8",
        "--seq-len", "64", "--n-experts", "2", "--mesh", "1,2,2,1",
        "--eval-interval", "2", "--eval-batches", "1",
        "--generate-tokens", "4")
    m = re.search(r"final loss ([\d.]+)", out)
    assert m, out
    assert float(m.group(1)) < 10.0
    vals = re.findall(r"val_loss: ([\d.]+)", out)
    # step 2 (interval) and step 3 (end-of-run, off-interval)
    assert len(vals) == 2, out
    assert all(0.0 < float(v) < 10.0 for v in vals)
    assert "val_accuracy" in out
    # the serving bridge decoded from the 4D-trained params
    g = re.search(r"generated: \[([\d, ]+)\]", out)
    assert g and len(g.group(1).split(",")) == 12, out  # 8 prompt + 4 new


@pytest.mark.slow   # tier-1 budget-discipline cut (round 22)
def test_train_lm_gspmd_example(tmp_path):
    """GSPMD expert-parallel LM training end-to-end: 'ep' rules on a
    (2,2) mesh (the CPU env fakes 4 devices), routed capacity dispatch —
    the compiler-partitioned MoE-at-scale path as a runnable script.
    (Fast-marked like the sibling 4D example test: tiny model, dense
    attention, ~15 s wall.)"""
    out = run_example(
        "train_lm_gspmd.py", "--rules", "ep", "--n-experts", "4",
        "--mesh", "2,2", "--steps", "10", "--batch-size", "8",
        "--seq-len", "64")
    first = re.search(r"step 0 \| loss: ([\d.]+)", out)
    final = re.search(r"final loss ([\d.]+) rules=ep", out)
    assert first and final, out
    # it actually learns: below both the step-0 loss and uniform ln(256)
    assert float(final.group(1)) < float(first.group(1))
    assert float(final.group(1)) < 5.545
    # held-out validation ran under the same shardings
    val = re.search(r"val_loss: ([\d.]+)", out)
    assert val and 0.0 < float(val.group(1)) < 10.0, out


@pytest.mark.slow
def test_caffe_train_example(tmp_path):
    out = run_example(
        "caffe_train.py", "--solver", "caffe/lenet_solver.prototxt",
        "--limit-train", "256", "--limit-test", "128", "-b", "32",
        "--max-iter", "80", "--dataset-dir", str(tmp_path / "none"),
        "--out", str(tmp_path / "snap"), timeout=600)
    m = re.search(r"test_accuracy': ([\d.]+)", out)
    assert m, out
    assert float(m.group(1)) > 0.5


@pytest.mark.slow
def test_tf_estimator_example(tmp_path):
    out = run_example(
        "tf_estimator.py", "--train_steps", "40",
        "--save_checkpoints_steps", "20", "--batch_size", "32",
        "--limit-train", "256", "--limit-test", "128",
        "--dataset-dir", str(tmp_path / "none"),
        "--model_dir", str(tmp_path / "est"), timeout=600)
    assert "final eval:" in out
    m = re.search(r"'accuracy': ([\d.]+)", out)
    assert m and float(m.group(1)) > 0.5, out


@pytest.mark.slow
def test_imagenet_resnet50_example(tmp_path):
    out = run_example(
        "imagenet_resnet50.py", "--steps", "6", "--batch-size", "8",
        "--image-size", "32", "--num-classes", "8",
        "--train-examples", "64", "--warmup-steps", "2",
        "--log-interval", "3", "--dtype", "float32",
        "--dataset-dir", str(tmp_path / "none"), timeout=600)
    assert "samples/sec" in out
    assert re.search(r"step 6/6", out), out


@pytest.mark.slow
def test_ddp_example_native_loader(tmp_path):
    """--num-workers routes the train pipeline through the native C++
    loader (falls back to Python transparently when unbuildable)."""
    from dtdl_tpu import native
    if not native.available():
        pytest.skip("native toolchain unavailable")
    out = run_example(
        "distributed_data_parallel.py", "--batch-size", "32",
        "--epochs", "1", "--num-workers", "2",
        "--limit-train", "128", "--limit-test", "64",
        "--dataset-dir", str(tmp_path / "none"),
        "--out", str(tmp_path / "o"), "--dtype", "float32", timeout=600)
    assert "DDP over 4 replicas" in out
    # the native loader actually ran (a silent Python fallback would pass
    # the other assertions too)
    assert "train loader: NativeDataLoader (2 workers)" in out
    assert "leader saved weights" in out


_HELP_SCRIPTS = [
    "single_device.py", "data_parallel.py", "distributed_data_parallel.py",
    "mnist_single.py", "mnist_mirror_strategy.py",
    "mnist_multi_worker_strategy.py", "train_mnist.py", "train_mnist_gpu.py",
    "train_mnist_multi.py", "mxnet_kvstore.py", "caffe_train.py",
    "tf_estimator.py", "train_lm.py", "train_lm_4d.py",
    "train_lm_gspmd.py", "imagenet_resnet50.py", "serve_fleet.py",
    "elastic_train.py",
]


_HELP_DRIVER = r"""
import io, runpy, sys, traceback
scripts = sys.argv[1:]
failures = []
for s in scripts:
    sys.argv = [s, "--help"]
    buf = io.StringIO()
    try:
        out, err = sys.stdout, sys.stderr
        sys.stdout = sys.stderr = buf
        try:
            runpy.run_path(s, run_name="__main__")
            failures.append(f"{s}: --help did not exit")
        except SystemExit as e:
            if e.code not in (0, None):
                failures.append(f"{s}: exit {e.code}\n{buf.getvalue()}")
        except BaseException:
            failures.append(f"{s}:\n{traceback.format_exc()}")
    finally:
        sys.stdout, sys.stderr = out, err
print("\n".join(failures) if failures else "ALL_HELP_OK")
sys.exit(1 if failures else 0)
"""


def test_every_example_parses_help():
    """Flag-surface smoke: argparse must build without alias collisions.

    All scripts run --help inside ONE subprocess (runpy), paying the ~3.5 s
    jax import once instead of 15x — this single-core box executes
    subprocesses serially, so per-script processes dominated the fast gate.
    """
    proc = subprocess.run(
        [sys.executable, "-c", _HELP_DRIVER] + _HELP_SCRIPTS,
        capture_output=True, text=True, timeout=300, env=CPU_ENV, cwd=EX)
    assert proc.returncode == 0 and "ALL_HELP_OK" in proc.stdout, (
        f"--help failures:\n{proc.stdout}\n{proc.stderr}")


@pytest.mark.slow
def test_train_lm_4d_checkpoint_resume(tmp_path):
    """True process-restart resume of the 4D path: a 3-step run that
    snapshots, then a fresh process resuming to step 6, must land on the
    same final loss as one uninterrupted 6-step process (sharded orbax
    restore against the abstract_state target)."""
    ck = str(tmp_path / "ck")
    common = ["--batch-size", "8", "--seq-len", "64", "--n-experts", "2",
              "--mesh", "1,2,2,1", "--log-interval", "2"]
    full = run_example("train_lm_4d.py", "--steps", "6",
                       "--out", str(tmp_path / "full"), *common)
    run_example("train_lm_4d.py", "--steps", "3", "--out", ck, *common)
    resumed = run_example("train_lm_4d.py", "--steps", "6", "--out", ck,
                          "--resume", *common)
    assert "resumed from snapshot at step 3" in resumed
    m_full = re.search(r"final loss ([\d.]+)", full)
    m_res = re.search(r"final loss ([\d.]+)", resumed)
    assert m_full and m_res, (full, resumed)
    assert m_full.group(1) == m_res.group(1), (full, resumed)


@pytest.mark.slow
def test_serve_lm_example():
    """Serving example end-to-end: continuous batching over synthetic
    traffic, compile counts stay bucketed (compile-heavy -> slow; the
    fast tier-1 serving coverage lives in tests/test_serve.py)."""
    out = run_example(
        "serve_lm.py", "--n-requests", "5", "--n-slots", "2",
        "--max-new-tokens", "6", "--harvest-lag", "2")
    assert re.search(r"served 5 requests", out), out
    assert "'decode': 1" in out, out


@pytest.mark.slow
@pytest.mark.elastic
def test_elastic_train_example_demo(tmp_path):
    """Elastic example end-to-end in --demo mode: a TCP coordinator, a
    crash-injected worker, survivors re-form and finish with identical
    param digests (compile-heavy -> slow; the fast TCP-store coverage
    lives in tests/test_tcpstore.py and tests/test_store_contract.py)."""
    out = run_example(
        "elastic_train.py", "--demo", "--steps", "6", "--workers", "3",
        "--ckpt-dir", str(tmp_path))
    assert "coordinator up at" in out, out
    assert re.search(r"rank 2 crashed at step 3; survivors detected",
                     out), out
    digests = re.findall(r"params_digest=([\d.]+)", out)
    assert len(digests) == 2 and digests[0] == digests[1], out


@pytest.mark.slow
@pytest.mark.fleet
def test_serve_fleet_example_kill_replica():
    """Fleet example end-to-end with the live-failover flag: replica 0
    dies mid-traffic, every request still finishes, nothing is lost
    (compile-heavy -> slow; fast fleet coverage in tests/test_fleet.py)."""
    out = run_example(
        "serve_fleet.py", "--n-requests", "10", "--n-slots", "2",
        "--max-new-tokens", "8", "--kill-replica-after", "4")
    assert re.search(r"served 10/10 requests", out), out
    assert "evicted replica 0" in out, out
    assert re.search(r"\[OK\]\s+requests lost: 0", out), out
