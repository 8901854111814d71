"""The pinned program contracts: audit the REAL hot-path programs.

Four programs carry this repo's performance story — the strategy train
step, the 4D megatron step, and the serving decode/verify pair.  This
module builds each one at a tiny fixed geometry (the audit is about
program *shape* — which collectives, what aliasing, any host traffic —
never about model quality, so small and fast is correct) and runs both
auditors over it:

* jaxpr level (dtdl_tpu/analysis/jaxpr_audit.py): callbacks, captured
  constants, the manual-SPMD collective census;
* compiled level (dtdl_tpu/analysis/hlo_audit.py): donation aliasing
  (the train step's state and the engines' KV arena MUST be donated),
  host transfers in the optimized module, the GSPMD collective census.

The result is compared against the checked-in baseline
(``dtdl_tpu/analysis/baselines.json``): any drift — a new all-gather
from a changed sharding, a lost ``donate_argnums``, a debug callback
left in a step — fails by name (rule ``census-drift`` or the auditor's
own finding) in tests/test_analysis_contracts.py and in
``scripts/audit.py --programs``.  Regenerate the baseline with
``scripts/audit.py --programs --rebase`` after an *intentional*
program-shape change, and say why in the PR.
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from dtdl_tpu.analysis.findings import Finding
from dtdl_tpu.analysis.hlo_audit import arg_leaf_indices, audit_compiled
from dtdl_tpu.analysis.jaxpr_audit import audit_jaxpr

#: program name -> builder; the contract surface of this module
PROGRAMS = ("train_step", "megatron_step", "serve_decode",
            "serve_verify", "serve_lora_decode")

#: devices each pinned geometry needs (train_step adapts to the local
#: mesh; the 4D megatron step is pinned at its (1, 1, 2, 4) mesh)
MIN_DEVICES = {"train_step": 1, "megatron_step": 8, "serve_decode": 1,
               "serve_verify": 1, "serve_lora_decode": 1}


def runnable_programs(names=PROGRAMS) -> tuple[list, list]:
    """Split ``names`` into (runnable, skipped) for THIS process's
    device count — scripts/audit.py runs outside the test
    harness's forced 8-device CPU platform, where the megatron
    geometry cannot build; skipping it loudly beats an error row."""
    n = jax.device_count()
    run = [p for p in names if MIN_DEVICES[p] <= n]
    return run, [p for p in names if p not in run]

#: census fields compared against the baseline (the rest of a report —
#: memory stats, eqn counts — is receipt, not contract)
BASELINE_FIELDS = ("jaxpr_collectives", "hlo_collectives",
                   "host_transfers", "callbacks", "bf16_to_f32_casts",
                   "donation_ok")


def baseline_path() -> pathlib.Path:
    return pathlib.Path(__file__).with_name("baselines.json")


def load_baseline() -> dict:
    p = baseline_path()
    return json.loads(p.read_text()) if p.exists() else {}


# ---------------------------------------------------------------------------
# program builders: (jitted, args, donate_argnums) at tiny fixed geometry
# ---------------------------------------------------------------------------

def _build_train_step():
    """The strategy train step (make_train_step under DataParallel on
    the full local mesh) — the PR 1 hot loop."""
    import optax

    from dtdl_tpu.models.mlp import MLP
    from dtdl_tpu.parallel.strategy import DataParallel
    from dtdl_tpu.train.state import init_state
    from dtdl_tpu.train.step import make_train_step

    n = jax.device_count()
    model = MLP(n_units=16, n_out=8)
    example = jnp.zeros((n, 12), jnp.float32)
    state = init_state(model, jax.random.PRNGKey(0), example,
                       optax.sgd(0.1))
    strategy = DataParallel()
    step = make_train_step(strategy)
    batch = {"image": jnp.zeros((2 * n, 12), jnp.float32),
             "label": jnp.zeros((2 * n,), jnp.int32)}
    return step, (state, batch), (0,)


def _build_megatron_step():
    """The 4D megatron step on a (1, 1, pipe=2, model=4) mesh — the
    manual-SPMD face, whose psums are hand-placed and must stay put."""
    import optax

    from dtdl_tpu.parallel import megatron as M
    from dtdl_tpu.runtime.mesh import build_mesh

    cfg = M.MegatronConfig(vocab_size=64, d_model=32, n_heads=4,
                           d_ff=64, n_stages=2, layers_per_stage=1,
                           n_microbatches=2, max_seq=32,
                           dtype=jnp.float32)
    mesh = build_mesh(shape=(1, 1, 2, 4), axes=M.AXES,
                      devices=jax.devices()[:8])
    opt = optax.sgd(0.1)
    params = M.place_params(
        mesh, cfg, jax.device_get(
            M.init_params(cfg, jax.random.PRNGKey(0))))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, {
        "tokens": np.zeros((2, 16), np.int32),
        "targets": np.zeros((2, 16), np.int32),
        "mask": np.ones((2, 16), np.float32)})
    args = (params, opt_state, batch["tokens"], batch["targets"],
            batch["mask"])
    return step, args, (0, 1)


def _tiny_engine():
    import flax.linen as nn

    from dtdl_tpu.models.transformer import transformer_lm
    from dtdl_tpu.serve.engine import InferenceEngine

    model = transformer_lm("tiny", vocab_size=64, d_model=32,
                           n_layers=2, n_heads=2, d_ff=64, max_seq=32,
                           attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    return InferenceEngine(model, params, n_slots=2, buckets=(8,))


def _build_serve_decode():
    """The ONE decode program every serving token rides (PR 2):
    zero host transfers is its entire reason to exist."""
    from dtdl_tpu.serve.sampling import SampleParams, pack

    eng = _tiny_engine()
    fn = eng._build_decode()
    args = (eng.params, eng.init_arena(), eng.init_last_tokens(),
            jnp.ones((eng.n_slots,), bool), jnp.zeros((), jnp.int32),
            jax.random.PRNGKey(0), *pack([SampleParams()] * eng.n_slots),
            jnp.ones((eng.n_slots, 64), bool),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    return fn, args, (1,)


def _build_serve_verify():
    """The k-wide verify program (PR 4 spec decode + round-19 chunked
    prefill share it) at k=2."""
    from dtdl_tpu.serve.sampling import SampleParams, pack

    eng = _tiny_engine()
    k = 2
    fn = eng._build_verify(k)
    B = eng.n_slots
    args = (eng.params, eng.init_arena(), eng.init_last_tokens(),
            jnp.zeros((B, k), jnp.int32), jnp.ones((B,), jnp.int32),
            jnp.ones((B,), bool), jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((), jnp.int32), jax.random.PRNGKey(0),
            *pack([SampleParams()] * B),
            jnp.ones((B, k + 1, 64), bool),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    return fn, args, (1,)


def _build_serve_lora_decode():
    """The decode program of a multi-LoRA engine (round 22): the bank
    gather must add no collectives and no host transfers — adapter ids
    and the bank itself ride in as data."""
    import flax.linen as nn

    from dtdl_tpu.models.transformer import transformer_lm
    from dtdl_tpu.serve.engine import InferenceEngine
    from dtdl_tpu.serve.sampling import SampleParams, pack

    model = transformer_lm("tiny", vocab_size=64, d_model=32,
                           n_layers=2, n_heads=2, d_ff=64, max_seq=32,
                           attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    eng = InferenceEngine(model, params, n_slots=2, buckets=(8,),
                          lora_rank=2, lora_adapters=2)
    fn = eng._build_decode()
    B = eng.n_slots
    args = (eng.params, eng.init_arena(), eng.init_last_tokens(),
            jnp.ones((B,), bool), jnp.zeros((), jnp.int32),
            jax.random.PRNGKey(0), *pack([SampleParams()] * B),
            jnp.ones((B, 64), bool),
            jnp.zeros((B,), jnp.int32), eng.adapter_bank.bank)
    return fn, args, (1,)


_BUILDERS = {"train_step": _build_train_step,
             "megatron_step": _build_megatron_step,
             "serve_decode": _build_serve_decode,
             "serve_verify": _build_serve_verify,
             "serve_lora_decode": _build_serve_lora_decode}


# ---------------------------------------------------------------------------
# auditing + baseline comparison
# ---------------------------------------------------------------------------

def audit_one(name: str) -> dict:
    """Build + audit one pinned program; returns the JSON-able report
    (``findings`` rendered, census fields flat)."""
    fn, args, donate = _BUILDERS[name]()
    ja = audit_jaxpr(fn, *args, name=name)
    expect = arg_leaf_indices(args, set(donate))
    ha = audit_compiled(fn, *args, name=name, expect_donated=expect)
    findings = ja.findings + ha.findings
    donation_ok = not any(f.rule == "hlo-undonated" for f in findings)
    mem = ha.census.get("memory") or {}
    return {
        "jaxpr_collectives": ja.census["collectives"],
        "hlo_collectives": ha.census["collectives"],
        "host_transfers": ha.census["host_transfers"],
        "callbacks": ja.census["callbacks"],
        "bf16_to_f32_casts": ja.census["bf16_to_f32_casts"],
        "donation_ok": donation_ok,
        # receipts (not baseline-compared): sizes drift with geometry
        "donated_bytes": mem.get("alias_bytes", 0),
        "const_bytes": ja.census["const_bytes"],
        "n_donated_args": len(ha.census["donated_args"]),
        "n_expected_donated": len(expect),
        "findings": [f.render() for f in findings],
        "_findings": findings,
    }


def audit_programs(names=PROGRAMS) -> dict:
    return {n: audit_one(n) for n in names}


def compare_to_baseline(reports: dict, baseline: dict) -> list[Finding]:
    """Named drift findings: every BASELINE_FIELDS mismatch between a
    report and the checked-in baseline, plus missing baselines."""
    out = []
    for name, rep in reports.items():
        base = baseline.get(name)
        if base is None:
            out.append(Finding(
                "census-drift", name, 0,
                "no checked-in baseline — run scripts/audit.py "
                "--programs --rebase and commit baselines.json"))
            continue
        for field in BASELINE_FIELDS:
            got, want = rep.get(field), base.get(field)
            if got != want:
                out.append(Finding(
                    "census-drift", name, 0,
                    f"{field} drifted from baseline: {want!r} -> "
                    f"{got!r} (intentional? scripts/audit.py "
                    f"--programs --rebase)",
                    detail={"field": field, "baseline": want,
                            "got": got}))
    return out


def save_baseline(reports: dict) -> pathlib.Path:
    """Write the comparable census subset as the new baseline."""
    slim = {name: {f: rep[f] for f in BASELINE_FIELDS}
            for name, rep in sorted(reports.items())}
    p = baseline_path()
    p.write_text(json.dumps(slim, indent=2, sort_keys=True) + "\n")
    return p
