"""The program names its own device work and accounts for its own set-up.

* **scopes** — in the lowered LM train step every ``dot_general``, every op
  of a Pallas kernel and every op that comes from optax lies under a
  component of the catalogue in ``dtdl_tpu/obs/trace.py``; the jitted steps
  carry their own names;
* **the map** — ``device_component`` sends recorded name stacks to the right
  ``(component, pass)``;
* **kernel names** — the flash and paged ``pallas_call`` equations carry
  ``name=`` (read from the jaxpr, so the interpreter path shows it);
* **the compile account** — a fresh ``jit`` adds a trace, a lowering and a
  compile row under the function's name, a second call adds none, the
  listeners register once, ``Observer.summary()`` carries the totals.

The audit that holds ``DEVICE_SCOPES`` to the source tree sits with the
span/event audit in tests/test_obs_export.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.extend import core as jex_core

from dtdl_tpu.models import remat_plan
from dtdl_tpu.models.transformer import TransformerLM
from dtdl_tpu.obs import Observer
from dtdl_tpu.obs.trace import (_ATTN_OTHER, _ATTN_PROJECTIONS, _GDN_MODULES,
                                 _KDA_MODULES, _MLA_PROJECTIONS,
                                _MOE_MODULES, DEVICE_SCOPES, KERNEL_NAMES,
                                MODULE_SCOPES, STEP_NAMES, device_component)
from dtdl_tpu.parallel import DataParallel, SingleDevice
from dtdl_tpu.runtime import compile_cache
from dtdl_tpu.runtime.mesh import DATA_AXIS
from dtdl_tpu.train import (make_eval_step, make_lm_train_step,
                            make_predict_step, make_train_step)
from dtdl_tpu.train.state import TrainState

# ---------------------------------------------------------------------------
# the lowered step: every op with its whole name stack
# ---------------------------------------------------------------------------

_QUOTED = re.compile(r'"([^"]*)"')


def _op_stacks(lowered):
    """``[(op, name stack, from_optax)]`` of every stablehlo op.

    An op's location starts with its name stack, then the Python frames it
    came from.  jax lowers a jitted callee and a scan body inside a custom
    VJP as private functions, and a ``shard_map`` body as the region of an
    ``sdy.manual_computation``; the ops inside carry stacks relative to
    that function or region, and the ``call`` op or the region's op carries
    the caller's.  Joining the two is what XLA's call inliner does with
    ``op_name``, so a function called from several places gives each of its
    ops several stacks."""
    from jaxlib.mlir import ir

    def own_stack(op):
        found = _QUOTED.search(str(op.location))
        return found.group(1) if found else ""

    module = lowered.compiler_ir("stablehlo")
    ops, callers = {}, {}       # function -> its ops / its call sites
    for func in module.body.operations:
        name = ir.StringAttr(func.attributes["sym_name"]).value
        mine = ops.setdefault(name, [])

        def visit(op, name=name, mine=mine):
            op = op.operation
            loc, stack = str(op.location), own_stack(op)
            around = op.parent
            while around is not None and around.name != "func.func":
                if around.name == "sdy.manual_computation":
                    stack = own_stack(around) + "/" + stack
                around = around.parent
            if op.name in ("func.call", "stablehlo.call"):
                callee = ir.FlatSymbolRefAttr(op.attributes["callee"]).value
                callers.setdefault(callee, []).append((name, stack))
            elif op.name.startswith("stablehlo."):
                mine.append((op.name, stack, "/optax/" in loc))
            return ir.WalkResult.ADVANCE

        func.operation.walk(visit)

    def prefixes(func, seen=()):
        if func not in callers or func in seen:
            return [""]
        return [p + stack + "/" for caller, stack in callers[func]
                for p in prefixes(caller, seen + (func,))]

    return [(op, prefix + stack, optax_op)
            for func, rows in ops.items() for prefix in prefixes(func)
            for op, stack, optax_op in rows]


def _tiny_lm_step(strategy, vocab_chunk_size):
    model = TransformerLM(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                          d_ff=128, max_seq=64, attn_impl="flash",
                          remat=True, dtype=jnp.bfloat16)
    tokens = jnp.zeros((2 * strategy.num_replicas, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    step = make_lm_train_step(strategy, vocab_chunk_size=vocab_chunk_size)
    return step.lower(state, {"tokens": tokens})


@pytest.fixture(scope="module")
def two_devices(devices):
    return DataParallel(mesh=jax.sharding.Mesh(np.asarray(devices[:2]),
                                               (DATA_AXIS,)))


# the rung of the checkpoint plan (models/remat_plan.py) from which a block
# no longer runs a component's matmuls or kernel a second time
_KEPT_FROM = {"flash": 1, "attn_proj": 2, "mlp": 3}


@pytest.mark.parametrize("parallel, vocab_chunk_size, limit", [
    (False, 0, None), (False, 96, None), (True, 0, None), (True, 96, None),
    (False, 0, 2_100_000), (True, 96, 10**9)],
    ids=["single-dense_head", "single-chunked_loss", "ddp2-dense_head",
         "ddp2-chunked_loss", "single-dense_head-flash_kept",
         "ddp2-chunked_loss-all_kept"])
def test_lowered_lm_step_leaves_no_matmul_kernel_or_update_op_unscoped(
        parallel, vocab_chunk_size, limit, two_devices, monkeypatch):
    # no device here reports a memory limit, so the plan is rung 0 (every
    # component shows a recompute pass); with a limit the step keeps what it
    # buys, and the expectation below follows the plan it recorded (2.1 MB:
    # the tiny step's 1.93 MB estimate, a sixteenth of margin, 36 KB to keep)
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: limit)
    strategy = two_devices if parallel else SingleDevice()
    lowered = _tiny_lm_step(strategy, vocab_chunk_size)
    rungs = compile_cache.remat_plans()[-1].rungs
    assert rungs == {None: (0, 0), 2_100_000: (1, 1), 10**9: (3, 3)}[limit]
    assert "module @jit_lm_train_step" in lowered.as_text()
    rows = _op_stacks(lowered)
    seen = {}
    for op, stack, from_optax in rows:
        component, phase = device_component(stack)
        kernel = any(k in stack.split("/") for k in KERNEL_NAMES)
        if op == "stablehlo.dot_general" or from_optax or kernel:
            assert component is not None, (op, stack)
            assert "jit(lm_train_step)" in stack, (op, stack)
        if from_optax:
            assert (component, phase) == ("update", "update"), (op, stack)
        if kernel:
            assert component == "flash", (op, stack)
        if op == "stablehlo.all_reduce" and component is not None:
            seen.setdefault("all_reduce", set()).add(component)
        if op == "stablehlo.dot_general":
            seen.setdefault(component, set()).add(phase)
    # what the tiny step must show of each component (remat: the block's
    # forward runs again in the backward pass unless every block keeps its
    # outputs, the head's does not)
    for component, rung in _KEPT_FROM.items():
        every = {"forward", "backward"} | (
            {"recompute"} if min(rungs) < rung else set())
        assert seen[component] == every, (component, rungs)
    if vocab_chunk_size:
        assert seen["loss"] == {"forward", "backward"} and "head" not in seen
    else:
        assert seen["head"] == {"forward", "backward"} and "loss" not in seen
    if parallel:
        assert "grad_sync" in seen["all_reduce"]


def _tiny_classifier_state():
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x.reshape(x.shape[0], -1))

    model = Tiny()
    batch = {"image": jnp.zeros((4, 2, 2, 1)), "label": jnp.zeros(4, jnp.int32)}
    params = model.init(jax.random.PRNGKey(0), batch["image"])["params"]
    return TrainState.create(apply_fn=model.apply, params=params,
                             tx=optax.sgd(0.1)), batch


@pytest.mark.parametrize("make, name", [
    (make_train_step, "train_step"), (make_eval_step, "eval_step"),
    (make_predict_step, "predict_step")])
@pytest.mark.parametrize("parallel", [False, True], ids=["single", "ddp2"])
def test_jitted_steps_carry_their_own_names(make, name, parallel,
                                            two_devices):
    assert name in STEP_NAMES and "lm_train_step" in STEP_NAMES
    state, batch = _tiny_classifier_state()
    lowered = make(two_devices if parallel else SingleDevice()).lower(
        state, batch)
    assert f"module @jit_{name}" in lowered.as_text()
    if name == "train_step":
        found = {device_component(stack) for op, stack, _ in
                 _op_stacks(lowered) if device_component(stack)[0]}
        assert {("loss", "forward"), ("loss", "backward"),
                ("update", "update")} <= found
        assert (("grad_sync", "update") in found) == parallel


# ---------------------------------------------------------------------------
# the map, on name stacks recorded from the lowered step (jax 0.9.0)
# ---------------------------------------------------------------------------

_BWD = "jit(lm_train_step)/transpose(jvp(TransformerLM))/"
_FWD = "jit(lm_train_step)/jvp(TransformerLM)/"
_REMAT = _BWD + "jvp(TransformerLM)/checkpoint/rematted_computation/"

STACKS = [
    ("jit(lm_train_step)/jvp(TransformerLM)/embed/jit(_take)/gather",
     "embed", "forward"),
    (_BWD + "embed/jit(_take)/scatter-add", "embed", "backward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/block_0/attn/q/dot_general",
     "attn_proj", "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/block_0/attn/reshape",
     "attn_other", "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/block_0/attn/flash_fwd/"
     "pallas_call", "flash", "forward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/rematted_computation/block_3/"
     "attn/flash_fwd/pallas_call", "flash", "recompute"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_3/attn/flash_bwd_dq/"
     "pallas_call", "flash", "backward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_3/attn/flash_bwd_dkv/"
     "pallas_call", "flash", "backward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/rematted_computation/block_1/"
     "mlp/wg/dot_general", "mlp", "recompute"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_1/mlp/wo/dot_general",
     "mlp", "backward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/rematted_computation/block_1/"
     "ln_mlp/rsqrt", "norm", "recompute"),
    ("jit(lm_train_step)/jvp(TransformerLM)/ln_f/mul", "norm", "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/head/bsd,vd->bsv/dot_general",
     "head", "forward"),
    (_BWD + "head/bsd,vd->bsv/dot_general", "head", "backward"),
    ("jit(lm_train_step)/jvp(loss)/reduce_max", "loss", "forward"),
    ("jit(lm_train_step)/transpose(jvp(loss))/mul", "loss", "backward"),
    ("jit(lm_train_step)/jvp(loss)/while/body/closed_call/td,vd->tv/"
     "dot_general", "loss", "forward"),
    ("jit(lm_train_step)/shard_map/grad_sync/psum", "grad_sync", "update"),
    ("jit(lm_train_step)/update/sqrt", "update", "update"),
    ("jit(lm_train_step)/update/jit(_where)/select_n", "update", "update"),
    ("jit(lm_train_step)/guard/select_n", "guard", "update"),
    ("jit(decode)/block_0/attn/paged_attn/pallas_call", "paged_attn",
     "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/block_1/moe/router/dot_general",
     "moe_router", "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/block_1/moe/ebsd,edf->ebsf/"
     "dot_general", "moe", "forward"),
    # the hybrid blocks (PR 29): flax's method scopes are passed over
    (_FWD + "block_0/block_0._hybrid/gdn/gdn/while/body/dot_general", "gdn",
     "forward"),
    (_REMAT + "block_0/block_0._hybrid/gdn/in_qkvz/dot_general", "gdn_proj",
     "recompute"),
    (_BWD + "block_2/block_2._hybrid/gdn/conv/mul", "gdn_conv", "backward"),
    (_FWD + "block_2/block_2._hybrid/gdn/norm/mul", "gdn_other", "forward"),
    (_FWD + "block_3/block_3._hybrid/attn/attn._grouped_attend/q/"
     "dot_general", "attn_proj", "forward"),
    (_FWD + "block_3/block_3._hybrid/attn/attn._grouped_attend/gate/mul",
     "attn_gate", "forward"),
    (_BWD + "block_3/block_3._hybrid/attn/attn._grouped_attend/flash_bwd_dq/"
     "pallas_call", "flash", "backward"),
    (_BWD + "block_1/block_1._hybrid/moe/experts/moe_tgmm/pallas_call",
     "moe_gmm", "backward"),
    (_REMAT + "block_1/block_1._hybrid/moe/moe_dispatch/gather",
     "moe_dispatch", "recompute"),
    (_FWD + "block_1/block_1._hybrid/moe/shared/wi/dot_general",
     "moe_shared", "forward"),
    (_FWD + "block_1/block_1._hybrid/moe/top_k", "moe", "forward"),
    # the choice of the experts' buffer (PR 32): a branch's ops keep their
    # scopes, and a backward branch's own forward reads as recomputation
    (_FWD + "block_1/block_1._hybrid/moe/cond/branch_0_fun/experts/moe_gmm/"
     "pallas_call", "moe_gmm", "forward"),
    (_FWD + "block_1/block_1._hybrid/moe/cond/branch_1_fun/moe_dispatch/"
     "jit(_take)/gather", "moe_dispatch", "forward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_1/block_1._hybrid/moe/cond/"
     "branch_0_fun/rematted_computation/jvp(experts)/jit(silu)/mul",
     "moe_experts", "recompute"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_1/block_1._hybrid/moe/cond/"
     "branch_0_fun/transpose(rematted_computation)/jvp(moe_dispatch)/"
     "jit(_take)/gather", "moe_dispatch", "backward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_1/block_1._hybrid/moe/cond/"
     "branch_1_fun/transpose(rematted_computation)/jvp(experts)/moe_tgmm/"
     "pallas_call", "moe_gmm", "backward"),
    (_BWD + "jvp(TransformerLM)/checkpoint/block_1/block_1._hybrid/moe/cond",
     "moe", "backward"),
    # Kimi Delta Attention and latent attention (PR 33)
    (_FWD + "block_0/block_0._hybrid/kda/kda/while/body/dot_general", "kda",
     "forward"),
    (_BWD + "block_0/block_0._hybrid/kda/kda/kda_chunk_bwd/pallas_call",
     "kda", "backward"),
    (_REMAT + "block_0/block_0._hybrid/kda/in_q/dot_general", "kda_proj",
     "recompute"),
    (_FWD + "block_1/block_1._hybrid/kda/f_b/dot_general", "kda_proj",
     "forward"),
    (_BWD + "block_1/block_1._hybrid/kda/g_a/dot_general", "kda_proj",
     "backward"),
    (_BWD + "block_2/block_2._hybrid/kda/conv/mul", "kda_conv", "backward"),
    (_FWD + "block_2/block_2._hybrid/kda/norm/mul", "kda_other", "forward"),
    (_FWD + "block_2/block_2._hybrid/kda/jit(softplus)/log1p", "kda_other",
     "forward"),
    (_FWD + "block_3/block_3._hybrid/attn/kv_a/dot_general", "attn_proj",
     "forward"),
    (_BWD + "block_3/block_3._hybrid/attn/kv_b/dot_general", "attn_proj",
     "backward"),
    (_FWD + "block_3/block_3._hybrid/attn/kv_norm/mul", "norm", "forward"),
    (_FWD + "block_3/block_3._hybrid/attn/flash_fwd/pallas_call", "flash",
     "forward"),
    # the step's own scalar bookkeeping and the rope table carry no scope
    ("jit(lm_train_step)/div", None, "forward"),
    ("jit(lm_train_step)/jvp(TransformerLM)/cos", None, "forward"),
]


@pytest.mark.parametrize("stack, component, phase", STACKS,
                         ids=[f"{c}-{p}-{i}" for i, (_, c, p)
                              in enumerate(STACKS)])
def test_device_component_maps_recorded_name_stacks(stack, component, phase):
    assert device_component(stack) == (component, phase)
    assert phase in ("forward", "recompute", "backward", "update")
    assert component is None or component in (
        set(DEVICE_SCOPES) | set(MODULE_SCOPES.values())
        | set(KERNEL_NAMES.values()) | {"attn_proj"}
        | set(_GDN_MODULES.values()) | set(_MOE_MODULES.values())
        | set(_KDA_MODULES.values()) | {"kda_other"}
        | set(_ATTN_OTHER.values()))


def test_module_scopes_are_the_models_own_modules():
    """MODULE_SCOPES against the module tree flax builds (every module of
    the LM holds a parameter, so the parameter tree shows each by name): a
    renamed or a new module fails here by name, where its ops would go
    unattributed in silence."""
    model = TransformerLM(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                          d_ff=32, max_seq=16, n_experts=2, moe_every=2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    blocks = [v for k, v in params.items() if k.startswith("block_")]
    modules = {name for block in blocks for name in block} | {
        k for k in params if not k.startswith("block_")}
    # the embedding is a parameter of the LM itself, under a scope
    assert len(blocks) == 2 and "embed" in DEVICE_SCOPES
    # (the norms on the sublayers' outputs are a ``post_norms`` block's:
    # ``test_windowed_module_scopes_are_the_models_own_modules``)
    assert modules - {"embed"} == set(MODULE_SCOPES) - _POST_NORMS
    assert {name for block in blocks
            for name in block["attn"]} == set(_ATTN_PROJECTIONS)


_POST_NORMS = {"ln_attn_out", "ln_mlp_out"}


def _hybrid_lm(**over):
    kw = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_seq=128, layer_kinds=("linear", "full"), n_kv_heads=1,
              attn_head_dim=16, rope_dims=4, qk_norm=True, attn_gate=True,
              norm_zero_centered=True, gdn_key_heads=1, gdn_value_heads=2,
              gdn_key_dim=8, gdn_value_dim=8, n_experts=2, moe_every=1,
              moe_dispatch="held", moe_router_width=8, moe_first_expert=2,
              moe_top_k=2, moe_d_ff=8, moe_shared_d_ff=8,
              tie_embeddings=False)
    return TransformerLM(**dict(kw, **over))


def test_hybrid_module_scopes_are_the_models_own_modules():
    """The same audit for the hybrid blocks: a Gated DeltaNet layer's and
    the held experts' sub-modules are the keys of their maps, and the gated
    attention adds the two head norms."""
    params = jax.eval_shape(_hybrid_lm().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    linear, full = params["block_0"], params["block_1"]
    assert set(linear) == {"ln_attn", "gdn", "ln_mlp", "moe"}
    assert set(full) == {"ln_attn", "attn", "ln_mlp", "moe"}
    assert {n for n in linear["gdn"] if n not in ("A_log", "dt_bias")} \
        == set(_GDN_MODULES)
    assert set(linear["moe"]) == set(_MOE_MODULES)
    assert set(full["attn"]) == set(_ATTN_PROJECTIONS) | (
        # kv_norm: latent attention's; gate_proj: a gate of its own
        set(_ATTN_OTHER) - {"gate", "kv_norm", "gate_proj"})
    assert {"head", "embed", "ln_f"} == {k for k in params
                                         if not k.startswith("block_")}
    assert "head" in DEVICE_SCOPES and "gate" in DEVICE_SCOPES


def _passes_by_component(lowered):
    """``{component: passes seen}`` of a lowered step, asserting on the way
    that every ``dot_general`` and every op of a kernel lies under a
    component, a kernel's under its own."""
    seen = {}
    for op, stack, _ in _op_stacks(lowered):
        if stack.startswith("closed_call:"):
            # jax lowers a delta rule's forward scan through a private
            # function whose call site carries this in place of a name
            # stack; XLA's inliner gives its ops the caller's op_name (the
            # chip's trace attributes them: PERF.md section 5)
            continue
        component, phase = device_component(stack)
        kernel = [k for k in stack.split("/") if k in KERNEL_NAMES]
        if op == "stablehlo.dot_general" or kernel:
            assert component is not None, (op, stack)
        if kernel:
            assert component == KERNEL_NAMES[kernel[0]], (op, stack)
        if component:
            seen.setdefault(component, set()).add(phase)
    return seen


def _kimi_lm(**over):
    """A KDA layer with a dense FFN, a latent-attention layer with held
    experts (sigmoid router, ungated shared expert)."""
    kw = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_seq=128, layer_kinds=("kda", "mla"), kda_heads=2,
              kda_head_dim=8, kda_gate_rank=4, mla_nope_dim=8,
              mla_rope_dim=4, mla_v_dim=8, mla_kv_rank=12, norm_eps=1e-5,
              n_experts=2, moe_every=1, first_dense_layers=1,
              moe_dispatch="held", moe_router_width=8, moe_first_expert=2,
              moe_top_k=2, moe_d_ff=8, moe_shared_d_ff=8,
              moe_router_act="sigmoid", moe_routed_scale=2.446,
              moe_shared_gate=False, tie_embeddings=False)
    return TransformerLM(**dict(kw, **over))


def test_kimi_module_scopes_are_the_models_own_modules():
    """The audit for the two mixers of PR 33: a Kimi Delta Attention
    layer's sub-modules are the keys of its map, latent attention's are its
    projections and the latent's norm, the leading dense layer is an
    ``mlp``, and the ungated shared expert has no ``gate``."""
    params = jax.eval_shape(_kimi_lm().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    kda, mla = params["block_0"], params["block_1"]
    assert set(kda) == {"ln_attn", "kda", "ln_mlp", "mlp"}
    assert set(mla) == {"ln_attn", "attn", "ln_mlp", "moe"}
    assert {n for n in kda["kda"] if n not in ("A_log", "dt_bias")} \
        == set(_KDA_MODULES)
    assert set(mla["attn"]) == set(_MLA_PROJECTIONS) | {"kv_norm"}
    assert _ATTN_OTHER["kv_norm"] == "norm"
    assert set(mla["moe"]) == set(_MOE_MODULES)
    assert set(mla["moe"]["shared"]) == {"wi", "wg", "wo"}
    assert "kda" in DEVICE_SCOPES


@pytest.mark.parametrize("dim", [8, 128], ids=["jnp", "kda_kernels"])
def test_lowered_kimi_step_leaves_no_matmul_or_kernel_unscoped(dim):
    """The same reading of the lowered train step for the Kimi blocks: every
    ``dot_general`` and every op of a kernel (at head sizes of 128 the
    channel-wise rule's own) lies under a component, and the new components
    show forward, recompute and backward (rung 0 on the CPU)."""
    model = _kimi_lm(attn_impl="flash", remat=True, dtype=jnp.bfloat16,
                     kda_head_dim=dim)
    tokens = jnp.zeros((2, 72), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    lowered = make_lm_train_step(SingleDevice()).lower(state,
                                                       {"tokens": tokens})
    seen = _passes_by_component(lowered)
    every = {"forward", "recompute", "backward"}
    for component in ("kda", "kda_proj", "kda_conv", "kda_other",
                      "attn_proj", "flash", "mlp", "moe_gmm", "moe_router",
                      "moe_shared"):
        assert seen[component] >= every, (component, seen.get(component))


@pytest.mark.parametrize("gdn_dim, router_width", [
    (8, 8), (128, 8), (8, 32)], ids=["jnp", "gdn_kernels", "two_buffers"])
def test_lowered_hybrid_step_leaves_no_matmul_or_kernel_unscoped(
        gdn_dim, router_width):
    """In the lowered train step of a hybrid model every ``dot_general``
    and every op of a grouped-matmul kernel, and at head sizes of 128 of
    the delta rule's kernels, lies under a component, and
    each new component shows the passes it should (rung 0 on the CPU:
    forward, recompute and backward).  At a router of 32 the experts'
    first buffer is smaller than the full one (4 tiles for 5), so the
    layer's kernels and gathers sit in the branches of its choice."""
    model = _hybrid_lm(attn_impl="flash", remat=True, dtype=jnp.bfloat16,
                       gdn_key_dim=gdn_dim, gdn_value_dim=gdn_dim,
                       moe_router_width=router_width)
    tokens = jnp.zeros((2, 72), jnp.int32)      # two chunks of the rule
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    lowered = make_lm_train_step(SingleDevice()).lower(state,
                                                       {"tokens": tokens})
    seen = _passes_by_component(lowered)
    assert any("/moe/cond/branch_1_fun/" in stack for _, stack, _ in
               _op_stacks(lowered)) == (router_width == 32)
    every = {"forward", "recompute", "backward"}
    for component in ("gdn", "gdn_proj", "gdn_conv", "moe_gmm",
                      "moe_dispatch", "moe_router", "moe_shared",
                      "attn_gate", "attn_proj", "flash"):
        assert seen[component] >= every, (component, seen.get(component))


# ---------------------------------------------------------------------------
# kernel names, from the jaxpr's pallas_call equations
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr, out=None):
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(item, jex_core.ClosedJaxpr):
                    _pallas_names(item.jaxpr, out)
                elif isinstance(item, jex_core.Jaxpr):
                    _pallas_names(item, out)
    return out


@pytest.mark.parametrize("fused_rope", [False, True], ids=["plain", "rope"])
def test_flash_pallas_calls_carry_their_names(fused_rope):
    from dtdl_tpu.ops.attention import flash_attention
    from dtdl_tpu.ops.rope import rope_frequencies

    q = jnp.zeros((1, 2, 16, 8), jnp.float32)
    rope = rope_frequencies(8, 16) if fused_rope else None

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, rope=rope).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert _pallas_names(jaxpr.jaxpr) == [
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]


def test_paged_pallas_call_carries_its_name():
    from dtdl_tpu.ops.paged_attention import paged_attention

    b, h, page, d, n_ptab = 2, 2, 8, 16, 2
    pool = jnp.zeros((b * n_ptab + 1, h, page, d), jnp.float32)
    table = 1 + jnp.arange(b * n_ptab, dtype=jnp.int32).reshape(b, n_ptab)
    jaxpr = jax.make_jaxpr(
        lambda q: paged_attention(q, pool, pool, table,
                                  jnp.asarray([3, 9], jnp.int32),
                                  jnp.ones(b, jnp.int32), scale=0.25))(
        jnp.zeros((b, h, 1, d), jnp.float32))
    assert _pallas_names(jaxpr.jaxpr) == ["paged_attn"]
    assert set(KERNEL_NAMES) == {"flash_fwd", "flash_bwd_dq",
                                 "flash_bwd_dkv", "flash_swa_fwd",
                                 "flash_swa_bwd_dq", "flash_swa_bwd_dkv",
                                 "paged_attn",
                                 "moe_gmm", "moe_tgmm",
                                 "gdn_chunk_fwd", "gdn_chunk_bwd",
                                 "kda_chunk_fwd", "kda_chunk_bwd"}


def test_kda_pallas_calls_carry_their_names():
    from dtdl_tpu.ops.gated_delta import kda_rule

    q = jnp.zeros((1, 70, 2, 128), jnp.float32)
    b = jnp.zeros((1, 70, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v, g, b: kda_rule(q, k, v, g, b).sum(),
        argnums=(0, 1, 2, 3, 4)))(q, q, q, q, b)
    assert _pallas_names(jaxpr.jaxpr) == ["kda_chunk_fwd", "kda_chunk_bwd"]
    assert KERNEL_NAMES["kda_chunk_fwd"] == KERNEL_NAMES["kda_chunk_bwd"] \
        == "kda"


def test_gdn_pallas_calls_carry_their_names():
    from dtdl_tpu.ops.gated_delta import gated_delta_rule

    q = jnp.zeros((1, 70, 1, 128), jnp.float32)
    v = jnp.zeros((1, 70, 2, 128), jnp.float32)
    g = jnp.zeros((1, 70, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: gated_delta_rule(*a).sum(), argnums=(0, 1, 2, 3, 4)))(
        q, q, v, g, g)
    assert _pallas_names(jaxpr.jaxpr) == ["gdn_chunk_fwd", "gdn_chunk_bwd"]
    assert KERNEL_NAMES["gdn_chunk_fwd"] == KERNEL_NAMES["gdn_chunk_bwd"] \
        == "gdn"


def _mosaic_calls(model, row_tokens, monkeypatch):
    """``{kernel name: calls}`` of the ``tpu_custom_call`` instructions in
    a model's train step lowered for a TPU (no chip, nothing compiled:
    jax lowers for a platform that is not attached)."""
    import collections
    import re

    import dtdl_tpu.ops.attention as attention
    monkeypatch.setattr(attention, "_use_interpret", lambda: False)
    tokens = jax.ShapeDtypeStruct((2, row_tokens), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, row_tokens - 1), jnp.int32))
    state = jax.eval_shape(
        lambda p: TrainState.create(apply_fn=model.apply, params=p,
                                    tx=optax.adamw(3e-4)), params["params"])
    text = make_lm_train_step(SingleDevice()).trace(
        state, {"tokens": tokens}).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("@tpu_custom_call") == len(
        re.findall(r'kernel_name = "\w+"', text))
    return collections.Counter(re.findall(r'kernel_name = "(\w+)"', text))


def test_the_lowered_tpu_step_holds_gdn_kernels_at_kernel_sized_heads_alone(
        monkeypatch):
    """The Qwen3-Next-shaped step at head sizes of 128 holds the rule's
    Mosaic calls under their names (not once a layer a pass: the calls are
    jitted on their own, and a lowering is shared); at head sizes of 8 the
    same model holds none, nor does the dense one."""
    common = dict(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    taken = _mosaic_calls(_hybrid_lm(gdn_key_dim=128, gdn_value_dim=128,
                                     **common), 73, monkeypatch)
    assert taken["gdn_chunk_fwd"] >= 1 and taken["gdn_chunk_bwd"] >= 1
    assert KERNEL_NAMES.keys() >= taken.keys() >= {
        "flash_fwd", "moe_gmm", "moe_tgmm"}
    small = _mosaic_calls(_hybrid_lm(**common), 73, monkeypatch)
    dense = _mosaic_calls(
        TransformerLM(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                      d_ff=32, max_seq=128, **common), 73, monkeypatch)
    for other in (small, dense):
        assert other["flash_fwd"] >= 1
        assert not [name for name in other if name.startswith("gdn_")]


def test_the_lowered_tpu_step_holds_kda_kernels_at_kernel_sized_heads_alone(
        monkeypatch):
    """The Kimi-shaped step at a head size of 128 holds the channel-wise
    rule's Mosaic calls under their names and the flash kernels of its
    latent-attention layer; at a head size of 8 it holds none of the
    rule's, and neither holds the scalar rule's."""
    common = dict(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    taken = _mosaic_calls(_kimi_lm(kda_head_dim=128, **common), 73,
                          monkeypatch)
    assert taken["kda_chunk_fwd"] >= 1 and taken["kda_chunk_bwd"] >= 1
    assert KERNEL_NAMES.keys() >= taken.keys() >= {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "moe_gmm", "moe_tgmm"}
    small = _mosaic_calls(_kimi_lm(**common), 73, monkeypatch)
    assert small["flash_fwd"] >= 1
    for step in (taken, small):
        assert not [name for name in step if name.startswith("gdn_")]
    assert not [name for name in small if name.startswith("kda_")]


def _windowed_lm(**over):
    """A windowed layer with a dense FFN, a full layer and a windowed one
    with held experts: grouped-query heads with q/k norm and a gate
    projection of their own, four norms a block (the Trinity block)."""
    kw = dict(vocab_size=64, d_model=16, n_layers=3, n_heads=4, n_kv_heads=2,
              attn_head_dim=8, d_ff=32, max_seq=128, norm_eps=1e-5,
              qk_norm=True, attn_gate="own", layer_windows=(24, 0, 24),
              layer_rotates=(True, False, True), post_norms=True,
              embed_scale=4.0, n_experts=2, moe_every=1,
              first_dense_layers=1, moe_dispatch="held", moe_router_width=8,
              moe_first_expert=2, moe_top_k=2, moe_d_ff=8, moe_shared_d_ff=8,
              moe_router_act="sigmoid", moe_routed_scale=2.826,
              moe_shared_gate=False, tie_embeddings=False)
    return TransformerLM(**dict(kw, **over))


def test_windowed_module_scopes_are_the_models_own_modules():
    """The audit for the block of PR 35: the two norms on the sublayers'
    outputs are module scopes, the gate's projection and the head norms are
    named under ``attn``, and nothing else is new."""
    params = jax.eval_shape(_windowed_lm().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    dense, full = params["block_0"], params["block_1"]
    assert set(dense) == {"ln_attn", "attn", "ln_attn_out", "ln_mlp", "mlp",
                          "ln_mlp_out"}
    assert set(full) == set(dense) - {"mlp"} | {"moe"}
    assert (set(dense) | set(full)) - {"attn", "mlp", "moe"} \
        == {k for k, v in MODULE_SCOPES.items() if v == "norm"} - {"ln_f"}
    assert _POST_NORMS <= set(MODULE_SCOPES)
    assert set(full["attn"]) == set(_ATTN_PROJECTIONS) | {
        "gate_proj", "q_norm", "k_norm"}
    assert set(full["attn"]) - set(_ATTN_PROJECTIONS) <= set(_ATTN_OTHER)
    assert _ATTN_OTHER["gate_proj"] == "attn_proj"
    assert device_component(_FWD + "block_0/ln_attn_out/mul") == (
        "norm", "forward")
    assert device_component(
        _REMAT + "block_2/attn/attn._grouped_attend/gate_proj/dot_general") \
        == ("attn_proj", "recompute")
    assert device_component(
        _BWD + "jvp(TransformerLM)/block_2/attn/attn._grouped_attend/"
        "flash_swa_bwd_dkv/mul") == ("flash_swa", "backward")


def test_lowered_windowed_step_leaves_no_matmul_or_kernel_unscoped():
    """The lowered train step of the Trinity block: every ``dot_general``
    and every op of a kernel lies under a component, the windowed layers'
    kernels under ``flash_swa`` and the full layer's under ``flash``, and
    each shows forward, recompute and backward (rung 0 on the CPU)."""
    model = _windowed_lm(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    tokens = jnp.zeros((2, 72), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    lowered = make_lm_train_step(SingleDevice()).lower(state,
                                                       {"tokens": tokens})
    seen = _passes_by_component(lowered)
    every = {"forward", "recompute", "backward"}
    for component in ("flash_swa", "flash", "attn_proj", "attn_gate", "norm",
                      "mlp", "moe_gmm", "moe_router", "moe_shared"):
        assert seen[component] >= every, (component, seen.get(component))
    assert seen["embed"] >= {"forward", "backward"}


def test_every_op_of_the_rehearsed_trinity_step_has_a_component():
    """The step ``benchmarks/run.py --rehearse`` drives for the Trinity cell
    (the family's own keywords at the rehearsal's sizes): every
    ``dot_general`` and every op of a kernel lies under a component, and so
    does every other op that lies inside a block (the norms on the
    sublayers' outputs, the gate, the rotation of the windowed layers)."""
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "benchmarks")
    for path in (bench, repo):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run as harness
    from runners import train
    manifest = harness.load_json(os.path.join(repo, "BENCHMARK.json"))
    cell, cfg = harness.resolve(manifest, "trinitymini-train-share16", True)
    plan = train.make_plan(cell, cfg)
    state = jax.eval_shape(plan.build, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["batch_per_chip"], cell["row_tokens"]), jnp.int32)}
    lowered = make_lm_train_step(SingleDevice()).lower(state, batch)
    seen = _passes_by_component(lowered)
    every = {"forward", "recompute", "backward"}
    for component in ("flash_swa", "flash", "attn_proj", "attn_gate", "norm",
                      "mlp", "moe_gmm", "moe_dispatch", "moe_router",
                      "moe_shared", "moe_experts"):
        assert seen[component] >= every, (component, seen.get(component))
    # (the residual stream's two adds sit in the block's own scope, under no
    # sub-module: XLA fuses them into their neighbours)
    inside = [(op, stack) for op, stack, _ in _op_stacks(lowered)
              if re.search(r"(^|/)block_\d+/(block_\d+\._hybrid/)?\w+/", stack)
              and device_component(stack)[0] is None]
    assert not inside, inside[:5]


def test_windowed_flash_pallas_calls_carry_their_names():
    from dtdl_tpu.ops.attention import flash_attention

    q = jnp.zeros((1, 2, 16, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=5).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = _pallas_names(jaxpr.jaxpr)
    assert names == ["flash_swa_fwd", "flash_swa_bwd_dq",
                     "flash_swa_bwd_dkv"]
    assert {KERNEL_NAMES[n] for n in names} == {"flash_swa"}


def test_the_lowered_tpu_step_holds_windowed_and_full_flash_calls(
        monkeypatch):
    """The Trinity-shaped step lowered for a TPU holds the windowed calls
    under their own names beside the full layer's, and a model without a
    window none of them."""
    common = dict(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    taken = _mosaic_calls(_windowed_lm(**common), 73, monkeypatch)
    assert KERNEL_NAMES.keys() >= taken.keys() >= {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_swa_fwd",
        "flash_swa_bwd_dq", "flash_swa_bwd_dkv", "moe_gmm", "moe_tgmm"}
    plain = _mosaic_calls(_windowed_lm(layer_windows=(), **common), 73,
                          monkeypatch)
    assert plain["flash_fwd"] >= 1
    assert not [name for name in plain if name.startswith("flash_swa")]


# ---------------------------------------------------------------------------
# the compile account
# ---------------------------------------------------------------------------

def _rows_of(rows, fun):
    return [(r.event.rsplit("/", 1)[-1], r.fun_name) for r in rows
            if r.fun_name in (fun, f"jit({fun})")]


def test_compile_account_rows_totals_and_single_registration():
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()
    # registered once: one event from jax arrives as one row
    start = len(compile_cache.compile_account())
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5, fun_name="probe")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs("/some/other/event", 0.5)
    probe = compile_cache.compile_account()[start:]
    assert [(r.fun_name, r.value) for r in probe] == [("probe", 0.5),
                                                      (None, 1)]

    def accounted_for(x):
        return (x * 3.0).sum()

    fn = jax.jit(accounted_for)
    x = jnp.arange(8.0)
    start = len(compile_cache.compile_account())
    fn(x).block_until_ready()
    rows = compile_cache.compile_account()[start:]
    assert _rows_of(rows, "accounted_for") == [
        ("jaxpr_trace_duration", "accounted_for"),
        ("jaxpr_to_mlir_module_duration", "jit(accounted_for)"),
        ("backend_compile_duration", "jit(accounted_for)")]
    assert all(r.value >= 0 and r.event in compile_cache.ACCOUNT_EVENTS
               for r in rows)
    ats = [r.at for r in rows]
    assert ats == sorted(ats)

    mid = len(compile_cache.compile_account())
    fn(x).block_until_ready()                   # steady state: no event
    assert len(compile_cache.compile_account()) == mid

    whole = compile_cache.compile_totals()
    # (and the newest checkpoint plan, experts' buffer and delta-rule paths, where a step of
    # this process made one: tests/test_remat_plan.py, test_qwen3_next.py)
    assert ({k for k in whole
             if not k.startswith(("remat_", "moe_", "gdn_", "kda_",
                                  "swa_"))}
            == set(compile_cache.ACCOUNT_EVENTS.values()))
    assert whole["compile_trace_s"] > 0 and whole["compile_backend_s"] > 0
    summary = Observer().summary()
    assert {k: summary[k] for k in whole} == whole


def test_compile_totals_count_a_nested_trace_and_a_retrieval_once(
        monkeypatch):
    Row = compile_cache.CompileRow
    trace = "/jax/core/compile/jaxpr_trace_duration"
    rows = [
        Row(trace, "inner", 10.5, 0.25),            # [10.25, 10.5] inside
        Row(trace, "outer", 11.0, 1.0),             # [10.0, 11.0]
        Row(trace, "later", 13.0, 0.5),             # [12.5, 13.0]
        Row("/jax/core/compile/backend_compile_duration", "jit(outer)",
            14.0, 2.0),
        Row("/jax/compilation_cache/cache_retrieval_time_sec", None, 13.5,
            1.5),
        Row("/jax/compilation_cache/cache_hits", None, 13.5, 1),
        Row("/jax/compilation_cache/cache_misses", None, 20.0, 1),
    ]
    assert compile_cache.covered_s(rows[:3]) == pytest.approx(1.5)
    assert compile_cache.covered_s(rows[3:5]) == pytest.approx(2.0)
    assert compile_cache.covered_s([]) == 0.0
    monkeypatch.setattr(compile_cache, "_ROWS", [])
    monkeypatch.setattr(compile_cache, "_PLANS", [])
    monkeypatch.setattr(compile_cache, "_EXPERT_BUFFERS", [])
    monkeypatch.setattr(compile_cache, "_GDN_PATHS", [])
    monkeypatch.setattr(compile_cache, "_KDA_PATHS", [])
    monkeypatch.setattr(compile_cache, "_WINDOW_CALLS", [])
    assert compile_cache.compile_totals() == {}
    monkeypatch.setattr(compile_cache, "_ROWS", rows)
    assert compile_cache.compile_totals() == {
        "compile_trace_s": pytest.approx(1.5), "compile_lower_s": 0.0,
        "compile_backend_s": pytest.approx(2.0),
        "compile_cache_retrieval_s": pytest.approx(1.5),
        "compile_cache_hits": 1, "compile_cache_misses": 1}


def test_tracer_has_no_dead_counter_or_profile_parser():
    from dtdl_tpu.obs import trace
    # the Chrome-JSON profile parser at a hard-coded pid/tid is gone
    assert not [n for n in dir(trace)
                if n.lower().startswith("xla") or n == "aggregate"]
    assert not hasattr(trace.Tracer, "counter")
    assert not hasattr(trace.NullTracer, "counter")
