"""Pallas paged-attention decode kernel: the kernel-round-2 contracts.

The kernel (dtdl_tpu/ops/paged_attention.py) replaces the gather path's
whole-pool materialization for decode (S=1) and verify (S=k+1) with a
grid that walks each slot's page table *inside* the kernel, DMA-ing only
live pages pool→VMEM with the int8/fp8 dequant scales folded into the
tile loads.  Contracts pinned here (interpret mode on CPU — bit-exact
the TPU program's arithmetic):

* **op parity** — kernel output matches the gather path's exact op
  order (einsum f32 → ×key_scale → mask at -1e30 → softmax →
  ×value_scale → value einsum) at decode and verify widths, quant off
  and fused-scale on; inactive rows are exactly zero;
* **garbage-page safety** — pool pages beyond a slot's live prefix
  (stale table tails, freed-and-reused pages) can hold NaN without
  touching the output: the grid guard clamps the walk at the slot's
  last live page, it never merely masks garbage *after* loading it;
* **engine token identity** — a ``paged_kernel=True`` engine produces
  per-request exactly the ``paged_kernel=False`` (gather) tokens on
  mixed speculative/non-speculative traffic with mid-flight slot reuse,
  under a RecompileSentinel at policy='raise' (same program count: the
  kernel rides the existing three program families);
* **flag semantics** — 'auto' resolves by backend (off on CPU), bad
  values fail by name, dense engines ignore the flag.
"""

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
import pytest

from dtdl_tpu.models.transformer import transformer_lm
from dtdl_tpu.obs import Observer
from dtdl_tpu.ops.paged_attention import paged_attention, paged_kernel_enabled
from dtdl_tpu.quant import canon_kv_dtype, kv_quantize
from dtdl_tpu.serve import InferenceEngine, NGramDraft, Request, Scheduler

MAX_SEQ = 48
BUCKETS = (8, 16)
PAGE = 8


@pytest.fixture(scope="module")
def model():
    return transformer_lm(
        "tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        d_ff=64, max_seq=MAX_SEQ, attn_impl="dense", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return nn.unbox(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))["params"])


# ---------------------------------------------------------------------------
# op-level parity vs the gather path's exact arithmetic
# ---------------------------------------------------------------------------

def _gather_reference(q, pk, pv, table, pos, active, scale,
                      key_scale=None, value_scale=None):
    """The engine gather path's op order, on the whole pooled table."""
    b, h, s_new, d = q.shape
    n_ptab = table.shape[1]
    page = pk.shape[2]
    k = jnp.take(pk, table, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, n_ptab * page, d)
    v = jnp.take(pv, table, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, n_ptab * page, d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    if key_scale is not None:
        ks = jnp.take(key_scale, table, axis=0).transpose(0, 2, 1, 3) \
            .reshape(b, h, n_ptab * page)
        s = s * ks.astype(jnp.float32)[:, :, None, :]
    cols = jnp.arange(n_ptab * page)[None, None, None, :]
    qpos = pos[:, None, None, None] + jnp.arange(s_new)[None, None, :, None]
    s = jnp.where(cols <= qpos, s * scale, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if value_scale is not None:
        vs = jnp.take(value_scale, table, axis=0).transpose(0, 2, 1, 3) \
            .reshape(b, h, n_ptab * page)
        p = p * vs.astype(jnp.float32)[:, :, None, :]
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.where(active[:, None, None, None] > 0, o.astype(q.dtype), 0.0)


def _pool_case(seed, quant, *, nan_tail=False, b=3, h=2, n_ptab=4,
               page=PAGE, d=16):
    """Random pool/table/pos geometry; slot 2 inactive.  With
    ``nan_tail`` every page beyond each slot's live prefix — including
    the stale table tail — holds NaN."""
    rng = np.random.default_rng(seed)
    n_pages = b * n_ptab + 1
    kf = rng.normal(size=(n_pages, h, page, d)).astype(np.float32)
    vf = rng.normal(size=(n_pages, h, page, d)).astype(np.float32)
    table = 1 + rng.permutation(b * n_ptab).reshape(b, n_ptab).astype(np.int32)
    pos = np.asarray([5, 2 * page + 3, 0], np.int32)[:b]
    active = np.asarray([1, 1, 0], np.int32)[:b]
    if nan_tail:
        live = {0}                      # page 0 is the shared null target
        for i in range(b):
            if active[i]:
                for j in range((int(pos[i]) + 1 + page - 1) // page):
                    live.add(int(table[i, j]))
        dead = [p for p in range(n_pages) if p not in live]
        kf[dead] = np.nan
        vf[dead] = np.nan
    pk, pv = jnp.asarray(kf), jnp.asarray(vf)
    ks = vs = None
    if quant:
        # True = int8 payload + f32 scales; 'fp8' = fp8 payload + bf16
        # scales (the [1, H, page] scale tile holds either dtype)
        kv_dtype = canon_kv_dtype("int8" if quant is True else quant)
        pk, ks = kv_quantize(pk, dtype=kv_dtype)
        pv, vs = kv_quantize(pv, dtype=kv_dtype)
        if nan_tail:
            # poison the dead pages' SCALES too (per-row scales of live
            # pages are untouched, so they still match a clean pool)
            dead_mask = ~np.isin(np.arange(n_pages),
                                 list(live))[:, None, None]
            ks = jnp.where(dead_mask, jnp.nan, ks)
            vs = jnp.where(dead_mask, jnp.nan, vs)
    return pk, pv, ks, vs, jnp.asarray(table), jnp.asarray(pos), \
        jnp.asarray(active)


@pytest.mark.parametrize("s_new", [1, 5])
@pytest.mark.parametrize("quant", [False, True, "fp8"])
def test_kernel_matches_gather_reference(s_new, quant):
    pk, pv, ks, vs, table, pos, active = _pool_case(0, quant)
    b, h, d = table.shape[0], pk.shape[1], pk.shape[3]
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, h, s_new, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    got = paged_attention(q, pk, pv, table, pos, active, scale=scale,
                          key_scale=ks, value_scale=vs)
    want = _gather_reference(q, pk, pv, table, pos, active, scale,
                             key_scale=ks, value_scale=vs)
    # online vs one-shot softmax reassociation only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert np.all(np.asarray(got)[np.asarray(active) == 0] == 0.0)


@pytest.mark.parametrize("quant", [False, True])
def test_garbage_pages_never_loaded(quant):
    """NaN in every non-live page (stale table tails, freed pool pages)
    must not reach the output — the guard clamps the page walk, it does
    not mask-after-load (NaN * 0 would already be NaN)."""
    pk, pv, ks, vs, table, pos, active = _pool_case(2, quant, nan_tail=True)
    b, h, d = table.shape[0], pk.shape[1], pk.shape[3]
    q = jnp.asarray(np.random.default_rng(3).normal(size=(b, h, 1, d)),
                    jnp.float32)
    got = np.asarray(paged_attention(q, pk, pv, table, pos, active,
                                     scale=1.0 / np.sqrt(d),
                                     key_scale=ks, value_scale=vs))
    assert np.all(np.isfinite(got))
    # and it still matches a reference over a garbage-free pool with the
    # same live contents
    pk2, pv2, ks2, vs2, *_ = _pool_case(2, quant, nan_tail=False)
    want = _gather_reference(q, pk2, pv2, table, pos, active,
                             1.0 / np.sqrt(d), key_scale=ks2,
                             value_scale=vs2)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-6)


def test_flag_semantics(model, params):
    assert paged_kernel_enabled(True) is True
    assert paged_kernel_enabled(False) is False
    assert paged_kernel_enabled("auto") == (
        jax.default_backend() == "tpu")
    with pytest.raises(ValueError, match="paged_kernel"):
        paged_kernel_enabled("yes")
    # dense engine: no pages, the flag is inert
    eng = InferenceEngine(model, params, n_slots=2, paged_kernel=True)
    assert eng.paged_kernel is False
    # paged engine: receipt says requested vs enabled
    eng = InferenceEngine(model, params, n_slots=2, page_size=PAGE,
                          buckets=BUCKETS)
    rec = eng.compile_stats()["kernels"]["paged_attention"]
    assert rec["requested"] == "auto"
    assert rec["enabled"] == (jax.default_backend() == "tpu")
    assert rec["page_size"] == PAGE


# ---------------------------------------------------------------------------
# engine-level token identity (interpret mode: the heavy cases)
# ---------------------------------------------------------------------------

def _run_traffic(engine, seed=1, n_reqs=4, spec=True):
    """Mixed spec/non-spec traffic over 2 slots: n_reqs > n_slots forces
    mid-flight slot reuse (retire + admit into freed pages)."""
    gen = np.random.default_rng(seed)
    lens = gen.integers(3, 15, n_reqs)
    news = gen.integers(3, 9, n_reqs)
    reqs = [Request(gen.integers(0, 64, int(n)).tolist(), int(m),
                    speculate=(3 if spec and i % 2 else 0))
            for i, (n, m) in enumerate(zip(lens, news))]
    sched = Scheduler(engine, harvest_lag=2,
                      draft=NGramDraft() if spec else None)
    sched.run(reqs)
    return [r.tokens for r in reqs]


def test_engine_decode_token_identity(model, params):
    """Kernel vs gather engines, plain decode traffic with slot reuse:
    greedy tokens identical per request, zero recompiles either side."""
    toks = {}
    for flag in (False, True):
        obs = Observer(sentinel="raise")
        eng = InferenceEngine(model, params, n_slots=2, buckets=BUCKETS,
                              page_size=PAGE, observer=obs,
                              paged_kernel=flag)
        toks[flag] = _run_traffic(eng, spec=False)
        assert obs.sentinel.summary()["recompile_events"] == 0
    assert toks[True] == toks[False]


@pytest.mark.slow
@pytest.mark.parametrize("kv", [None, "int8", "fp8"])
def test_engine_spec_token_identity(model, params, kv):
    """Kernel vs gather under mixed speculative/non-speculative traffic
    (the verify width S=k+1 path), per KV dtype — the int8/fp8 rows pin
    the in-kernel scale fusion against the gather path's dequant."""
    toks = {}
    for flag in (False, True):
        obs = Observer(sentinel="raise")
        eng = InferenceEngine(model, params, n_slots=2, buckets=BUCKETS,
                              page_size=PAGE, observer=obs, kv_dtype=kv,
                              paged_kernel=flag)
        toks[flag] = _run_traffic(eng, seed=7, n_reqs=6, spec=True)
        assert obs.sentinel.summary()["recompile_events"] == 0
        rec = eng.compile_stats()["kernels"]["paged_attention"]
        assert rec["enabled"] is flag
        assert rec["fused_scales"] == (kv is not None)
    assert toks[True] == toks[False]
