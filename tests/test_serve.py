"""Serving subsystem: engine/scheduler/sampling correctness pins.

The three ISSUE-2 contracts, on a tiny f32 dense config (tier-1 budget —
one shared engine = three compiled programs for the whole module):

* **token identity** — a continuously-batched mixed-length run produces,
  per request, exactly the tokens one-at-a-time eager ``model.apply``
  greedy decode produces;
* **mid-flight admission** — a queued request enters a freed slot while
  other slots keep decoding;
* **compile counts** — one prefill program per touched prompt bucket,
  one decode program, regardless of traffic mix.
"""

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
import pytest

from dtdl_tpu.models.transformer import (
    CacheOverflowError, cache_max_seq, transformer_lm,
)
from dtdl_tpu.serve import (
    InferenceEngine, PromptTooLongError, Request, SampleParams, Scheduler,
    sample,
)

MAX_SEQ = 48
BUCKETS = (8, 16)


@pytest.fixture(scope="module")
def model():
    return transformer_lm(
        "tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        d_ff=64, max_seq=MAX_SEQ, attn_impl="dense", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return nn.unbox(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))["params"])


@pytest.fixture(scope="module")
def engine(model, params):
    # 2 slots on purpose: admission pressure for the continuous-batching
    # tests, and the smallest decode program
    return InferenceEngine(model, params, n_slots=2, buckets=BUCKETS)


def ref_greedy(model, params, prompt, n_new):
    """One-at-a-time reference: full-forward logits for the first token
    (the non-serving semantics), then scalar-index KV decode — all eager
    ``model.apply``, nothing shared with the engine's compiled path."""
    cache = model.init_cache(1)
    _, m = model.apply({"params": params, "cache": cache},
                       jnp.asarray([prompt], jnp.int32), decode=True,
                       mutable=["cache"])
    logits = model.apply({"params": params},
                         jnp.asarray([prompt], jnp.int32))
    out = [int(jnp.argmax(logits[0, -1]))]
    cache = m["cache"]
    for _ in range(n_new - 1):
        logits, m = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray([[out[-1]]], jnp.int32), decode=True,
            mutable=["cache"])
        cache = m["cache"]
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def test_batched_greedy_token_identical_to_one_at_a_time(model, params,
                                                         engine):
    """THE serving pin: mixed-length prompts, interleaved through 2 slots
    with slot reuse, each request's tokens == its solo greedy decode."""
    gen = np.random.default_rng(1)
    lens = (3, 9, 14, 5, 7)
    n_new = (6, 4, 8, 3, 5)
    prompts = [gen.integers(0, 64, n).tolist() for n in lens]
    reqs = [Request(p, n) for p, n in zip(prompts, n_new)]
    done = Scheduler(engine, harvest_lag=3).run(reqs)
    assert len(done) == len(reqs)
    for req, prompt, n in zip(reqs, prompts, n_new):
        assert req.done
        assert req.tokens == ref_greedy(model, params, prompt, n), \
            f"rid={req.rid} diverged from solo decode"


def test_scheduler_admits_into_freed_slot_mid_flight(engine):
    """r0 occupies a slot for 10 steps; r1 (2 tokens) frees the other
    slot early; r2, queued at submit, must enter that freed slot while
    r0 is still decoding — iteration-level batching, not run-to-
    completion."""
    gen = np.random.default_rng(2)
    r0 = Request(gen.integers(0, 64, 6).tolist(), 10)
    r1 = Request(gen.integers(0, 64, 4).tolist(), 2)
    r2 = Request(gen.integers(0, 64, 5).tolist(), 4)
    sched = Scheduler(engine, harvest_lag=2)
    done = sched.run([r0, r1, r2])
    assert [r.done for r in (r0, r1, r2)] == [True] * 3
    assert r0.admit_step == 0 and r1.admit_step == 0
    # r0 decodes through step 9 (prefill + 9 decode rounds); r2 must have
    # been admitted strictly inside that window, after r1's retirement
    assert 0 < r2.admit_step < 9
    assert len(r0.tokens) == 10 and len(r1.tokens) == 2
    assert len(r2.tokens) == 4
    s = sched.metrics.summary()
    assert s["requests_finished"] == 3
    assert 0 < s["occupancy_mean"] <= 1.0
    assert s["decode_tokens"] == sum(len(r.tokens) for r in (r0, r1, r2)) - 3


def test_exactly_one_compile_per_shape_bucket(engine):
    """Prompt lengths 3/5/8 share the 8-bucket, 9/16 the 16-bucket; after
    arbitrary traffic there is ONE compiled prefill per touched bucket
    and ONE decode program (jit cache size 1 each — the no-per-request-
    recompile receipt)."""
    gen = np.random.default_rng(3)
    for lens in ((3, 5, 8), (9, 16)):
        reqs = [Request(gen.integers(0, 64, n).tolist(), 3) for n in lens]
        Scheduler(engine, harvest_lag=1).run(reqs)
    stats = engine.compile_stats()
    assert set(stats["prefill"]) == {8, 16}
    assert all(n == 1 for n in stats["prefill"].values()), stats
    assert stats["decode"] == 1, stats
    # a second scheduler over the same engine reuses every program
    Scheduler(engine).run([Request(gen.integers(0, 64, 4).tolist(), 2)])
    assert engine.compile_stats() == stats


def test_sampling_masks_and_greedy():
    """sample(): per-slot dynamic greedy / temperature / top-k / top-p."""
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(4, 32)),
                         jnp.float32)
    argmax = jnp.argmax(logits, -1).astype(jnp.int32)
    z = jnp.zeros(4)
    # temperature 0 = raw argmax whatever the other knobs say
    got = sample(logits, key, z, jnp.asarray([0, 3, 1, 7], jnp.int32),
                 jnp.asarray([1.0, 0.5, 0.9, 1.0]))
    assert (got == argmax).all()
    # top_k=1 and tiny top_p both collapse a hot distribution to argmax
    ones = jnp.ones(4)
    got = sample(logits, key, ones, jnp.full(4, 1, jnp.int32), ones)
    assert (got == argmax).all()
    got = sample(logits, key, ones, jnp.zeros(4, jnp.int32),
                 jnp.full(4, 1e-6))
    assert (got == argmax).all()
    # top_k=5 at high temperature: every draw stays inside each row's
    # top-5 set; per-slot mixing (row 0 greedy) stays deterministic
    top5 = jax.lax.top_k(logits, 5)[1]
    temps = jnp.asarray([0.0, 2.0, 2.0, 2.0])
    ks = jnp.asarray([0, 5, 5, 5], jnp.int32)
    for i in range(20):
        got = sample(logits, jax.random.PRNGKey(i), temps, ks, ones)
        assert got[0] == argmax[0]
        for b in range(1, 4):
            assert got[b] in top5[b]


def test_sampled_run_reproducible(engine):
    """Same scheduler seed -> identical sampled outputs (counter-based
    PRNG; sampling configs are runtime values, so this reuses the same
    compiled decode program)."""
    gen = np.random.default_rng(5)
    prompts = [gen.integers(0, 64, n).tolist() for n in (4, 6)]
    sp = SampleParams(temperature=1.0, top_k=8, top_p=0.9)

    def run(seed):
        reqs = [Request(p, 5, sampling=sp) for p in prompts]
        Scheduler(engine, seed=seed, harvest_lag=2).run(reqs)
        return [r.tokens for r in reqs]

    assert run(7) == run(7)


def test_eos_stops_and_trims(model, params, engine):
    """EOS termination under lag harvest: the slot decodes past the stop
    token for up to ``harvest_lag`` steps, but the output is trimmed at
    EOS (inclusive) — identical to the lag=0 sync-exact result."""
    gen = np.random.default_rng(6)
    prompt = gen.integers(0, 64, 5).tolist()
    ref = ref_greedy(model, params, prompt, 8)
    eos = ref[2]   # stop 3 tokens in

    for lag in (0, 3):
        req = Request(prompt, 8, eos_id=eos)
        Scheduler(engine, harvest_lag=lag).run([req])
        assert req.tokens == ref[:3], f"lag={lag}"


def test_budget_clamped_to_cache_capacity(engine):
    """A request asking for more tokens than max_seq leaves room for is
    clamped (prefill token + one per writable position), instead of the
    pre-guard behavior of silently clamping the cache index into the
    last row."""
    gen = np.random.default_rng(7)
    prompt = gen.integers(0, 64, 14).tolist()   # bucket 16, room for 35
    req = Request(prompt, 99)
    Scheduler(engine, harvest_lag=1).run([req])
    assert req.done
    assert len(req.tokens) == MAX_SEQ - len(prompt) + 1


def test_cache_overflow_raises_and_max_seq_exposed(model, params):
    """Eager decode past the rope table raises the named error (scalar
    and per-slot index both), and max_seq is recoverable from any cache
    pytree."""
    cache = model.init_cache(2)
    assert cache_max_seq(cache) == MAX_SEQ
    assert cache_max_seq(model.cache_shapes(2, per_slot_index=True)) \
        == MAX_SEQ
    # scalar index at the brink: prompt fills all but one position, the
    # next two steps are write-at-last-row then overflow
    toks = jnp.zeros((2, MAX_SEQ - 1), jnp.int32)
    _, m = model.apply({"params": params, "cache": cache}, toks,
                       decode=True, mutable=["cache"])
    _, m = model.apply({"params": params, "cache": m["cache"]},
                       jnp.zeros((2, 1), jnp.int32), decode=True,
                       mutable=["cache"])
    with pytest.raises(CacheOverflowError, match="max_seq"):
        model.apply({"params": params, "cache": m["cache"]},
                    jnp.zeros((2, 1), jnp.int32), decode=True,
                    mutable=["cache"])
    # vector index: one slot at the limit poisons the batch -> named error
    arena = model.init_cache(2, per_slot_index=True)
    arena = jax.tree.map(
        lambda a: jnp.asarray([3, MAX_SEQ], jnp.int32)
        if a.ndim == 1 else a, arena)
    with pytest.raises(CacheOverflowError, match="max_seq"):
        model.apply({"params": params, "cache": arena},
                    jnp.zeros((2, 1), jnp.int32), decode=True,
                    mutable=["cache"])


def test_engine_rejects_bad_inputs(engine):
    # submit-time validation: a bad request must be refused BEFORE it can
    # reach admission (where it would strand the other in-flight requests)
    with pytest.raises(ValueError, match="empty"):
        Scheduler(engine).submit(Request([], 1))
    # an oversized prompt is a *data* problem, not a caller bug: it comes
    # back rejected (error set, never queued) instead of crashing a run
    # with other requests in flight — the engine's named error carries
    # the configured bucket list
    sched = Scheduler(engine)
    bad = sched.submit(Request(list(range(BUCKETS[-1] + 1)), 1))
    assert bad.done and bad.error is not None
    assert "bucket" in bad.error and str(BUCKETS) in bad.error
    assert not sched.queue and bad in sched.finished
    assert sched.metrics.summary()["requests_rejected"] == 1
    with pytest.raises(PromptTooLongError, match="bucket"):
        engine.bucket_for(BUCKETS[-1] + 1)
    with pytest.raises(ValueError, match="empty"):
        engine.prefill(engine.init_arena(), engine.init_last_tokens(),
                       0, [])
    with pytest.raises(ValueError, match="max_seq"):
        engine.prefill(engine.init_arena(), engine.init_last_tokens(),
                       0, list(range(MAX_SEQ + 1)))
    with pytest.raises(ValueError, match="slot"):
        engine.prefill(engine.init_arena(), engine.init_last_tokens(),
                       5, [1, 2])
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request([1, 2], 0)
    with pytest.raises(ValueError, match="temperature"):
        SampleParams(temperature=-1.0)


def test_engine_inputs_are_snapshots_not_aliases():
    """What the engine hands a program is the host value AS OF THE CALL.
    On XLA:CPU a 64-byte-aligned numpy buffer may be aliased rather than
    copied, and dispatch is async — so without the snapshot a scheduler
    that zeroes a slot's page-table row right after dispatching its last
    decode changes what that decode reads (the cause of the once-flaky
    greedy token-identity pins)."""
    from dtdl_tpu.serve.engine import _snapshot

    raw = np.zeros(2 * 6 * 4 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64                 # force the aliasable case
    table = raw[off:off + 48].view(np.int32).reshape(2, 6)
    busy = jnp.ones((1500, 1500))
    f = jax.jit(lambda t, x: t + 0 * x.sum().astype(jnp.int32))
    for _ in range(5):
        table[:] = 3
        y = busy @ busy @ busy                    # keep the queue busy
        out = f(_snapshot(table, jnp.int32), y)
        table[:] = 0                              # "retire" after dispatch
        assert np.asarray(out).tolist() == [[3] * 6] * 2
    # device arrays pass through untouched (no host round trip)
    dev = jnp.arange(3)
    assert _snapshot(dev) is dev
