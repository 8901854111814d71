"""Analyzer correctness (ISSUE 15): the seeded-violation corpus and the
jaxpr/HLO program auditors.

Two acceptance oracles:

1. **corpus** — known-bad mini modules where every planted violation
   (sentinel ``PLANT:<rule-id>`` comments) must be flagged with the
   EXACT rule id at the exact line, and a known-clean twin of the same
   shapes must produce zero findings (the false-positive bound).
2. **program audits** — a deliberately sync-leaking jitted step is
   flagged at both jaxpr (``jaxpr-callback``) and compiled
   (``hlo-host-transfer``) level; lost donation, closure-captured
   params, and the collective census are each pinned on tiny programs.
"""

import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtdl_tpu.analysis import (arg_leaf_indices, audit_compiled,
                               audit_jaxpr, census_jaxpr, lint_paths)

# ---------------------------------------------------------------------------
# the corpus: rel-path -> source.  `# PLANT:rule-id` marks a line that
# MUST be flagged with exactly that rule; everything else must not be.
# ---------------------------------------------------------------------------

BAD = {
    # hot-path host syncs, one per sibling rule
    "dtdl_tpu/serve/bad_sync.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def decode_loop(arena, metrics):
            loss = jnp.mean(arena)
            host = jax.device_get(arena)            # PLANT:host-sync-get
            arena.block_until_ready()               # PLANT:host-sync-block
            metrics.append(loss.item())             # PLANT:host-sync-item
            metrics.append(float(jnp.mean(arena)))  # PLANT:host-sync-float
            return np.asarray(arena), host          # PLANT:host-sync-asarray
    """,
    # deprecated shard_map spelling + missing donation in a step factory
    "dtdl_tpu/parallel/bad_compat.py": """
        import jax
        from jax.experimental.shard_map import shard_map  # PLANT:compat-shard-map

        def make_train_step(fn):
            step = jax.jit(fn)                      # PLANT:jit-donate
            return step

        @jax.jit                                    # PLANT:jit-donate
        def update_step(state, batch):
            return state

        def make_eval_step(fn):
            return jax.jit(fn)          # eval: donation not expected
    """,
    # wall clock + host RNG inside a traced function
    "dtdl_tpu/train/bad_trace.py": """
        import time
        import numpy as np
        import jax

        def make_step():
            def step(state, batch):
                t0 = time.time()                    # PLANT:trace-host-time
                noise = np.random.rand(4)           # PLANT:trace-host-rng
                return state, (t0, noise)
            return jax.jit(step, donate_argnums=(0,))

        def host_loop():
            t0 = time.time()       # untraced host timing: fine
            return t0
    """,
    # catalog drift: an uncataloged emitter + a stale catalog entry.
    # the package-root marker makes the corpus "the whole package", so
    # the stale direction (full-set evidence) runs — see rules/catalogs
    "dtdl_tpu/__init__.py": """
        # corpus package root
    """,
    "dtdl_tpu/obs/trace.py": """
        SPAN_CATALOG = frozenset({"data", "ghost_span"})  # PLANT:obs-catalog-stale
        EVENT_CATALOG = frozenset({"good_event"})
    """,
    "dtdl_tpu/serve/bad_events.py": """
        def run(obs, state):
            with obs.span("data"):
                pass
            obs.event("good_event")
            obs.event("rogue_event")                # PLANT:obs-event-uncataloged
            obs.event(f"evt_{state}")               # PLANT:obs-event-dynamic
    """,
    # a window counter missing from _WINDOW_COUNTERS + a stale entry
    "dtdl_tpu/serve/bad_metrics.py": """
        class Metrics:
            def __init__(self):
                self.n_steps = 0
                self.peak = 0

            def on_step(self):
                self.n_steps += 1
                self.peak = max(self.peak, 1)

            def summary(self):
                return {
                    "steps": self.n_steps,          # PLANT:metrics-window-counter
                    "peak": self.peak,
                }

            _WINDOW_COUNTERS = frozenset({"ghost"})  # PLANT:metrics-window-stale
    """,
    # suppression machinery misuse (the @-1 offsets anchor a plant to
    # the suppression COMMENT line above the sentinel)
    "dtdl_tpu/serve/bad_suppress.py": """
        import jax

        def harvest(x):
            # audit: ok[host-sync-get]
            y = jax.device_get(x)                   # PLANT:suppress-no-reason@-1
            # audit: ok[host-sync-item] nothing here trips this rule
            s = 1                                   # PLANT:suppress-stale@-1
            # audit: ok[not-a-rule] bogus id
            u = 2                                   # PLANT:suppress-unknown@-1
            return y, s, u
    """,
}

# the clean twin: the same shapes done right — zero findings expected
CLEAN = {
    "dtdl_tpu/serve/good_sync.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def admit(prompt):
            # audit: ok[host-sync-asarray] caller-supplied host list
            return np.asarray(prompt, np.int32)

        def drain(queue):
            # audit: ok[host-sync-get] the sanctioned boundary drain
            return jax.device_get(queue)
    """,
    "dtdl_tpu/utils/good_host.py": """
        import numpy as np

        def shuffle(xs, seed):
            rng = np.random.default_rng(seed)  # not a hot-path module
            return np.asarray(xs)[rng.permutation(len(xs))]
    """,
    "dtdl_tpu/parallel/good_step.py": """
        import jax
        import time

        def make_train_step(fn):
            return jax.jit(fn, donate_argnums=(0,))

        def make_predict_step(fn):
            return jax.jit(fn)     # predict: params reused, no donation

        def wall_clock():
            return time.time()     # host side, never traced
    """,
    "dtdl_tpu/__init__.py": """
        # corpus package root (full-set catalog evidence, as in BAD)
    """,
    "dtdl_tpu/obs/trace.py": """
        SPAN_CATALOG = frozenset({"data"})
        EVENT_CATALOG = frozenset({"good_event"})
    """,
    "dtdl_tpu/serve/good_events.py": """
        def run(obs):
            with obs.span("data"):
                obs.event("good_event")
    """,
    "dtdl_tpu/serve/good_metrics.py": """
        class Metrics:
            def __init__(self):
                self.n_steps = 0
                self.peak = 0

            def on_step(self):
                self.n_steps += 1
                self.peak = max(self.peak, 1)

            def summary(self):
                return {"steps": self.n_steps, "peak": self.peak}

            _WINDOW_COUNTERS = frozenset({"steps"})
    """,
}

_PLANT_RE = re.compile(r"#.*?PLANT:([a-z-]+)(@(-?\d+))?")


def _write(tmp_path, corpus):
    planted = set()
    for rel, src in corpus.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        src = textwrap.dedent(src).strip() + "\n"
        f.write_text(src)
        for i, line in enumerate(src.splitlines(), start=1):
            m = _PLANT_RE.search(line)
            if m:
                planted.add((rel, i + int(m.group(3) or 0), m.group(1)))
    return planted


def test_corpus_every_planted_violation_flagged_by_exact_rule(tmp_path):
    """100% of planted violations flagged with the exact rule id at the
    exact line — and NOTHING else (zero false positives on the bad
    corpus beyond the plants themselves)."""
    planted = _write(tmp_path, BAD)
    got = {(f.path, f.line, f.rule)
           for f in lint_paths([str(tmp_path)], root=str(tmp_path))}
    missed = planted - got
    extra = got - planted
    assert not missed, f"planted but not flagged: {sorted(missed)}"
    assert not extra, f"false positives: {sorted(extra)}"


def test_corpus_clean_twin_zero_findings(tmp_path):
    """The known-clean twin of every bad shape: zero findings, and the
    two justified suppressions in it are consumed (not stale)."""
    _write(tmp_path, CLEAN)
    findings = lint_paths([str(tmp_path)], root=str(tmp_path))
    assert findings == [], [f.render() for f in findings]


def test_lint_only_rules_filter(tmp_path):
    _write(tmp_path, BAD)
    got = {f.rule for f in lint_paths([str(tmp_path)],
                                      root=str(tmp_path),
                                      only_rules=["host-sync"])}
    assert got == {"host-sync-get", "host-sync-block", "host-sync-item",
                   "host-sync-float", "host-sync-asarray"}


# ---------------------------------------------------------------------------
# program audits: the sync-leaking step + donation + consts + census
# ---------------------------------------------------------------------------

def _leaky_step(state, x):
    # the planted leak: a host callback on the hot path
    y = jax.pure_callback(
        lambda a: np.asarray(a) * 2,
        jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    return state + y.sum(), y


def test_sync_leaking_step_flagged_at_both_levels():
    args = (jnp.zeros(()), jnp.ones((8,)))
    ja = audit_jaxpr(_leaky_step, *args, name="leaky")
    assert [f.rule for f in ja.findings] == ["jaxpr-callback"]
    assert ja.census["callbacks"] == 1
    ha = audit_compiled(_leaky_step, *args, name="leaky")
    assert any(f.rule == "hlo-host-transfer" for f in ha.findings)
    assert ha.census["host_transfers"] >= 1


def test_clean_step_no_findings():
    def step(state, x):
        return state + x.sum(), x * 2
    ja = audit_jaxpr(step, jnp.zeros(()), jnp.ones((8,)))
    assert ja.findings == [] and ja.census["callbacks"] == 0


def test_lost_donation_flagged_and_restored_donation_clean():
    def step(state, x):
        return state + x.sum(), x * 2

    args = (jnp.zeros((128,)), jnp.ones((8,)))
    expect = arg_leaf_indices(args, {0})
    assert expect == {0}
    bad = audit_compiled(jax.jit(step), *args, name="undonated",
                         expect_donated=expect)
    assert [f.rule for f in bad.findings] == ["hlo-undonated"]
    good = audit_compiled(jax.jit(step, donate_argnums=(0,)), *args,
                          name="donated", expect_donated=expect)
    assert good.findings == []
    assert good.census["donated_args"] == [0]


def test_donation_detected_on_sharding_annotated_args(devices):
    """An arg that carries an mhlo.sharding attribute BEFORE its
    donation attribute must still read as donated — the sharding value
    is a quoted string containing '}' and must not truncate the
    attr-dict parse (the blind spot every real mesh program would hit)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dtdl_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(shape=(8,), axes=("data",), devices=devices)

    def step(state, x):
        return state + x.sum(), x * 2

    args = (jax.device_put(jnp.zeros((8, 4)),
                           NamedSharding(mesh, P("data"))),
            jnp.ones((8,)))
    rep = audit_compiled(jax.jit(step, donate_argnums=(0,)), *args,
                         name="sharded", expect_donated={0})
    assert rep.findings == []
    assert 0 in set(rep.census["donor_args"]), rep.census
    assert rep.census["donated_args"] == [0]


def test_closure_captured_params_flagged():
    params = jnp.ones((300_000,), jnp.float32)      # 1.2 MB closed over

    def step(x):
        return (params * x).sum()

    a = audit_jaxpr(step, jnp.ones((300_000,)), name="closure")
    assert [f.rule for f in a.findings] == ["jaxpr-const-capture"]
    assert a.census["const_bytes"] >= 1_200_000
    # passed as an argument instead: no capture
    ok = audit_jaxpr(lambda p, x: (p * x).sum(), params,
                     jnp.ones((300_000,)), name="arg")
    assert ok.findings == []


def test_collective_census_jaxpr_and_hlo(devices):
    from jax.sharding import PartitionSpec as P
    from dtdl_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(shape=(8,), axes=("data",), devices=devices)

    def inner(x):
        return jax.lax.psum(x.sum(), "data")

    fn = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=(P("data"),),
                               out_specs=P()))
    x = jnp.ones((8, 4), jnp.float32)
    census = census_jaxpr(jax.make_jaxpr(fn)(x))
    assert census["collectives"]["psum"]["count"] == 1
    ha = audit_compiled(fn, x, name="psum")
    assert ha.census["collectives"]["all-reduce"]["count"] == 1
    # bytes: one f32 scalar allreduce
    assert ha.census["collectives"]["all-reduce"]["bytes"] == 4


def test_bf16_upcast_census():
    def mixed(x):
        y = x.astype(jnp.float32)          # one deliberate upcast
        return y.sum()

    c = census_jaxpr(jax.make_jaxpr(mixed)(
        jnp.ones((4,), jnp.bfloat16)))
    assert c["bf16_to_f32_casts"] == 1
