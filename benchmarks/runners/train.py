"""The training runner (``"runner": "train"`` in a cell's file).

Drives the program's LM train step exactly as ``examples/train_lm.py``
does — ``make_lm_train_step(strategy)`` on a ``TrainState`` placed by
``strategy.replicate``, batches through ``strategy.shard_batch`` — with
``choose_strategy("auto")``, so a cell whose file says ``chips: 4`` runs
``shard_map`` DDP with no change here.

From the program it takes the system under test only.  Weights, batches,
clocks, spans, the reference and every number come from ``benchmarks/``;
what differs from one model family to the next comes from the
configuration's family (``lib/modules.py``).
"""

from __future__ import annotations

import contextlib
import math
import os
import shutil
import time

from lib import correct, modules, tokens, weights, xplane

MAX_IN_FLIGHT = 2
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _leaf_path(path) -> str:
    """``block_0/attn/q/kernel`` from a key path; flax's partitioning box
    (an attribute node) is not part of the name."""
    return "/".join(str(k.key) for k in path if hasattr(k, "key"))


class _Spans:
    """Host spans on the host's clock, kept in memory; each is also a
    ``TraceAnnotation`` so a trace carries it on the trace's clock."""

    def __init__(self):
        self.rows = []          # (name, start_s, duration_s)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation("bench:" + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter() - t0))

    def total(self, name) -> float:
        return sum(d for n, _, d in self.rows if n == name)


def make_plan(cell: dict, cfg: dict):
    """What a run builds before it touches a device: the program's model,
    its parameter leaves by path, and ``build(key) -> TrainState`` (weights
    from the seed by the benchmark's generator, ``optax.adamw`` state), to
    be called under ``jit``.  ``tools/topology_compile.py`` lowers the same
    plan for a chip that is not attached."""
    import types

    import jax
    import jax.numpy as jnp
    import optax

    from dtdl_tpu.models.transformer import TransformerLM
    from dtdl_tpu.train.state import TrainState

    if cell["optimizer"]["name"] != "adamw":
        raise ValueError("the train runner knows optax.adamw alone")
    family = modules.family_of(cfg)
    model = TransformerLM(dtype=jnp.bfloat16,
                          **family.model_kwargs(cfg, bool(cell["remat"])))
    tx = optax.adamw(float(cell["optimizer"]["lr"]))
    example = jnp.zeros((1, int(cell["row_tokens"]) - 1), jnp.int32)
    abstract = jax.eval_shape(
        lambda k: model.init(k, example, train=False)["params"],
        jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [_leaf_path(p) for p, _ in flat]
    if len(set(paths)) != len(paths):
        raise ValueError("parameter paths are not unique")
    shapes = {p: tuple(leaf.shape) for p, (_, leaf) in zip(paths, flat)}

    def build(key):
        made = weights.make_params(key, shapes, family.leaf_moments)
        params = jax.tree_util.tree_unflatten(treedef,
                                              [made[p] for p in paths])
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    return types.SimpleNamespace(model=model, tx=tx, shapes=shapes,
                                 paths=paths, build=build, family=family)


class Session:
    """The compiled step of one cell and the one call and feed that warm-up
    and window alike go through.  ``run`` makes one for one seed;
    ``tools/readings.py`` drives a dozen seeds through one."""

    def __init__(self, cell: dict, cfg: dict, opts: dict, wrap_step=None):
        import jax

        from dtdl_tpu.parallel import choose_strategy
        from dtdl_tpu.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

        self.spans = _Spans()
        with self.spans.span("backend"):
            self.devices = devices = jax.devices()
        self.platform = devices[0].platform
        chips = int(cell["chips"])
        if not opts["rehearse"] and (self.platform != "tpu"
                                     or len(devices) != chips):
            raise SystemExit(
                f"cell {cell['name']} needs {chips} TPU chip(s); JAX found "
                f"{len(devices)} x {self.platform} "
                f"({devices[0].device_kind})")
        if len(devices) != chips:
            raise SystemExit(f"rehearsal wanted {chips} devices, "
                             f"got {len(devices)}")

        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.compiles.append(time.perf_counter())
            if name == _COMPILE_EVENT else None)

        self.cell, self.cfg, self.chips = cell, cfg, chips
        self.rows = int(cell["batch_per_chip"]) * chips
        self.row_tokens = int(cell["row_tokens"])
        self.distribution = cell.get("token_distribution", "uniform")
        self.wrap_step = wrap_step
        self.strategy = choose_strategy("auto")
        if self.strategy.num_replicas != chips:
            raise SystemExit(
                f"strategy spans {self.strategy.num_replicas} replicas, "
                f"the cell asks for {chips}")
        with self.spans.span("plan"):
            self.plan = make_plan(cell, cfg)
        self.build = jax.jit(self.plan.build)
        self.driven = None
        self.losses = []

    def _by_path(self, tree) -> dict:
        import jax
        return dict(zip(self.plan.paths, jax.tree_util.tree_leaves(tree)))

    def start(self, seed: int):
        """The seed's state on the device(s); the step is lowered and
        compiled against the first one."""
        from dtdl_tpu.train import make_lm_train_step
        self.seed, self.losses = int(seed), []
        self.key = weights.seed_key(seed)
        with self.spans.span("make_state"):
            state = self.strategy.replicate(self.build(self.key))
        if self.driven is None:
            step = make_lm_train_step(
                self.strategy,
                vocab_chunk_size=int(self.cell.get("vocab_chunk_size", 0)))
            with self.spans.span("lower"):
                lowered = step.lower(state, self.feed(0))
            with self.spans.span("compile"):
                compiled = lowered.compile()
            if self.platform == "tpu" \
                    and "tpu_custom_call" not in compiled.as_text():
                raise SystemExit(
                    "the compiled train step holds no Mosaic kernel "
                    "(tpu_custom_call): the flash path is not timed")
            self.driven = (self.wrap_step(compiled, self) if self.wrap_step
                           else compiled)
        return state

    def feed(self, index: int):
        import jax.numpy as jnp
        with self.spans.span("feed"):
            host = tokens.batch_tokens(
                self.seed, index, self.rows, self.row_tokens,
                self.cfg["vocab_size"], self.distribution)
            return self.strategy.shard_batch({"tokens": jnp.asarray(host)})

    def drive(self, state, index: int):
        """One step: feed, dispatch, and settle the loss of two steps back
        (at most ``MAX_IN_FLIGHT`` steps run ahead of the host)."""
        batch = self.feed(index)
        with self.spans.span("dispatch"):
            state, metrics = self.driven(state, batch)
        losses = self.losses
        losses.append(metrics["loss"])
        if len(losses) > MAX_IN_FLIGHT:
            with self.spans.span("settle"):
                losses[-1 - MAX_IN_FLIGHT] = float(
                    losses[-1 - MAX_IN_FLIGHT])
        return state

    def first_steps(self, state):
        """The first ``correct.STEPS`` steps through ``drive``; returns the state and what the
        program produced of the numbers ``correct`` compares."""
        import jax
        state = self.drive(state, 0)
        # the first gradient as the optimizer got it: mu_1 = (1 - b1) g_1
        grad = correct.leaf_readings(
            self._by_path(_first_moment(state.opt_state)), self.key,
            1.0 / (1.0 - correct.ref.ADAMW["b1"]))
        for i in range(1, correct.STEPS):
            state = self.drive(state, i)
        change = correct.change_readings(self._by_path(state.params),
                                         self.key,
                                         self.plan.family.leaf_moments)
        jax.block_until_ready(state)
        return state, correct.on_host(jax.device_get({
            "loss": self.losses[: correct.STEPS], "grad": grad,
            "change": change}))

    def reference(self, precision: str = "f32", fault=None) -> dict:
        return correct.reference_readings(
            self.cfg, self.plan.shapes, self.seed, self.rows,
            self.row_tokens, self.distribution,
            float(self.cell["optimizer"]["lr"]), precision=precision,
            fault=fault, chips=self.chips)


def _first_moment(opt_state):
    import jax
    import optax
    is_adam = lambda s: isinstance(s, optax.ScaleByAdamState)  # noqa: E731
    adam = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=is_adam)
            if is_adam(s)]
    if len(adam) != 1:
        raise ValueError("expected one Adam state in the optimizer")
    return adam[0].mu


def run(cell: dict, cfg: dict, opts: dict, wrap_step=None) -> dict:
    """One run of one cell; returns the run record the metric readers read.

    ``opts``: ``seed``, ``seconds``, ``trace`` (bool), ``rehearse`` (bool),
    ``t_start`` (``perf_counter`` at process start), ``scratch`` (a
    directory inside the checkout for the trace).  ``wrap_step`` is for the
    fault tests alone: it receives the compiled step (and the session) and
    returns what is driven in its place.
    """
    import jax

    imports_s = time.perf_counter() - opts["t_start"]
    ses = Session(cell, cfg, opts, wrap_step)
    spans, seconds = ses.spans, float(opts["seconds"])

    # -- set-up: state, compile, the first steps, one more to warm the settle
    state = ses.start(opts["seed"])
    with spans.span("warmup"):
        state, program = ses.first_steps(state)
        state = ses.drive(state, correct.STEPS)   # the first loss settles
        jax.block_until_ready(state)
    t_setup = time.perf_counter()
    setup_s = t_setup - opts["t_start"]
    next_index = correct.STEPS + 1

    # -- with --trace 1: a short traced window of its own ------------------
    traced = None
    if opts["trace"]:
        trace_dir = os.path.join(opts["scratch"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.perf_counter()
        limit = min(float(cell.get("trace_seconds", 3.0)), seconds)
        steps = 0
        with spans.span("window"):
            while steps < 2 or time.perf_counter() - t0 < limit:
                state = ses.drive(state, next_index)
                next_index += 1
                steps += 1
            with spans.span("drain"):
                jax.block_until_ready(state)
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        traced = {"dir": trace_dir, "steps": steps, "seconds": t1 - t0}

    # -- the measured window -----------------------------------------------
    compiles_before = len(ses.compiles)
    w0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - w0 < seconds:
        state = ses.drive(state, next_index)
        next_index += 1
        steps += 1
    with spans.span("drain"):
        jax.block_until_ready(state)
    w1 = time.perf_counter()
    compiles_in_window = len(ses.compiles) - compiles_before

    losses = [float(x) for x in ses.losses]
    fullest = max((d.memory_stats() or {} for d in ses.devices),
                  key=lambda m: m.get("peak_bytes_in_use", 0))
    peak = fullest.get("peak_bytes_in_use", 0)
    del state
    ses.driven = None

    # -- the reference, once the window has closed --------------------------
    r0 = time.perf_counter()
    refr = ses.reference()
    reference_s = time.perf_counter() - r0
    nums = correct.numbers(program, refr)
    limits = dict(cell["limits"])
    ok, compared = correct.judge(nums, limits)
    failed = sum(1 for x in losses if not math.isfinite(x))
    ok = ok and failed == 0 and compiles_in_window == 0 and steps > 0
    compared["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    compared["nonfinite_losses"] = {"value": failed, "limit": 0}

    platform, devices = ses.platform, ses.devices
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    trace, breakdown = None, None
    if traced:
        trace, breakdown = _read_trace(traced, platform)
        if not opts.get("keep_trace"):
            shutil.rmtree(traced["dir"], ignore_errors=True)
        if trace["devices"]:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]

    chips = ses.chips
    target_tokens = ses.rows * (ses.row_tokens - 1)
    return {
        "cell": cell, "config": cfg, "shapes": ses.plan.shapes,
        "correct": ok, "compared": compared,
        "notes": dict(
            {k: nums[k] for k in nums if k not in limits},
            setup_spans_s=dict(
                {name: spans.total(name) for name in (
                    "backend", "plan", "make_state", "lower", "compile",
                    "warmup")}, imports=imports_s),
            reference_s=reference_s, window_steps=steps,
            window_s=w1 - w0),
        "attempted": len(losses), "failed": failed,
        "end_to_end": {
            "train_tokens_per_s": steps * target_tokens / (w1 - w0) / chips,
            "setup_s": setup_s,
        },
        "window": {"steps": steps, "seconds": w1 - w0, "start": w0,
                   "target_tokens_per_step": target_tokens,
                   "compiles": compiles_in_window},
        "traced": traced, "trace": trace, "breakdown": breakdown,
        "spans": spans.rows, "memory_stats": fullest,
        "device": device,
    }


def _read_trace(traced: dict, platform: str):
    """The traced window's device events, cut to the ``bench:window`` span,
    with the contract's ``busy_s`` / ``window_s`` and ``breakdown``."""
    data = xplane.load(xplane.find_xplane(traced["dir"]))
    window = [s for s in data["spans"] if s[0] == "bench:window"]
    if platform == "tpu" and not (data["devices"] and window):
        raise SystemExit("the trace holds no TPU op line or no window span")
    if not window:
        return {"devices": {}, "spans": data["spans"]}, None
    t0, t1 = window[-1][1], window[-1][1] + window[-1][2]
    ops = {n: xplane.clip(ev, t0, t1) for n, ev in data["devices"].items()}
    trace = {"devices": ops, "spans": data["spans"], "t0": t0, "t1": t1,
             "steps": traced["steps"], "window_s": (t1 - t0) / 1e9}
    if not ops:
        return trace, None
    trace["busy_s"] = sum(xplane.busy_union_ns(ev)
                          for ev in ops.values()) / len(ops) / 1e9
    by_name = {}
    for ev in ops.values():
        for name, ns in xplane.time_by_name(ev).items():
            name = xplane.label(name)
            by_name[name] = by_name.get(name, 0.0) + ns / len(ops) / 1e9
    first = ops[min(ops)]
    breakdown = {
        "device_ops": [[n, s] for n, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[xplane.covering_span(data["spans"], s + d / 2), d / 1e9]
                      for s, d in xplane.idle_gaps(first, t0, t1)[:10]],
    }
    return trace, breakdown
