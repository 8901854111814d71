"""Recompile sentinel: turn silent retraces into named, arg-diffed events.

A recompile on TPU is a multi-second stall that the async dispatch
pipeline hides until the drain — the loop just gets mysteriously slow.
The tests already police this by hand (``_cache_size()`` asserts in
tests/test_serve.py, ``engine.compile_stats()``); this
module is that pattern made a reusable runtime guard: wrap any jitted
callable with :meth:`RecompileSentinel.watch` and every call compares
the function's jit-cache size before/after.  Growth past the expected
compile budget produces a :class:`RecompileEvent` naming the function
and **which abstract args changed** (shape/dtype diff against the
signature that compiled last time — the two things a retrace can key
on that a loop author actually controls).

Policies: ``'warn'`` logs through the ``dtdl_tpu`` logger (default —
observability must not change program behavior), ``'raise'`` turns the
event into a :class:`RecompileError` (CI mode: fail the run where the
retrace happens, not 40 minutes later in a profile), ``'silent'`` only
records.  A callable policy receives the event.

The sentinel reads only host-side jit bookkeeping — no device syncs,
no effect on what compiles.  Persistent-compile-cache note: a disk
cache hit (dtdl_tpu/runtime/compile_cache.py) still *traces* the
function, so it still counts here — correctly so, because tracing + cache lookup is
the stall being policed.  Functions without ``_cache_size`` (plain
Python callables, non-jit wrappers) pass through unwrapped.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict
from typing import Any, Callable, Optional

_log = logging.getLogger("dtdl_tpu")

# per-sentinel LRU bound on watched-function states: each state pins its
# jit (executables + closed-over params) via a strong ref, so a process
# that churns through many step fns / engines must not grow unboundedly.
# Evicting a state forgets that fn's compile count (a re-watch restarts
# its budget) — the bound is sized so only genuinely churny workloads hit
# it, the same trade loop.py's _BUNDLED_CACHE makes.
_MAX_WATCH_STATES = 64


class RecompileError(RuntimeError):
    """An unexpected retrace under policy='raise'."""


def abstract_signature(args: tuple, kwargs: dict) -> dict:
    """Flat {path: 'f32[8,64]'} view of a call's abstract leaves.

    jax's own cache key also includes static argnums and tree
    structure; shapes/dtypes are the part a training/serving loop
    author can act on, so that is what the diff speaks in.
    """
    import jax
    import numpy as np

    def leaf_str(x) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{np.dtype(dtype).name}[{','.join(map(str, shape))}]"
        if isinstance(x, (bool, int, float, str)):
            return f"{type(x).__name__}:{x!r}"        # static-ish leaf
        return type(x).__name__

    out = {}
    for tree, root in ((args, "args"), (kwargs, "kwargs")):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in flat:
            out[root + jax.tree_util.keystr(path)] = leaf_str(leaf)
    return out


def diff_signatures(old: Optional[dict], new: dict) -> dict:
    """{path: 'old -> new'} for every leaf that changed (or appeared)."""
    if not old:
        return {}
    out = {}
    for k, v in new.items():
        if old.get(k) != v:
            out[k] = f"{old.get(k, '<absent>')} -> {v}"
    for k in old:
        if k not in new:
            out[k] = f"{old[k]} -> <absent>"
    return out


@dataclasses.dataclass
class RecompileEvent:
    """One unexpected retrace."""
    name: str
    n_compiles: int            # total traces of this fn since watch()
    cache_size: int            # jit cache entries after this call
    signature: dict            # the signature that (re)traced
    diff: dict                 # vs the signature that compiled before

    def message(self) -> str:
        changed = ("; ".join(f"{k}: {v}" for k, v in self.diff.items())
                   or "signature change outside shapes/dtypes "
                      "(static arg / tree structure)")
        return (f"unexpected retrace #{self.n_compiles} of "
                f"{self.name!r} (jit cache now {self.cache_size} "
                f"entries) — changed args: {changed}")


class _WatchState:
    """Per-underlying-function sentinel state, owned by the sentinel and
    SHARED across wrappers: re-watching the same jit (every train_epoch
    call wraps anew) must not grant a fresh compile budget, or a genuine
    epoch-2 retrace would be silently absorbed as 'the first compile'."""

    __slots__ = ("fn", "compiles", "last_sig")

    def __init__(self, fn: Callable):
        self.fn = fn                 # strong ref: pins id(fn) while kept
        self.compiles = 0
        self.last_sig: Optional[dict] = None


class _Watched:
    """Callable wrapper over a jit + its shared sentinel state.

    Delegates every attribute to the wrapped jit (``.lower``,
    ``._cache_size`` — so ``InferenceEngine.compile_stats`` and
    ``dump_graph`` keep working on a watched function).
    """

    def __init__(self, fn: Callable, name: str, expected: int,
                 sentinel: "RecompileSentinel", state: _WatchState):
        self._fn = fn
        self._name = name
        self._expected = expected
        self._sentinel = sentinel
        self._state = state

    def __call__(self, *args, **kwargs):
        fn = self._fn
        st = self._state
        before = fn._cache_size()
        out = fn(*args, **kwargs)
        after = fn._cache_size()
        if after > before:
            st.compiles += 1
            sig = abstract_signature(args, kwargs)
            if st.compiles > self._expected:
                self._sentinel._fire(RecompileEvent(
                    name=self._name, n_compiles=st.compiles,
                    cache_size=after, signature=sig,
                    diff=diff_signatures(st.last_sig, sig)))
            st.last_sig = sig
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


class RecompileSentinel:
    """Watches jitted callables for unexpected retraces (see module
    docstring).  One sentinel serves a whole process; events accumulate
    on :attr:`events` whatever the policy."""

    def __init__(self, policy: str | Callable = "warn"):
        if not callable(policy) and policy not in ("warn", "raise",
                                                   "silent"):
            raise ValueError(f"policy must be 'warn', 'raise', 'silent' "
                             f"or callable, got {policy!r}")
        self.policy = policy
        self.events: list[RecompileEvent] = []
        self._states: OrderedDict[int, _WatchState] = OrderedDict()

    def watch(self, fn: Callable, name: str | None = None,
              expected: int = 1) -> Callable:
        """Wrap ``fn``; the first ``expected`` traces are the compile
        budget (1 for a plain jit; 2 for an unroll bundle whose ragged
        tail legitimately recompiles once).  Re-watching the same fn
        (loops re-wrap per epoch/leg) resumes its existing compile
        count rather than re-granting the budget.  Non-jit callables
        (no ``_cache_size``) are returned unwrapped."""
        if hasattr(fn, "_sentinel") and getattr(fn, "_sentinel") is self:
            fn = fn._fn              # re-watching a wrapper: unwrap first
        if not hasattr(fn, "_cache_size"):
            return fn
        state = self._states.get(id(fn))
        if state is None or state.fn is not fn:
            state = self._states[id(fn)] = _WatchState(fn)
        self._states.move_to_end(id(fn))
        while len(self._states) > _MAX_WATCH_STATES:
            self._states.popitem(last=False)
        return _Watched(fn, name or getattr(fn, "__name__", "jit_fn"),
                        expected, self, state)

    def _fire(self, event: RecompileEvent) -> None:
        self.events.append(event)
        if callable(self.policy):
            self.policy(event)
        elif self.policy == "warn":
            _log.warning("%s", event.message())
        elif self.policy == "raise":
            raise RecompileError(event.message())

    def summary(self) -> dict:
        return {"recompile_events": len(self.events),
                "recompiled_fns": sorted({e.name for e in self.events})}


class NullSentinel:
    """Disabled sentinel: watch() is identity, nothing records."""

    events: list = []

    def watch(self, fn: Callable, name: str | None = None,
              expected: int = 1) -> Callable:
        return fn

    def summary(self) -> dict:
        return {}


NULL_SENTINEL = NullSentinel()
