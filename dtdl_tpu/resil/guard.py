"""Step anomaly guard: on-device finite checks, lag-harvested policies.

A NaN/Inf loss or gradient burst is the most common way a long training
run dies — and the naive defense (``if not np.isfinite(float(loss))``
in the step loop) is a per-step host↔device sync, the exact stall PR 1
eliminated.  The guard splits the job across the async boundary:

* **in-jit** (:meth:`StepGuard.select`, folded into the compiled step by
  ``make_train_step(..., guard=)``): compute the global gradient norm,
  test ``isfinite(loss) & isfinite(grad_norm)`` (plus an optional
  ``grad_norm_limit``), and **select the old state when the step is
  bad** — a poisoned update never reaches the parameters, no matter how
  late the host learns about it.  The badness flag and the grad norm
  ride the step's metric dict through the PR-1 MetricsQueue, so the
  guard adds ZERO host↔device syncs (pinned by the sync-counting
  harness in tests/test_obs.py).  When no fault fires the select is
  ``where(False, old, new) == new`` elementwise — guarded training is
  bitwise identical to unguarded (pinned by tests/test_resil.py).

* **host-side** (:meth:`StepGuard.observe`, fed each drained per-step
  metric dict by train_epoch / Trainer): count bad steps and apply the
  policy, up to ``harvest lag`` steps after the fact — safe, because
  the in-jit select already suppressed the bad updates:

  - ``skip``     — log/count; a skipped step leaves the state exactly
    as if its batch had been dropped from the stream.  After
    ``max_consecutive`` bad steps in a row it escalates to
    :class:`GuardEscalationError` (a burst that long is divergence or
    broken data, not a transient).
  - ``raise``    — :class:`AnomalousStepError` on the first bad step.
  - ``rollback`` — after ``max_consecutive`` consecutive bad steps,
    raise :class:`GuardRollback`; the Trainer catches it, restores the
    last good snapshot, and resumes mid-epoch.  After ``max_rollbacks``
    rollbacks it escalates — a run that keeps rolling back is not
    making progress.

The replica-consistency rule: ``select`` must see only replica-invariant
inputs (the metric-synced loss, post-``grad_sync`` gradients), so every
replica takes the same branch and the replicated state stays bitwise
identical — the step factories order the calls accordingly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax


class AnomalousStepError(RuntimeError):
    """policy='raise': a non-finite (or over-limit) step was observed."""


class GuardEscalationError(RuntimeError):
    """The consecutive-bad-step (or rollback-budget) threshold tripped."""


class GuardRollback(Exception):
    """Control-flow signal: restore the last good snapshot and continue.

    Raised by :meth:`StepGuard.observe` under policy='rollback'; caught
    by ``Trainer._run``.  Deliberately NOT a RuntimeError so generic
    ``except RuntimeError`` recovery code cannot swallow it."""


class StepGuard:
    """Anomaly guard folded into a compiled train step (module docstring).

    One instance guards one logical training run: it is closed over by
    the jitted step (the pure :meth:`select` piece) and fed drained
    metrics on the host (:meth:`observe`).  Counters — ``n_bad``,
    ``n_rollbacks``, ``consecutive`` — are host state, lag-harvested.
    """

    POLICIES = ("skip", "raise", "rollback")

    def __init__(self, policy: str = "skip", max_consecutive: int = 3,
                 grad_norm_limit: Optional[float] = None,
                 max_rollbacks: int = 3, observer=None):
        if policy not in self.POLICIES:
            raise ValueError(f"unknown guard policy {policy!r} "
                             f"(one of {self.POLICIES})")
        if max_consecutive < 1:
            raise ValueError(f"max_consecutive must be >= 1, got "
                             f"{max_consecutive}")
        from dtdl_tpu.obs.observer import NULL_OBSERVER
        self.policy = policy
        self.max_consecutive = max_consecutive
        self.grad_norm_limit = grad_norm_limit
        self.max_rollbacks = max_rollbacks
        self.observer = observer or NULL_OBSERVER
        # host-side counters (updated at harvest, not dispatch)
        self.n_steps = 0
        self.n_bad = 0
        self.consecutive = 0
        self.n_rollbacks = 0
        self.last_bad: Optional[dict] = None
        self._win_prev = {"steps": 0, "bad_steps": 0, "rollbacks": 0}

    # ---- the in-jit piece (pure, traceable) --------------------------

    def select(self, old_state, new_state, loss, grads):
        """Suppress the update when the step is anomalous.

        ``loss`` must already be replica-invariant (metric-synced) and
        ``grads`` post-``grad_sync`` — see the module docstring.  Returns
        ``(state, {'bad_step', 'grad_norm'})``; the extra metrics ride
        the step's existing metric pytree through the async queue.
        """
        gnorm = optax.global_norm(grads)
        bad = jnp.logical_not(jnp.isfinite(loss) & jnp.isfinite(gnorm))
        if self.grad_norm_limit is not None:
            bad = jnp.logical_or(bad, gnorm > self.grad_norm_limit)
        # one Conditional over the whole state, not a select per leaf:
        # both branches are already-computed values, so XLA forwards the
        # chosen tree (measurably cheaper than N selects on CPU; under
        # shard_map the cond lowers to selects on the replicated flag)
        guarded = jax.lax.cond(bad, lambda: old_state, lambda: new_state)
        return guarded, {"bad_step": bad.astype(jnp.float32),
                         "grad_norm": gnorm}

    # ---- the host-side piece (lag-harvested) -------------------------

    def observe(self, vals: dict) -> None:
        """Apply the policy to one drained per-step metric dict.

        Called once per step *at the drain boundary* — up to ``lag``
        steps after dispatch, which is safe because the in-jit select
        already kept the bad update out of the state."""
        self.n_steps += 1
        if not vals.get("bad_step", 0.0):
            self.consecutive = 0
            return
        self.n_bad += 1
        self.consecutive += 1
        self.last_bad = {"loss": vals.get("loss"),
                         "grad_norm": vals.get("grad_norm")}
        self.observer.event("guard_bad_step", **self.last_bad)
        detail = (f"anomalous step (loss={vals.get('loss')}, "
                  f"grad_norm={vals.get('grad_norm')}): update suppressed "
                  f"on device")
        if self.policy == "raise":
            raise AnomalousStepError(detail)
        if self.consecutive >= self.max_consecutive:
            if self.policy == "rollback":
                self.consecutive = 0
                self.n_rollbacks += 1
                if self.n_rollbacks > self.max_rollbacks:
                    raise GuardEscalationError(
                        f"{self.n_rollbacks} rollbacks exceeded the budget "
                        f"of {self.max_rollbacks} — the run is not making "
                        f"progress; last bad step: {self.last_bad}")
                self.observer.event("guard_rollback",
                                    n_rollbacks=self.n_rollbacks)
                raise GuardRollback(detail)
            raise GuardEscalationError(
                f"{self.max_consecutive} consecutive anomalous steps under "
                f"policy='skip' — this is divergence or broken data, not a "
                f"transient; last bad step: {self.last_bad}")

    def summary(self) -> dict:
        """Run-level counters for reports."""
        return {"guard_steps": self.n_steps, "guard_bad_steps": self.n_bad,
                "guard_rollbacks": self.n_rollbacks}

    def window(self) -> dict:
        """Counter increments since the last :meth:`window` call — the
        no-arg delta source a :class:`~dtdl_tpu.obs.export.
        MetricsExporter` samples at drain boundaries (register as
        ``exporter.add_source("guard", guard.window)``; the source name
        supplies the ``guard_`` prefix, so keys here are bare).  The
        derived ``bad_step_ratio`` gauge plus the good/bad counter pair
        are exactly the fields ``default_train_slos()`` judges — the
        training twin of the serve ``window()`` sources."""
        cur = {"steps": self.n_steps, "bad_steps": self.n_bad,
               "rollbacks": self.n_rollbacks}
        out = {k: cur[k] - self._win_prev[k] for k in cur}
        self._win_prev = cur
        out["good_steps"] = out["steps"] - out["bad_steps"]
        out["bad_step_ratio"] = (out["bad_steps"] / out["steps"]
                                 if out["steps"] else 0.0)
        return out
