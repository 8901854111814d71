"""The timed path broken underneath, for the tests: each fault takes the
compiled step and the runner's session (``runners/train.py:run``'s
``wrap_step``) and returns what is driven in the step's place.  Run as a
script it drives the rest of a run of a cell's rehearsal, without the
harness's look for a chip, and prints what the run record says of
``correct``:

    python3 _faults.py <cell> <seed> [<fault>]
"""

import json
import sys
import time


def state_unchanged(compiled, ses):
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        _, metrics = compiled(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return step


def half_batch(compiled, ses):
    """Half of the rows left out, the mean taken over the rest: the
    program's own step with a mask over the targets of the first half."""
    import jax.numpy as jnp

    from dtdl_tpu.train import make_lm_train_step
    masked = make_lm_train_step(ses.strategy)

    def step(state, batch):
        rows, row_tokens = batch["tokens"].shape
        mask = (jnp.arange(rows) < rows // 2).astype(jnp.float32)
        mask = jnp.broadcast_to(mask[:, None], (rows, row_tokens - 1))
        return masked(state, dict(batch, mask=mask))
    return step


def exchange_left_out(compiled, ses):
    """The gradients' exchange between the chips left out: the program's
    own step under its own strategy, whose ``grad_sync`` hands each chip's
    gradient back as it is.  The replicas then differ, which ``shard_map``
    has to be told not to check; the state that comes back is one chip's."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dtdl_tpu.train import make_lm_train_step

    class Unsynced(type(ses.strategy)):
        def grad_sync(self, grads):
            return grads

        def compile(self, step_fn, donate_state=True):
            mapped = jax.shard_map(
                step_fn, mesh=self.mesh, in_specs=(P(), P(self.axis)),
                out_specs=(P(), P()), check_vma=False)
            return jax.jit(mapped,
                           donate_argnums=(0,) if donate_state else ())

    return make_lm_train_step(Unsynced(ses.strategy.mesh, ses.strategy.axis))


def main(cell_name, seed, fault=None):
    import _paths  # noqa: F401
    import run as harness
    from runners import train
    _, cell, cfg = harness.load_cell(cell_name, rehearse=True)
    record = train.run(cell, cfg, {
        "seed": int(seed), "seconds": 0.2, "trace": False, "rehearse": True,
        "t_start": time.perf_counter(), "scratch": None},
        wrap_step=globals()[fault] if fault else None)
    print(json.dumps({k: record[k] for k in (
        "correct", "compared", "window", "attempted")}))


if __name__ == "__main__":
    main(*sys.argv[1:])
