"""Train state: params + optimizer state + BN statistics + step counter.

One immutable pytree replacing the reference's scattered mutable state (model
parameters inside ``nn.Module``, optimizer slots inside ``torch.optim.SGD``,
BN running stats as module buffers).  Being a pytree, the whole state is
shardable, donatable, and checkpointable as a unit — full trainer-state resume
(the Chainer snapshot shape, reference chainer/train_mnist.py:91-93,120-122)
is just serializing this object.
"""

from __future__ import annotations

from typing import Any, Callable

import flax
import jax
import optax
from flax import core


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: core.FrozenDict[str, Any]
    opt_state: optax.OptState
    batch_stats: core.FrozenDict[str, Any] | None
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    def apply_gradients(self, *, grads, batch_stats=None):
        updates, new_opt_state = self.tx.update(
            grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=batch_stats if batch_stats is not None
            else self.batch_stats,
        )

    @classmethod
    def create(cls, *, apply_fn, params, tx, batch_stats=None):
        import jax.numpy as jnp
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            batch_stats=batch_stats,
            apply_fn=apply_fn,
            tx=tx,
        )


def init_state(model, rng, example_input, tx) -> TrainState:
    """Initialize model variables and wrap them in a TrainState.

    The whole initialization (flax init + optimizer slot init) runs under one
    jit: eager init would dispatch (and compile) thousands of tiny ops one by
    one — minutes for a 110-layer model on an accelerator vs seconds
    jitted.
    """
    def build(rng):
        variables = model.init(rng, example_input, train=False)
        params = variables["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=tx,
            batch_stats=variables.get("batch_stats"))

    return jax.jit(build)(rng)
