"""What a data-parallel step computes, for the references: the mean over the
shards of each shard's loss and gradient."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def loss_and_grad(shard_value_and_grad, params, *sharded):
    """``shard_value_and_grad(params, *shard) -> (loss, grad)`` is one compiled
    program, called once a shard; every array of ``sharded`` has the shards
    on its first axis. Returns the mean loss, as a float, and the mean
    gradient."""
    shards = len(sharded[0])
    loss, grad = 0.0, None
    for s in range(shards):
        l, g = shard_value_and_grad(params, *(x[s] for x in sharded))
        loss += float(l) / shards
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    return loss, jax.tree.map(lambda g: g / shards, grad)


def norm(tree) -> float:
    """The global norm of a tree, summed leaf by leaf on the host."""
    return sum(float(jnp.sum(jnp.square(x)))
               for x in jax.tree.leaves(tree)) ** 0.5
