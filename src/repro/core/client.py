"""FL client: local SGD training + priority computation (Steps 2-3)."""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.priority import model_priority
from repro.core.rngs import client_rng
from repro.optim.sgd import sgd_update


def sgd_epoch_scan(loss_fn: Callable, lr: float,
                   use_kernel: bool = True) -> Callable:
    """Returns ``run(params, batched_data, take=None) -> (params,
    per_batch_losses)``: one SGD step per batch, scanned. With ``take``,
    each step's slice of ``batched_data`` is what ``take`` makes its
    batch from (the sweep round passes row indices and gathers them on
    the device). ``use_kernel=False`` keeps the update on its jnp oracle
    (a Pallas call cannot be partitioned, so a cohort sharded over
    several devices needs it).

    THE local-SGD inner loop — the ragged per-user trainer, the stacked
    vmap path and the fused cohort round all build on this one scan,
    so the three HostBackend paths can't drift apart numerically
    (their winner parity is pinned by ``tests/test_fused_round.py``).
    """

    def run(params, batched_data, take=None):
        def step(p, batch):
            if take is not None:
                batch = take(batch)
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            return sgd_update(p, grads, lr, use_kernel), loss

        return jax.lax.scan(step, params, batched_data)

    return run


def make_local_trainer(loss_fn: Callable, lr: float) -> Callable:
    """Returns jit'd ``train(params, batched_data) -> (params, mean_loss)``.

    ``batched_data``: pytree whose leaves have shape (num_batches, batch,
    ...); one SGD step per batch, scanned.
    """
    run = sgd_epoch_scan(loss_fn, lr)

    @jax.jit
    def train(params, batched_data):
        params, losses = run(params, batched_data)
        return params, losses.mean()

    return train


def batch_epoch(rng: np.random.Generator, data, batch_size: int):
    """Shuffle + reshape host data into (nb, bs, ...); drops remainder."""
    n = len(jax.tree.leaves(data)[0])
    nb = max(1, n // batch_size)
    perm = rng.permutation(n)[: nb * batch_size]
    return jax.tree.map(
        lambda a: np.asarray(a)[perm].reshape((nb, batch_size) + a.shape[1:]),
        data)


class Client:
    """One FL user: local dataset + 1-epoch SGD + Eq. 2 priority."""

    def __init__(self, uid: int, data, loss_fn, *, lr=1e-2, batch_size=32,
                 local_epochs=1, seed=0):
        self.uid = uid
        self.data = data
        self.num_examples = len(jax.tree.leaves(data)[0])
        self.batch_size = batch_size
        self.local_epochs = local_epochs
        self._trainer = make_local_trainer(loss_fn, lr)
        # per-user stream spawned from the experiment seed (core.rngs):
        # independent across users AND across experiment seeds, unlike
        # the old `seed + 1000 * uid` rule
        self._rng = client_rng(seed, uid)

    def train(self, global_params) -> Tuple:
        """Step 2: returns (local_params, mean_loss)."""
        params = global_params
        loss = jnp.zeros(())
        for _ in range(self.local_epochs):
            batched = batch_epoch(self._rng, self.data, self.batch_size)
            params, loss = self._trainer(params, batched)
        return params, loss

    def priority(self, local_params, global_params) -> float:
        """Step 3: Eq. 2."""
        return float(model_priority(local_params, global_params))
