"""Local objective step laws compiled into the training scans.

``objective_epoch_scan`` is the FedProx/FedDyn-generalized twin of
``core.client.sgd_epoch_scan`` — same scan, same ``sgd_update``, plus

* a proximal gradient term ``prox * (w - w_global)`` (FedProx's
  ``mu``, FedDyn's ``alpha``), and
* an optional per-user h-vector subtracted from the gradient (FedDyn's
  dynamic regularizer; updated at merge time in the backend).

Bit-transparency: the proximal term sits behind a per-term
``jnp.where(prox != 0, ...)`` guard because ``g + 0 * (w - w_g)`` is
NOT an IEEE-754 identity (it flips -0.0 gradients to +0.0).  The h
subtraction needs no guard: h is exactly +0.0 until the first
``alpha != 0`` merge, and ``x - (+0.0)`` IS a bitwise identity for
every x (including -0.0).  So an inert spec's trained params — and
hence its Eq. 2 priorities and contention winners — are bit-equal to
the plain scan's.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.objectives.spec import LocalObjective, register_local
from repro.optim.sgd import sgd_update

register_local(LocalObjective("fedavg", uses_h=False, coeff=lambda s: 0.0))
register_local(LocalObjective("fedprox", uses_h=False, coeff=lambda s: s.mu))
register_local(LocalObjective("feddyn", uses_h=True, coeff=lambda s: s.alpha))


def objective_epoch_scan(loss_fn: Callable, lr: float, use_h: bool,
                         use_kernel: bool = True) -> Callable:
    """Returns ``run(params, batched_data, glob, prox[, h]) ->
    (params, per_batch_losses)``.

    ``glob`` is the round-start global (the proximal anchor), ``prox``
    a scalar (traced, so one compiled program serves every coefficient —
    sweeps vmap a per-lane (E,) vector over it), ``h`` the per-user
    FedDyn state when ``use_h`` (structural: lanes without h-state in a
    mixed sweep ride the same program with an all-zero h row, which is
    bitwise free — see module docstring). ``use_kernel`` as in
    ``sgd_epoch_scan``.
    """

    def run(params, batched_data, glob, prox, h=None):
        def step(p, batch):
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            grads = jax.tree.map(
                lambda g, pp, wg: jnp.where(
                    prox != 0.0, g + prox * (pp - wg), g),
                grads, p, glob)
            if use_h:
                grads = jax.tree.map(jnp.subtract, grads, h)
            return sgd_update(p, grads, lr, use_kernel), loss

        return jax.lax.scan(step, params, batched_data)

    return run
