"""Pallas TPU kernel: fused FedAvg combine  out = sum_k alpha_k * w_k.

jnp's ``(stacked * a).sum(0)`` materializes the scaled stack (K extra
HBM writes+reads); this kernel keeps the K-way weighted reduction in
VMEM: each grid step loads one (K, BLOCK) tile and writes one BLOCK —
K reads + 1 write, the streaming lower bound for Eq. (1).

alphas ride along as a (K, 1) f32 operand replicated to every step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_COLS = 2048
LANES = 128


def _kernel(x_ref, a_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (K, 1, BLOCK_COLS)
    a = a_ref[...].astype(jnp.float32)          # (K, 1)
    aw = a[:, :, None]
    # masked semantics: alpha == 0 contributes exact zero even for a
    # non-finite row (a diverged non-winner in the full-cohort merge)
    terms = jnp.where(aw != 0.0, x * aw, 0.0)
    o_ref[...] = jnp.sum(terms, axis=0).astype(o_ref.dtype)


def _retile(x, k):
    flat = x.reshape(k, -1)
    n = flat.shape[1]
    cols = -(-n // BLOCK_COLS) * BLOCK_COLS
    out = jnp.zeros((k, cols), x.dtype).at[:, :n].set(flat)
    return out


def fedavg_pallas(stacked, alphas, *, interpret=False):
    """stacked: (K, ...) any shape; alphas: (K,) f32."""
    k = stacked.shape[0]
    orig_shape = stacked.shape[1:]
    n = 1
    for s in orig_shape:
        n *= s
    x = _retile(stacked, k)                      # (K, cols)
    cols = x.shape[1]
    x = x.reshape(k, 1, cols)
    a = alphas.reshape(k, 1).astype(jnp.float32)
    grid = (cols // BLOCK_COLS,)
    out = pl.pallas_call(
        _kernel,
        name="fedavg_combine",
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1, BLOCK_COLS), lambda i: (0, 0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cols), stacked.dtype),
        interpret=interpret,
    )(x, a)
    return out.reshape(cols)[:n].reshape(orig_shape)
