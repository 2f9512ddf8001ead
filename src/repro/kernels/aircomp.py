"""Pallas TPU kernel: AirComp over-the-air merge
``out = (sum_k w_k * x_k + n) * scale``.

The analog superposition the over-the-air merge models is exactly the
Eq. 1 weighted reduction plus an additive receiver-noise plane and a
scalar post-scaling — so the kernel keeps ``fedavg_combine``'s
streaming shape (each grid step reduces one (K, BLOCK) tile in VMEM)
and fuses the noise add + scale into the same pass: K + 1 reads and
1 write per output block, vs K reads + 1 write for the noiseless
kernel. That bounds the AirComp merge at ~(K+1)/K of fedavg's memory
traffic, which is what keeps it within 2x of ``fedavg_combine``
throughput at cohort scale (``BENCH_channel.json``).

Weights ride along as a (K, 1) f32 operand replicated to every step,
the scale as a (1, 1) f32 operand; the noise plane is pre-generated by
the caller (jax.random on the output shape) and tiled like the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_COLS = 2048


def _kernel(x_ref, w_ref, n_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (K, 1, BLOCK_COLS)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    noise = n_ref[...].astype(jnp.float32)      # (1, BLOCK_COLS)
    scale = s_ref[...].astype(jnp.float32)      # (1, 1)
    ww = w[:, :, None]
    # masked semantics: weight == 0 contributes exact zero even for a
    # non-finite row (a diverged loser in the full-cohort merge)
    terms = jnp.where(ww != 0.0, x * ww, 0.0)
    acc = jnp.sum(terms, axis=0) + noise
    o_ref[...] = (acc * scale[0]).astype(o_ref.dtype)


def _retile(x, k):
    flat = x.reshape(k, -1)
    n = flat.shape[1]
    cols = -(-n // BLOCK_COLS) * BLOCK_COLS
    out = jnp.zeros((k, cols), x.dtype).at[:, :n].set(flat)
    return out


def aircomp_pallas(stacked, weights, noise, scale, *, interpret=False):
    """stacked: (K, ...) any shape; weights: (K,) f32; noise: the
    output shape (or broadcastable); scale: scalar."""
    k = stacked.shape[0]
    orig_shape = stacked.shape[1:]
    n = 1
    for s in orig_shape:
        n *= s
    x = _retile(stacked, k)                      # (K, cols)
    cols = x.shape[1]
    x = x.reshape(k, 1, cols)
    w = weights.reshape(k, 1).astype(jnp.float32)
    nz = jnp.broadcast_to(jnp.asarray(noise, jnp.float32),
                          orig_shape).reshape(1, -1)
    nz = jnp.zeros((1, cols), jnp.float32).at[:, :n].set(nz)
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    grid = (cols // BLOCK_COLS,)
    out = pl.pallas_call(
        _kernel,
        name="aircomp_combine",
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1, BLOCK_COLS), lambda i: (0, 0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cols), stacked.dtype),
        interpret=interpret,
    )(x, w, nz, sc)
    return out.reshape(cols)[:n].reshape(orig_shape)
