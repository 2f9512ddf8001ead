"""Pallas TPU kernel: server aggregator step (objectives, DESIGN.md §10)

    d    = old - avg
    m'   = kind == 0 ? m : b1*m + (kind == 2 ? 1 - b1 : 1) * d
    v'   = kind == 2 ? b2*v + (1 - b2)*d² : v
    step = kind == 2 ? m' / (sqrt(v') + eps) : m'
    out  = inert ? avg : old - slr*step

with ``inert = (kind == 0) | (kind == 1 & b1 == 0 & slr == 1)`` — the
bit-level passthrough the objectives-inert winner-pin twins rely on
(see ``ref.server_opt_combine_ref`` for the full law and contract).

Tiling follows ``robust_pallas``: all four state tensors are flattened
and zero-padded to a (1, cols) row, each grid step streams one
(1, BLOCK_COLS) tile of avg/old/m/v plus the replicated (1, 5) consts
and writes the matching tiles of the three outputs — the streaming
lower bound (4 reads, 3 writes per block).  Elementwise, so the pad
lanes produce garbage that the caller's final slice drops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg import BLOCK_COLS, _retile


def _kernel(a_ref, o_ref, m_ref, v_ref, c_ref, out_ref, nm_ref, nv_ref):
    a = a_ref[...].astype(jnp.float32)           # (1, BLOCK_COLS)
    o = o_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)           # (1, 5)
    kind, b1, b2 = c[0, 0], c[0, 1], c[0, 2]
    slr, eps = c[0, 3], c[0, 4]
    d = o - a
    scale1 = jnp.where(kind == 2.0, 1.0 - b1, 1.0)
    nm = jnp.where(kind == 0.0, m, b1 * m + scale1 * d)
    nv = jnp.where(kind == 2.0, b2 * v + (1.0 - b2) * d * d, v)
    step = jnp.where(kind == 2.0, nm / (jnp.sqrt(nv) + eps), nm)
    inert = (kind == 0.0) | ((kind == 1.0) & (b1 == 0.0) & (slr == 1.0))
    out_ref[...] = jnp.where(inert, a, o - slr * step).astype(out_ref.dtype)
    nm_ref[...] = nm.astype(nm_ref.dtype)
    nv_ref[...] = nv.astype(nv_ref.dtype)


def server_opt_pallas(avg, old, m, v, consts, *, interpret=False):
    """avg/old/m/v: (...) one shape; consts: (5,) f32.
    Returns (out, new_m, new_v) with the input shapes/dtypes."""
    orig_shape = avg.shape
    n = 1
    for sdim in orig_shape:
        n *= sdim
    a = _retile(avg[None], 1)                    # (1, cols)
    o = _retile(old[None], 1)
    mm = _retile(m[None], 1)
    vv = _retile(v[None], 1)
    c = consts.reshape(1, 5).astype(jnp.float32)
    cols = a.shape[1]
    grid = (cols // BLOCK_COLS,)
    row = pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i))
    out, nm, nv = pl.pallas_call(
        _kernel,
        name="server_opt_combine",
        grid=grid,
        in_specs=[row, row, row, row,
                  pl.BlockSpec((1, 5), lambda i: (0, 0))],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, cols), avg.dtype),
                   jax.ShapeDtypeStruct((1, cols), m.dtype),
                   jax.ShapeDtypeStruct((1, cols), v.dtype)],
        interpret=interpret,
    )(a, o, mm, vv, c)
    unpad = lambda x: x.reshape(cols)[:n].reshape(orig_shape)
    return unpad(out), unpad(nm), unpad(nv)
