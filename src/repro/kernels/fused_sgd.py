"""Pallas TPU kernel: fused SGD update  p <- p - lr * g  (one RMW pass).

The FL client's local step (paper Sec. II-A) touches every parameter;
fusing the scale+subtract avoids a temporary lr*g HBM round-trip. lr is
a traced scalar carried as a (1, 1) operand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128


def _kernel(p_ref, g_ref, lr_ref, o_ref):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (p - lr_ref[0, 0] * g).astype(o_ref.dtype)


def _retile(x):
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    padded = jnp.zeros((rows * LANES,), x.dtype).at[:n].set(flat)
    return padded.reshape(rows, LANES)


def fused_sgd_pallas(param, grad, lr, *, interpret=False):
    orig_shape = param.shape
    n = param.size
    p = _retile(param)
    g = _retile(grad)
    rows = p.shape[0]
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _kernel,
        name="fused_sgd",
        grid=(rows // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(p.shape, param.dtype),
        interpret=interpret,
    )(p, g, lr_arr)
    return out.reshape(-1)[:n].reshape(orig_shape)
