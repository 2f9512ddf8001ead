"""Evaluation helpers for engine runs.

Moved here from the deleted ``repro.core.federated`` shim — the eval
callback is part of the engine surface (``FLEngine(eval_fn=...)``), not
of the paper's core selection math.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.engine import spans


def make_accuracy_eval(apply_fn, x_test, y_test, batch: int = 256):
    """Batched classifier accuracy eval_fn. Each batch's test slice goes
    to the device and its logits come back to the host (counters
    ``eval.h2d_bytes`` and ``eval.fetches`` of ``repro.engine.spans``)."""
    x_test = np.asarray(x_test)
    y_test = np.asarray(y_test)
    apply_jit = jax.jit(apply_fn)

    def eval_fn(params) -> float:
        correct = 0
        for i in range(0, len(y_test), batch):
            x = x_test[i:i + batch]
            logits = apply_jit(params, x)
            if spans.on:
                spans.count("eval.h2d_bytes", x.nbytes)
                spans.count("eval.fetches")
            correct += int((np.argmax(np.asarray(logits), -1)
                            == y_test[i:i + batch]).sum())
        return correct / len(y_test)

    return eval_fn
