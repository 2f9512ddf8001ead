"""Program side of the fixture's next-token model (``program`` in
``bench/fixtures/configs/tiny-lm.json``): the engine trains the output
layer alone; the embedding is closed over, shared and never updated."""
import jax
import jax.numpy as jnp
import numpy as np


def _losses(embed, params, tokens):
    h = jnp.take(embed, tokens[:, :-1], axis=0)
    logits = jnp.einsum("bsd,dv->bsv", h, params["out"]["w"]) \
        + params["out"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)


def build(cfg, weights, test):
    """``(trained weights, loss_fn, eval_fn, build_host_engine kwargs)``:
    the trained part is the output layer; the eval is the held-out mean
    next-token loss."""
    embed = weights["embed"]
    tokens = np.asarray(test["tokens"])

    def loss_fn(params, batch):
        return jnp.mean(_losses(embed, params, batch["tokens"]))

    total = jax.jit(lambda p, t: jnp.sum(_losses(embed, p, t)))

    def eval_fn(params):
        return float(total(params, tokens)) / (tokens.shape[0]
                                               * (tokens.shape[1] - 1))

    return {"out": weights["out"]}, loss_fn, eval_fn, {}
