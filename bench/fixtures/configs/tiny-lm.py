"""Plain float32 reference of the fixture's next-token model: a frozen
embedding table (vocab x d) and a trained output layer (d -> vocab);
the logits of position i predict token i + 1."""
import jax
import jax.numpy as jnp


def init(key, cfg):
    v, d = cfg["vocab_size"], cfg["d_model"]
    k1, k2 = jax.random.split(key)
    s = d ** -0.5
    return {"embed": jax.random.normal(k1, (v, d), jnp.float32),
            "out": {"w": jax.random.uniform(k2, (d, v), jnp.float32, -s, s),
                    "b": jnp.zeros((v,), jnp.float32)}}


def split(weights):
    """The output layer is trained; the embedding is frozen."""
    return {"out": weights["out"]}, {"embed": weights["embed"]}


def _token_losses(trained, frozen, tokens):
    onehot = jax.nn.one_hot(tokens[:, :-1], frozen["embed"].shape[0],
                            dtype=frozen["embed"].dtype)
    h = onehot @ frozen["embed"]
    logits = h @ trained["out"]["w"] + trained["out"]["b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def loss(trained, frozen, batch, cfg):
    """Mean next-token cross-entropy of a batch of sequences."""
    return jnp.mean(_token_losses(trained, frozen, batch["tokens"]))


def evaluate(trained, frozen, test, cfg, dtype, batch=256):
    """Held-out mean next-token loss, in ``dtype``."""
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    fn = jax.jit(lambda t, f, x: jnp.sum(_token_losses(cast(t), cast(f), x)))
    tokens = test["tokens"]
    total = sum(float(fn(trained, frozen, tokens[i:i + batch]))
                for i in range(0, len(tokens), batch))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def train_flops_per_example(cfg, tr):
    """Multiply-adds x2 of one sequence's SGD step (``tr["seq_len"]``
    predicted tokens) through the trained layer: its forward pass and
    its weight gradient; the frozen embedding needs no gradient."""
    return 2 * 2 * cfg["d_model"] * cfg["vocab_size"] * tr["seq_len"]


def parameter_count(cfg):
    """Weights of the trained part: Eq. 1 reads and writes these."""
    return cfg["d_model"] * cfg["vocab_size"] + cfg["vocab_size"]
