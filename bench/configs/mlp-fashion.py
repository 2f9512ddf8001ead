"""Plain float32 reference of the paper's MLP (784-200-10, ReLU), its
initialisation, and its training cost counted from the shapes."""
import jax
import jax.numpy as jnp


def init(key, cfg):
    d, h, c = cfg["d_input"], cfg["d_hidden"], cfg["n_classes"]
    k1, k2 = jax.random.split(key)
    s1, s2 = d ** -0.5, h ** -0.5
    return {"fc1": {"w": jax.random.uniform(k1, (d, h), jnp.float32, -s1, s1),
                    "b": jnp.zeros((h,), jnp.float32)},
            "fc2": {"w": jax.random.uniform(k2, (h, c), jnp.float32, -s2, s2),
                    "b": jnp.zeros((c,), jnp.float32)}}


def apply(params, x):
    x = x.reshape(x.shape[0], -1)
    h = jnp.maximum(jnp.dot(x, params["fc1"]["w"]) + params["fc1"]["b"], 0)
    return jnp.dot(h, params["fc2"]["w"]) + params["fc2"]["b"]


def train_flops_per_example(cfg, tr):
    """Multiply-adds x2 of one SGD example: the forward pass, the weight
    gradients, and the input gradient of every layer but the first (the
    data needs none; ``tr``, the traffic, sets no size of an image)."""
    d, h, c = cfg["d_input"], cfg["d_hidden"], cfg["n_classes"]
    fwd1, fwd2 = 2 * d * h, 2 * h * c
    return fwd1 + fwd2 + fwd1 + 2 * fwd2


def parameter_count(cfg):
    d, h, c = cfg["d_input"], cfg["d_hidden"], cfg["n_classes"]
    return d * h + h + h * c + c
