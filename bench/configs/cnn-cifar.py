"""Plain float32 reference of the paper's CNN (5x5 conv 128 -> 2x2 max
pool -> 5x5 conv 256 -> 2x2 max pool -> FC 10, ReLU after each conv,
SAME padding), its initialisation, and its training cost counted from
the shapes."""
import jax
import jax.numpy as jnp


def _side(cfg):
    return cfg["input_shape"][0] // 4


def init(key, cfg):
    k = cfg["kernel"]
    cin = cfg["input_shape"][2]
    c1, c2 = cfg["conv_channels"]
    flat = c2 * _side(cfg) ** 2
    n = cfg["n_classes"]
    k1, k2, k3 = jax.random.split(key, 3)
    s = flat ** -0.5
    return {"conv1": {"w": 0.05 * jax.random.normal(k1, (k, k, cin, c1)),
                      "b": jnp.zeros((c1,), jnp.float32)},
            "conv2": {"w": 0.05 * jax.random.normal(k2, (k, k, c1, c2)),
                      "b": jnp.zeros((c2,), jnp.float32)},
            "fc": {"w": jax.random.uniform(k3, (flat, n), jnp.float32, -s, s),
                   "b": jnp.zeros((n,), jnp.float32)}}


def _conv_relu(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.maximum(y + b, 0)


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(params, x):
    x = _pool(_conv_relu(x, params["conv1"]["w"], params["conv1"]["b"]))
    x = _pool(_conv_relu(x, params["conv2"]["w"], params["conv2"]["b"]))
    x = x.reshape(x.shape[0], -1)
    return jnp.dot(x, params["fc"]["w"]) + params["fc"]["b"]


def train_flops_per_example(cfg, tr):
    """Multiply-adds x2 of one SGD example: forward, weight gradients,
    and input gradients of every layer but the first (``tr``, the
    traffic, sets no size of an image)."""
    k = cfg["kernel"]
    hw = cfg["input_shape"][0]
    cin = cfg["input_shape"][2]
    c1, c2 = cfg["conv_channels"]
    conv1 = 2 * hw * hw * c1 * k * k * cin
    conv2 = 2 * (hw // 2) ** 2 * c2 * k * k * c1
    fc = 2 * c2 * _side(cfg) ** 2 * cfg["n_classes"]
    return 2 * conv1 + 3 * conv2 + 3 * fc


def parameter_count(cfg):
    k = cfg["kernel"]
    cin = cfg["input_shape"][2]
    c1, c2 = cfg["conv_channels"]
    flat = c2 * _side(cfg) ** 2
    return (k * k * cin * c1 + c1 + k * k * c1 * c2 + c2
            + flat * cfg["n_classes"] + cfg["n_classes"])
