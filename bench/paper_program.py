"""The program side of the paper's two classifiers (``program`` in
``bench/configs/mlp-fashion.json`` and ``cnn-cifar.json``): the
program's own model (``repro.models.paper_models``), a softmax
cross-entropy on ``batch["x"]`` / ``batch["y"]`` and the program's
accuracy eval. Every weight is trained."""


def build(cfg, weights, test):
    """``(trained weights, loss_fn, eval_fn, build_host_engine kwargs)``
    of the configuration ``cfg``, from the initial ``weights`` and the
    test split ``test`` (``{"x", "y"}``)."""
    import jax
    import jax.numpy as jnp
    from repro.engine import make_accuracy_eval
    from repro.models.paper_models import get_paper_model
    pm = cfg["program_model"]
    _, apply_fn = get_paper_model(pm["name"], pm["dataset"])
    n_classes = cfg["n_classes"]

    def loss_fn(params, batch):
        logits = apply_fn(params, batch["x"])
        oh = jax.nn.one_hot(batch["y"], n_classes)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    return (weights, loss_fn, make_accuracy_eval(apply_fn, test["x"],
                                                 test["y"]), {})
