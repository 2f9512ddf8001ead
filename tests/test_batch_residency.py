"""The dense sweep's data stack on the device
(``HostBackend._resident_data``): the batches each round program
gathers step by step equal the host gather of the same draws bit for
bit, and the stack is put on the device once per backend and never
donated."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import (ExperimentSpec, SweepSpec, build_host_engine,
                          make_accuracy_eval, spans)
from repro.engine import backends
from repro.engine.backends import TILE, data_rows
from repro.objectives import ObjectiveSpec
from repro.sharding import cohort_mesh

NUM_USERS, N_PER_USER, DIM, CLASSES = 6, 40, 12, 4
BATCH, K, ROUNDS, EPOCHS = 8, 2, 3, 2
SEEDS = (3, 11)                         # E=2 lanes
#: an example in the resident stack: x padded to whole tiles, and y
RESIDENT_ROW = -(-DIM // (TILE[0] * TILE[1])) * TILE[0] * TILE[1] * 4 + 4
TAKE = (N_PER_USER // BATCH) * BATCH

#: the plain sweep and both objective round programs (with and without
#: the FedDyn h-state)
OBJECTIVES = {
    "plain": None,
    "fedprox": ObjectiveSpec(local="fedprox", mu=0.1),
    "feddyn": ObjectiveSpec(local="feddyn", alpha=0.1, aggregator="fedadam",
                            server_lr=0.1),
}


@pytest.fixture(scope="module")
def cell():
    rng = np.random.default_rng(29)
    user_data = []
    for u in range(NUM_USERS):
        probs = np.ones(CLASSES) / CLASSES
        probs[u % CLASSES] += 1.0
        probs /= probs.sum()
        user_data.append({
            "x": rng.normal(size=(N_PER_USER, DIM)).astype(np.float32),
            "y": rng.choice(CLASSES, N_PER_USER, p=probs).astype(np.int32),
        })
    x_test = rng.normal(size=(50, DIM)).astype(np.float32)
    y_test = rng.choice(CLASSES, 50).astype(np.int32)

    def apply_fn(params, x):
        return x @ params["w"] + params["b"]

    def loss_fn(params, batch):
        logits = apply_fn(params, batch["x"])
        oh = jax.nn.one_hot(batch["y"], CLASSES)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    params = {"w": jnp.zeros((DIM, CLASSES), jnp.float32),
              "b": jnp.zeros((CLASSES,), jnp.float32)}
    return params, loss_fn, user_data, make_accuracy_eval(
        apply_fn, x_test, y_test, batch=16)


def _specs(objective=None):
    return [ExperimentSpec(
        rounds=ROUNDS, strategy="priority-distributed", seed=s,
        k_per_round=K, batch_size=BATCH, local_epochs=EPOCHS, eval_every=1,
        round_mode="fused", objective=objective) for s in SEEDS]


def _engine(cell, specs, mesh=None):
    params, loss_fn, user_data, eval_fn = cell
    return build_host_engine(specs[0], params, loss_fn, user_data, eval_fn,
                             mesh=mesh)


def _sweep(cell, specs, mesh=None):
    res = _engine(cell, specs, mesh).run_sweep(SweepSpec(specs))
    jax.block_until_ready(res.final_globals)
    return res


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_device_batches_equal_host_batches(cell, objective, monkeypatch):
    """Every batch each round program trains on, read back from inside
    its scan, against the host's fancy-index of the same draws. An
    ``id`` leaf names each example, so a batch read back in any order
    finds its host twin."""
    params, loss_fn, user_data, eval_fn = cell
    users = [{**d, "id": (u * N_PER_USER + np.arange(N_PER_USER)).astype(
        np.int32)} for u, d in enumerate(user_data)]
    seen = []
    take = backends.take_rows

    def spy(rows, idx, shapes):
        out = take(rows, idx, shapes)
        jax.debug.callback(
            lambda b: seen.append(jax.tree.map(np.asarray, b)), out)
        return out
    monkeypatch.setattr(backends, "take_rows", spy)
    specs = _specs(OBJECTIVES[objective])
    res = build_host_engine(specs[0], params, loss_fn, users, eval_fn
                            ).run_sweep(SweepSpec(specs))
    jax.block_until_ready(res.final_globals)
    jax.effects_barrier()

    be = build_host_engine(specs[0], params, loss_fn, users).backend
    st = be.sweep_init(params, SEEDS,
                       objectives=[s.objective for s in specs])
    rows = np.arange(NUM_USERS)[None, :, None]
    steps = EPOCHS * TAKE // BATCH
    want = {}
    for _ in range(ROUNDS):
        b = be._gather_sweep_rows(rows, be._draw_sweep_big(st))
        assert b["x"].shape == (len(SEEDS), NUM_USERS, steps, BATCH, DIM)
        for e in range(len(SEEDS)):
            for u in range(NUM_USERS):
                for k in range(steps):
                    one = jax.tree.map(lambda a: a[e, u, k], b)
                    want[tuple(one["id"])] = one
    assert len(want) == ROUNDS * len(SEEDS) * NUM_USERS * steps
    assert sorted(tuple(b["id"]) for b in seen) == sorted(want)
    for got in seen:
        _leaves_equal(got, want[tuple(got["id"])])


def test_resident_stack_is_put_once_and_never_donated(cell):
    specs = _specs()
    eng = _engine(cell, specs)
    be = eng.backend
    spans.start()
    try:
        first = eng.run_sweep(SweepSpec(specs))
        data = be._resident_data(len(SEEDS))
        second = eng.run_sweep(SweepSpec(specs))
        jax.block_until_ready(second.final_globals)
    finally:
        _, counters = spans.stop()
    assert counters["batches.resident_bytes"] == (
        NUM_USERS * N_PER_USER * RESIDENT_ROW)
    assert list(be._sweep_data.values()) == [data]
    assert be._resident_data(len(SEEDS)) is data
    # readable after every round program took it next to a donated stack
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(data))
    # the round program donates the model stack and never the data
    _, rnd, _ = be._sweep_fns[len(SEEDS)]
    st = be.sweep_init(cell[0], SEEDS)
    info, _ = rnd.lower(st.stack, data, be.sweep_batches(st), True).args_info
    donated = [{i.donated for i in jax.tree.leaves(a)} for a in info]
    assert donated == [{True}, {False}, {False}]
    assert data["x"].dtype == jnp.float32
    _leaves_equal(data, jax.tree.map(data_rows, be._xstack))
    for h1, h2 in zip(first.histories, second.histories):
        assert h1.winners == h2.winners
        assert h1.train_loss == h2.train_loss


def test_one_device_mesh_gives_the_same_sweep(cell):
    specs = _specs()
    plain = _sweep(cell, specs)
    mesh = cohort_mesh(jax.devices()[:1])
    eng = _engine(cell, specs, mesh)
    meshed = eng.run_sweep(SweepSpec(specs))
    data = eng.backend._resident_data(len(SEEDS))
    assert data["x"].sharding.mesh.shape == mesh.shape
    for hp, hm in zip(plain.histories, meshed.histories):
        assert hp.winners == hm.winners
        assert hp.train_loss == hm.train_loss
    _leaves_equal(plain.final_globals, meshed.final_globals)
