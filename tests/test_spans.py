"""The round loop's tracer (``repro.engine.spans``): nothing is recorded
while it is off, a sweep gives bit-identical results with it on or off,
every round has its span tree, the counters count what the shapes say,
and the spans sit on the ``time.time_ns`` clock."""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import (ExperimentSpec, SweepSpec, backends,
                          build_host_engine, make_accuracy_eval, spans)

# a paper-shaped cell at toy widths: U=10 non-IID users, K=2, eval
# every round
NUM_USERS, N_PER_USER, DIM, CLASSES = 10, 48, 12, 4
BATCH, K, N_TEST, EVAL_BATCH = 16, 2, 100, 32
ROUNDS, LANES = 3, 2


@pytest.fixture(scope="module")
def cell():
    rng = np.random.default_rng(13)
    user_data = []
    for u in range(NUM_USERS):
        probs = np.ones(CLASSES) / CLASSES
        probs[u % CLASSES] += 1.0
        probs /= probs.sum()
        user_data.append({
            "x": rng.normal(size=(N_PER_USER, DIM)).astype(np.float32),
            "y": rng.choice(CLASSES, N_PER_USER, p=probs).astype(np.int32),
        })
    x_test = rng.normal(size=(N_TEST, DIM)).astype(np.float32)
    y_test = rng.choice(CLASSES, N_TEST).astype(np.int32)

    def apply_fn(params, x):
        return x @ params["w"] + params["b"]

    def loss_fn(params, batch):
        logits = apply_fn(params, batch["x"])
        oh = jax.nn.one_hot(batch["y"], CLASSES)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    params = {"w": jnp.zeros((DIM, CLASSES), jnp.float32),
              "b": jnp.zeros((CLASSES,), jnp.float32)}
    return params, loss_fn, user_data, make_accuracy_eval(
        apply_fn, x_test, y_test, batch=EVAL_BATCH)


#: round paths: (round_mode, sparse_priority)
PATHS = {"fused": ("fused", None), "sparse-stale": ("sparse", "stale"),
         "sparse-prepass": ("sparse", "prepass")}


def _sweep(cell, path, *, traced, overlap=True):
    """One fresh engine, one ``LANES``-lane sweep; returns the result
    and, when ``traced``, the recorded spans and counters."""
    params, loss_fn, user_data, eval_fn = cell
    mode, prio = PATHS[path]
    specs = [ExperimentSpec(
        rounds=ROUNDS, strategy="priority-distributed", seed=s,
        k_per_round=K, batch_size=BATCH, eval_every=1, round_mode=mode,
        **({"sparse_priority": prio} if prio else {})) for s in (3, 4)]
    engine = build_host_engine(specs[0], params, loss_fn, user_data,
                               eval_fn)
    rec, counters = [], {}
    if traced:
        spans.start()
    try:
        res = engine.run_sweep(SweepSpec(specs, overlap=overlap))
        jax.block_until_ready(res.final_globals)
    finally:
        if traced:
            rec, counters = spans.stop()
    return res, rec, counters


def _children(rec, i):
    return [(j, r) for j, r in enumerate(rec) if r[3] == i]


def test_off_records_nothing(cell):
    assert not spans.on
    assert spans.span("fl.round", round=0) is spans.span("x")
    spans.count("eval.fetches", 5)
    _sweep(cell, "fused", traced=False)
    spans.start()
    rec, counters = spans.stop()
    assert rec == [] and counters == {}
    assert not spans.on


@pytest.mark.parametrize("path", ["fused", "sparse-stale"])
def test_results_identical_with_tracer_on_and_off(cell, path):
    off, _, _ = _sweep(cell, path, traced=False)
    on, rec, _ = _sweep(cell, path, traced=True)
    assert rec
    for h_off, h_on in zip(off.histories, on.histories):
        assert h_on.winners == h_off.winners
        assert h_on.train_loss == h_off.train_loss
        assert h_on.priorities == h_off.priorities
        assert h_on.accuracy == h_off.accuracy
        np.testing.assert_array_equal(h_on.selections, h_off.selections)
        assert h_on.collisions == h_off.collisions
    for a, b in zip(jax.tree.leaves(off.final_globals),
                    jax.tree.leaves(on.final_globals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _batches_ok(rec, i, for_round, **attrs):
    name, a, b, _, at = rec[i]
    assert name == "fl.round.batches"
    assert at == {"job": 0, "for_round": for_round, **attrs}
    kids = _children(rec, i)
    # the fused round program gathers its batches on the device: the
    # host only draws their rows
    assert [r[0] for _, r in kids] == ["fl.round.batches.draw"]
    for _, (_, ka, kb, _, kat) in kids:
        assert a <= ka <= kb <= b and kat == at


@pytest.mark.parametrize("overlap", [True, False])
def test_fused_span_tree(cell, overlap):
    _, rec, _ = _sweep(cell, "fused", traced=True, overlap=overlap)
    [(si, sweep)] = [(i, r) for i, r in enumerate(rec)
                     if r[0] == "fl.sweep"]
    assert sweep[3] is None and sweep[4] == {"job": 0}
    top = _children(rec, si)
    names = [r[0] for _, r in top]
    assert names == (["fl.round.batches", "fl.round.train"]
                     + ["fl.round"] * ROUNDS)
    _batches_ok(rec, top[0][0], 0)
    assert top[1][1][4] == {"job": 0, "for_round": 0}
    for t, (ri, rnd) in enumerate(top[2:]):
        assert rnd[4] == {"job": 0, "round": t}
        assert sweep[1] <= rnd[1] <= rnd[2] <= sweep[2]
        kids = _children(rec, ri)
        draw = ([] if t + 1 == ROUNDS else ["fl.round.batches"])
        train = ([] if t + 1 == ROUNDS else ["fl.round.train"])
        want = (draw + ["fl.round.wait", "fl.round.select",
                        "fl.round.merge"] + train if overlap else
                ["fl.round.wait", "fl.round.select", "fl.round.merge"]
                + draw + train)
        want += ["fl.round.record"] + ["fl.round.eval"] * LANES
        assert [r[0] for _, r in kids] == want
        for j, (ki, k) in enumerate(kids):
            assert rnd[1] <= k[1] <= k[2] <= rnd[2]
            if j:
                assert kids[j - 1][1][2] <= k[1]
            if k[0] == "fl.round.batches":
                _batches_ok(rec, ki, t + 1, round=t)
            elif k[0] == "fl.round.train":
                assert k[4] == {"job": 0, "round": t, "for_round": t + 1}
            elif k[0] == "fl.round.eval":
                assert k[4]["lane"] == j - (len(kids) - LANES)
            else:
                assert k[4] == {"job": 0, "round": t}


def test_sparse_span_tree(cell):
    _, rec, _ = _sweep(cell, "sparse-prepass", traced=True)
    [si] = [i for i, r in enumerate(rec) if r[0] == "fl.sweep"]
    rounds = _children(rec, si)
    assert [r[0] for _, r in rounds] == ["fl.round"] * ROUNDS
    for t, (ri, rnd) in enumerate(rounds):
        kids = _children(rec, ri)
        assert [r[0] for _, r in kids] == [
            "fl.round.prepass", "fl.round.wait", "fl.round.select",
            "fl.round.train", "fl.round.merge", "fl.round.record",
        ] + ["fl.round.eval"] * LANES
        assert kids[3][1][4] == {"job": 0, "round": t, "for_round": t}
        # the prepass draws every lane's epochs, then gathers the
        # cohort in chunks; the winners' rows are gathered for training
        pre = [r[0] for _, r in _children(rec, kids[0][0])]
        assert pre[0] == "fl.round.batches.draw"
        assert set(pre[1:]) == {"fl.round.batches.gather"}
        assert [r[0] for _, r in _children(rec, kids[3][0])] == [
            "fl.round.batches.gather"]


def test_job_ids_follow_the_calls(cell):
    params, loss_fn, user_data, eval_fn = cell
    spec = ExperimentSpec(rounds=1, k_per_round=K, batch_size=BATCH,
                          round_mode="fused")
    engine = build_host_engine(spec, params, loss_fn, user_data)
    spans.start()
    try:
        engine.run_sweep([spec])
        engine.run_sweep([spec])
    finally:
        rec, _ = spans.stop()
    jobs = [r[4]["job"] for r in rec if r[0] == "fl.sweep"]
    assert jobs == [0, 1]
    for name, _, _, parent, attrs in rec:
        if parent is not None:
            assert attrs["job"] == rec[parent][4]["job"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_counters_match_the_shapes(cell, path):
    _, _, counters = _sweep(cell, path, traced=True)
    row = DIM * 4 + 4                  # float32 x, int32 y
    take = (N_PER_USER // BATCH) * BATCH
    if path == "fused":
        # a round ships its int32 index tensor; the data stack goes to
        # the device once
        assert counters["batches.h2d_bytes"] == (
            ROUNDS * LANES * NUM_USERS * take * 4)
        tile = math.prod(backends.TILE)  # x padded to whole tiles
        assert counters["batches.resident_bytes"] == (
            NUM_USERS * N_PER_USER * (-(-DIM // tile) * tile * 4 + 4))
        assert counters["batches.device_gathers"] == ROUNDS
        assert "batches.host_gathers" not in counters
    else:
        rows = ROUNDS * LANES * K
        if path == "sparse-prepass":   # the prepass trains every user
            rows += ROUNDS * LANES * NUM_USERS
        assert counters["batches.h2d_bytes"] == rows * take * row
        assert counters["batches.host_gathers"] == ROUNDS
        assert "batches.device_gathers" not in counters
    evals = ROUNDS * LANES
    assert counters["eval.h2d_bytes"] == evals * N_TEST * DIM * 4
    assert counters["eval.fetches"] == evals * math.ceil(N_TEST / EVAL_BATCH)


def test_spans_on_the_wall_clock():
    spans.start()
    try:
        t0 = time.time_ns()
        with spans.span("outer", job=7):
            t1 = time.time_ns()
            with spans.span("inner", round=2):
                time.sleep(0.002)
            t2 = time.time_ns()
        spans.count("n", 2)
        spans.count("n")
    finally:
        rec, counters = spans.stop()
    (o, oa, ob, op, oat), (i, ia, ib, ip, iat) = rec
    assert (o, op, oat) == ("outer", None, {"job": 7})
    assert (i, ip, iat) == ("inner", 0, {"job": 7, "round": 2})
    assert abs(oa - t0) <= 1e6 and abs(ia - t1) <= 1e6
    assert abs(ob - t2) <= 1e6
    assert oa <= ia and ia + 2e6 <= ib <= ob
    assert counters == {"n": 3}


def test_stop_inside_a_span_ends_it():
    spans.start()
    with spans.span("open"):
        rec, _ = spans.stop()
        assert not spans.on
    [(name, a, b, parent, _)] = rec
    assert name == "open" and parent is None and a <= b
