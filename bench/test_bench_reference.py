"""The plain reference agrees with the program at tiny sizes on the CPU,
and the comparison tells a broken timed path from a sound one."""
import functools
import hashlib
import json
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compare, control, reference, run
from repro.core.csma import CSMAConfig, CSMASimulator
from repro.core.rngs import entropy_u64, strategy_seed
from repro.engine import HostBackend
from repro.engine.engine import FLEngine

SLOT = reference.SLOT_S
BENCH = Path(__file__).resolve().parent
#: a next-token cell with a frozen part, made of data files, a reference
#: and a program builder alone, outside BENCHMARK.json
FIXTURES = BENCH / "fixtures"
CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 31 + 12345

#: winner-sparse rounds on stale priorities with device contention, the
#: traffic of a cross-device population, on the MLP
SPARSE = {"users": 400, "examples_per_user": 32, "partition": "iid",
          "k": 16, "cw_base": 400, "contention_backend": "device",
          "round_mode": "sparse", "sparse_priority": "stale",
          "rounds_per_job": 4}

#: what the harness read for the two paper cells at ``SEED`` on the CPU
#: while it knew only classifiers: sha256 of the data and of the initial
#: weights, and the six numbers of the comparison
READ_BEFORE = {
    "paper-mlp": {
        "data": "a2db535f6326268917eb32837f4532c54bb78405d9df5afd932d454510d39fbe",
        "init": "933ddea7767db69ec5fe007b617844fa21a4596dabb45a18fe786b3d68c834c4",
        "checks": {"selection_faults": 0.0, "loss_gap": 0.0, "prio_gap": 0.0,
                   "acc_gap": 0.0, "update_gap": 1.7556579588817257e-08,
                   "change_gap": 1.3444869692956331e-08}},
    "paper-cnn": {
        "data": "be43ff3f264d91dc02d6d46519e625c350ee6813348ae22b9f3f4a6063f84196",
        "init": "d1b5ee369ad1c9ab31af9f57298d61eac05bd3a6c78c257dfbd730a617191c39",
        "checks": {"selection_faults": 0.0, "loss_gap": 6.931729768238498e-08,
                   "prio_gap": 0.0, "acc_gap": 0.0,
                   "update_gap": 4.643614855645681e-08,
                   "change_gap": 6.36068002968588e-08}},
}


def tiny_cell(name, root=BENCH):
    """The cell with the ``tiny`` blocks of its traffic and configuration
    files applied: fewer users and examples, and for the CNN narrower
    channels on 8x8 images (widths are the configuration's own on the
    chip)."""
    cell = run.load_cell(name, root)
    cell.traffic = {**cell.traffic, **cell.traffic.get("tiny", {})}
    cell.config = {**cell.config, **cell.config.get("tiny", {})}
    return cell


def run_tiny(name, seed=SEED, root=BENCH):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", "0.01"])
    return run.run(args, cell=tiny_cell(name, root), chip=False)


@functools.cache
def sound_run(name, root=BENCH):
    """``run_tiny`` of an unbroken program, once a process."""
    return run_tiny(name, root=root)


def digest(tree) -> str:
    h = hashlib.sha256()
    for a in jax.tree.leaves(tree):
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _contention_inputs(rng, n, collide):
    windows = 50.0 / rng.uniform(1.0, 1.2, n)
    backoff = rng.uniform(0.0, 1.0, n) * windows
    if collide:
        backoff = np.round(backoff / 4) * 4       # many equal slots
    part = rng.uniform(size=n) < 0.9
    return backoff * SLOT, windows * SLOT, part


@pytest.mark.parametrize("collide", [False, True])
def test_numpy_contention_matches_program(collide):
    rng = np.random.default_rng(7)
    for seed in range(5):
        b, w, part = _contention_inputs(rng, 40, collide)
        sim = CSMASimulator(CSMAConfig(), seed=0)
        mine = np.random.default_rng(seed)
        theirs = np.random.default_rng(seed)
        got = sim.contend_batch(b[None], w[None], np.array([6]), part[None],
                                rngs=[theirs]).round_result(0).winners
        assert reference.csma_numpy(b, w, 6, part, mine) == got


@pytest.mark.parametrize("n,k", [(40, 6), (600, 16)])
def test_device_contention_stream_matches_program(n, k):
    rng = np.random.default_rng(11)
    for seed in range(3):
        b, w, part = _contention_inputs(rng, n, collide=True)
        ss = strategy_seed(seed)
        sim = CSMASimulator(CSMAConfig(), seed=ss, backend="device")
        for call in range(2):
            got = sim.contend_batch(b[None], w[None], np.array([k]),
                                    part[None]).round_result(0).winners
            want = reference.csma_device_stream(
                b, w, k, part, entropy_u64(ss), call)
            assert want == got
        assert reference.contention_entropy(seed) == entropy_u64(ss)


@pytest.mark.parametrize("name,root", [
    pytest.param(c, r, id=c)
    for c, r in [(c, BENCH) for c in CELLS] + [("tiny-lm", FIXTURES)]])
def test_reference_matches_engine(name, root):
    res = sound_run(name, root)
    assert res["correct"], res["checks"]
    assert res["checks"]["selection_faults"]["value"] == 0
    assert res["checks"]["rounds_not_compared"] == {"value": 0, "limit": 0}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", sorted(READ_BEFORE))
def test_paper_cells_read_as_before(name):
    """The same seed draws the same data and weights and reads the same
    six numbers as under the harness that knew only classifiers."""
    want = READ_BEFORE[name]
    users, test, init = run.make_inputs(tiny_cell(name), SEED)
    assert digest((users, test)) == want["data"]
    assert digest(jax.device_get(init)) == want["init"]
    checks = sound_run(name)["checks"]
    got = {k: checks[k]["value"] for k in compare.CHECKS}
    assert got == want["checks"]


def test_reference_matches_first_sparse_job():
    """The reference's stale winner-sparse rounds and device-contention
    stream against the first job of a fresh engine (a later job starts
    from the priorities the one before left, which the reference does
    not model)."""
    cell = tiny_cell("paper-mlp")
    cell.traffic = {**cell.traffic, **SPARSE}
    cell.config = {**cell.config, "n_test": 200}
    seed = 2 ** 31 + 777
    users, test, init = run.make_inputs(cell, seed)
    engine, spec, recorder, trained = run.build_engine(
        cell, users, test, init, jax.devices()[:1])
    recorder.capture_left = run.CHECKED_ROUNDS
    job = run.job_seed(seed, 1)
    res, _, _ = run.run_job(
        engine, replace(spec, seed=job, rounds=run.CHECKED_ROUNDS),
        recorder)
    h = res.histories[0]
    init_host = jax.device_get(init)
    prog = reference.Trajectory(
        winners=[list(w) for w in h.winners], losses=list(h.train_loss),
        priorities=[np.asarray(p, np.float64) for p in h.priorities],
        accuracy=list(h.accuracy),
        globals=[jax.device_get(trained)] + recorder.captured)
    ref = reference.run_job(cell.model, cell.config, init_host, users, test,
                            cell.params, job, run.CHECKED_ROUNDS,
                            probe=prog)
    out = compare.compare(prog, ref)
    assert out["checks"]["selection_faults"] == 0
    assert out["rounds_compared"] == run.CHECKED_ROUNDS
    assert all(out["checks"][k] < 1e-3 for k in
               ("loss_gap", "prio_gap", "update_gap", "change_gap")), out


def _carried_state(monkeypatch):
    """Each job starts from the global model the job before ended on."""
    orig = FLEngine.run_sweep

    def run_sweep(self, *a, **k):
        res = orig(self, *a, **k)
        self._init_params = jax.tree.map(lambda x: x[0], res.final_globals)
        return res
    monkeypatch.setattr(FLEngine, "run_sweep", run_sweep)


def _zero_weight_merge(monkeypatch):
    orig = HostBackend.sweep_merge

    def merge(self, st, tr, idx, w, *a, **k):
        return orig(self, st, tr, idx, np.zeros_like(w), *a, **k)
    monkeypatch.setattr(HostBackend, "sweep_merge", merge)


def _half_batch(monkeypatch):
    import jax
    orig = HostBackend.sweep_batches

    def batches(self, st):
        b = orig(self, st)
        return jax.tree.map(lambda a: a[:, :, :, :a.shape[3] // 2], b)
    monkeypatch.setattr(HostBackend, "sweep_batches", batches)


def _altered_winner(monkeypatch):
    orig = FLEngine._select_lanes

    def select(self, lanes, counters, prios64, t):
        winners, sels = orig(self, lanes, counters, prios64, t)
        w = winners[0]
        if w:
            w[-1] = next(u for u in range(self.num_users) if u not in w)
        return winners, sels
    monkeypatch.setattr(FLEngine, "_select_lanes", select)


def _exchange_left_out(monkeypatch, chips=4):
    """Each chip merges only the winners it holds: the merge of the
    first chip's share of the cohort (users ``[0, U / chips)``), its
    weights renormalised, stands for the cross-chip reduction."""
    orig = HostBackend.sweep_merge

    def merge(self, st, tr, idx, w, *a, **k):
        w = np.where(np.asarray(idx) < self.num_users // chips, w, 0.0)
        s = w.sum(axis=1, keepdims=True)
        w = (w / np.where(s > 0, s, 1.0)).astype(np.float32)
        return orig(self, st, tr, idx, w, *a, **k)
    monkeypatch.setattr(HostBackend, "sweep_merge", merge)


def _frozen_part_trained(monkeypatch):
    """The program trains, prioritises and merges the embedding that the
    configuration freezes."""
    from bench.fixtures import tiny_lm_program as prog
    import jax.numpy as jnp

    def build(cfg, weights, test):
        _, _, eval_fn, extra = orig(cfg, weights, test)

        def loss_fn(params, batch):
            return jnp.mean(prog._losses(params["embed"], params,
                                         batch["tokens"]))
        return weights, loss_fn, lambda p: eval_fn({"out": p["out"]}), extra
    orig = prog.build
    monkeypatch.setattr(prog, "build", build)


@pytest.mark.parametrize("fault", [_zero_weight_merge, _half_batch,
                                   _altered_winner, _carried_state])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny("paper-mlp")
    assert not res["correct"], json.dumps(res["checks"])


@pytest.mark.parametrize("name,root,fault", [
    ("cohort4-cnn", BENCH, _zero_weight_merge),
    ("cohort4-cnn", BENCH, _half_batch),
    ("cohort4-cnn", BENCH, _exchange_left_out),
    ("cohort4-cnn", BENCH, _altered_winner),
    ("tiny-lm", FIXTURES, _zero_weight_merge),
    ("tiny-lm", FIXTURES, _half_batch),
    ("tiny-lm", FIXTURES, _frozen_part_trained),
], ids=lambda v: getattr(v, "__name__", v if isinstance(v, str) else ""))
def test_broken_cell_is_not_correct(name, root, fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(name, root=root)
    assert not res["correct"], json.dumps(res["checks"])


def test_run_that_compares_fewer_rounds_is_not_correct(monkeypatch):
    """A run whose comparison reads fewer global models than it checks
    is not correct, whatever its numbers read."""
    orig = compare.compare

    def fewer(prog, ref):
        return {**orig(prog, ref), "rounds_compared": 1}
    monkeypatch.setattr(compare, "compare", fewer)
    res = run_tiny("paper-mlp")
    assert not res["correct"]
    assert res["checks"]["rounds_not_compared"] == {
        "value": run.CHECKED_ROUNDS - 1, "limit": 0}
    assert compare.judge({k: v["value"] for k, v in res["checks"].items()
                          if k in compare.CHECKS},
                         run.load_cell("paper-mlp").workload["limits"])


def test_exchange_fault_fails_the_cell():
    """The reference with the exchange between chips left out (Eq. 1
    over the first chip's share of the users, renormalised), in the
    program's place at the tiny sizes, fails the cell's limits, and
    does so as a merge that moved: no round keeps the global model as
    it was."""
    cell = tiny_cell("cohort4-cnn")
    out = control.control_checks(cell, 99, "exchange")
    assert not compare.judge(out["checks"], cell.workload["limits"]), out
    assert out["rounds_compared"] == run.CHECKED_ROUNDS
    assert out["checks"]["update_gap"] not in (0.0, 1.0), out
    users, test, init = run.make_inputs(cell, 99)
    traj = reference.run_job(cell.model, cell.config, jax.device_get(init),
                             users, test, cell.params, run.job_seed(99, 1),
                             run.CHECKED_ROUNDS, fault="exchange", shards=4)
    for a, b in zip(traj.globals, traj.globals[1:]):
        assert not np.array_equal(a["fc"]["w"], b["fc"]["w"])
    with pytest.raises(ValueError):
        reference.run_job(cell.model, cell.config, jax.device_get(init),
                          users, test, cell.params, 1, 1, fault="exchange")


def test_lower_precision_control_is_not_correct():
    cell = tiny_cell("paper-mlp")
    out = control.control_checks(cell, 99)
    assert not compare.judge(out["checks"], cell.workload["limits"]), out


@pytest.mark.parametrize("name", [c for c in CELLS if c != "paper-mlp"])
def test_lower_precision_control_fails_the_cell(name):
    """The bfloat16 control in the program's place, at the cell's tiny
    sizes, fails one of the cell's limits."""
    cell = tiny_cell(name)
    out = control.control_checks(cell, 99)
    assert not compare.judge(out["checks"], cell.workload["limits"]), out


def test_reference_follows_a_slot_boundary_flip():
    """Fed a probe whose priorities the reference's selection turns into
    the probe's own winners, the reference selects as the probe did and
    merges those users' models; a probe whose winners its priorities do
    not give is not followed."""
    cell = tiny_cell("paper-mlp")
    users, test, init = run.make_inputs(cell, SEED)
    args = (cell.model, cell.config, jax.device_get(init), users, test,
            cell.params, 7, 2)
    own = reference.run_job(*args)
    prios = own.priorities[0].copy()
    prios[next(u for u in range(len(users))
               if u not in own.winners[0])] = 1e3
    flipped = reference.Selector(7, len(users), cell.params).peek(prios)
    assert flipped != own.winners[0]

    def probe(winners):
        return reference.Trajectory(winners=[winners],
                                    priorities=[prios])
    followed = reference.run_job(*args, probe=probe(flipped))
    assert followed.winners[0] == followed.probe_winners[0] == flipped
    assert followed.priorities[0].tolist() == own.priorities[0].tolist()
    assert not np.allclose(followed.globals[1]["fc1"]["w"],
                           own.globals[1]["fc1"]["w"])
    kept = reference.run_job(*args, probe=probe(flipped[::-1]))
    assert kept.winners == own.winners
    assert compare.compare(probe(flipped[::-1]), kept)["checks"][
        "selection_faults"] == 1


def _traj(winners, probe=()):
    g = [{"w": np.full(3, float(t))} for t in range(len(winners) + 1)]
    return reference.Trajectory(
        winners=winners, losses=[1.0] * len(winners),
        priorities=[np.full(4, 1.1)] * len(winners),
        accuracy=[0.5] * len(winners), globals=g,
        probe_winners=list(probe))


def test_slot_boundary_flip_stops_the_comparison():
    ref = _traj([[0, 1], [2, 3], [1, 2]], probe=[[0, 1], [3, 2], [1, 2]])
    prog = _traj([[0, 1], [3, 2], [0, 3]])
    out = compare.compare(prog, ref)
    assert out["checks"]["selection_faults"] == 0
    assert out["rounds_compared"] == 1


def test_unexplained_winners_are_a_selection_fault():
    ref = _traj([[0, 1], [2, 3]], probe=[[0, 1], [2, 3]])
    prog = _traj([[0, 1], [3, 2]])
    out = compare.compare(prog, ref)
    assert out["checks"]["selection_faults"] == 1
    limits = dict.fromkeys(compare.CHECKS, 1.0)
    limits["selection_faults"] = 0
    assert not compare.judge(out["checks"], limits)
    same = compare.compare(ref, ref)["checks"]
    assert compare.judge(same, {**limits, "acc_gap": None})
