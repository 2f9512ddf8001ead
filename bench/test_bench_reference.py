"""The plain reference agrees with the program at tiny sizes on the CPU,
and the comparison tells a broken timed path from a sound one."""
import json
from dataclasses import replace

import jax
import numpy as np
import pytest

from bench import compare, control, reference, run
from repro.core.csma import CSMAConfig, CSMASimulator
from repro.core.rngs import entropy_u64, strategy_seed
from repro.engine import HostBackend
from repro.engine.engine import FLEngine

SLOT = reference.SLOT_S

#: tiny stand-ins of each cell: fewer users and examples, and for the
#: CNN narrower channels on 8x8 images (widths are the configuration's
#: own on the chip)
TINY = {
    "paper-mlp": ({"users": 4, "rounds_per_job": 3},
                  {"n_train": 800, "n_test": 200}),
    "paper-cnn": ({"users": 3, "rounds_per_job": 3, "k": 1,
                   "batch_size": 8},
                  {"n_train": 48, "n_test": 32, "conv_channels": [4, 8],
                   "input_shape": [8, 8, 3]}),
}

#: winner-sparse rounds on stale priorities with device contention, the
#: traffic of a cross-device population, on the MLP
SPARSE = {"users": 400, "examples_per_user": 32, "partition": "iid",
          "k": 16, "cw_base": 400, "contention_backend": "device",
          "round_mode": "sparse", "sparse_priority": "stale",
          "rounds_per_job": 4}


def tiny_cell(name):
    cell = run.load_cell(name)
    tr, cfg = TINY[name]
    cell.traffic = {**cell.traffic, **tr}
    cell.config = {**cell.config, **cfg}
    return cell


def run_tiny(name, seed=2 ** 31 + 12345):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", "0.01"])
    return run.run(args, cell=tiny_cell(name), chip=False)


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


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_matches_engine(name):
    res = run_tiny(name)
    assert res["correct"], res["checks"]
    assert res["checks"]["selection_faults"]["value"] == 0
    assert list(res)[-1] == "checks"


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
    engine, spec, recorder = run.build_engine(cell, users, test, init,
                                              jax.devices()[:1])
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
        accuracy=list(h.accuracy), globals=[init_host] + recorder.captured)
    ref = reference.run_job(cell.model.apply, init_host, users, test,
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


@pytest.mark.parametrize("fault", [_zero_weight_merge, _half_batch,
                                   _altered_winner, _carried_state])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny("paper-mlp")
    assert not res["correct"], json.dumps(res["checks"])


def test_lower_precision_control_is_not_correct():
    cell = tiny_cell("paper-mlp")
    out = control.control_checks(cell, 99)
    assert not compare.judge(out["checks"], cell.workload["limits"]), out


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
