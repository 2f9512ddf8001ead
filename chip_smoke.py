"""Run the FL round on a TPU and check what it computes.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cohort-sharded round, four chips

On one chip, four phases run in this one process:

* ``device``: the default device is a TPU, and ``kernels.ops``
  dispatches to the compiled Pallas kernels (no interpret mode).
* ``paper``: the paper cell through ``repro.launch.train``'s own
  builder (MLP 784-200-10 on the seeded synthetic Fashion set, U=10,
  K=2, ``priority-distributed`` with the fairness counter) runs
  ``FLEngine.run``, then a 4-seed ``SweepSpec.grid`` through
  ``run_sweep``. Losses must be finite and accuracy above chance. The
  Eq. 2 (``delta_norm``) and Eq. 1 (``gather_combine``) kernels are
  checked against their jnp oracles on a trained cohort stack.
* ``contention``: B=4 lanes of N=1e5 contenders through
  ``CSMASimulator(backend="device")``; the Pallas event path and the jnp
  oracle path must pick identical winners.
* ``sparse``: the winner-sparse round (``round_mode="sparse"``) at
  U=1e4, K=64 for two rounds.

``--chips 4`` runs only the cohort-sharded fused round (U=1000, K=64)
over four chips and the same cell on one chip, and compares their loss
and accuracy trajectories.

Every check raises on failure, so any failed phase exits non-zero. The
last line of standard output, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Where JAX finds no TPU the script exits non-zero before any phase runs.
The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
#: chance accuracy of the 10-class synthetic Fashion set
CHANCE = 0.1
PAPER_ROUNDS = 10
SWEEP_SEEDS = 4
#: |kernel - oracle| bounds on the chip, both in f32. delta_norm sums
#: 1.6e5 squares in another order than XLA's reduce; gather_combine
#: adds K products in the same order as the oracle.
DELTA_NORM_RTOL = 1e-4
PRIORITY_RTOL = 1e-5
GATHER_RTOL, GATHER_ATOL = 1e-6, 1e-7
CONTENTION_LANES, CONTENDERS, CONTENTION_K = 4, 100_000, 64
SPARSE_USERS, SPARSE_K, SPARSE_ROUNDS = 10_000, 64, 2
SHARD_USERS, SHARD_K, SHARD_ROUNDS, SHARD_CHIPS = 1000, 64, 5, 4
#: sharded vs one-chip trajectories: the sharded round uses the jnp
#: reductions, so Eq. 2 priorities differ in the last bits and a
#: backoff can round to another slot. Round 0 trains every user from
#: the same init before any selection, so its loss must agree closely;
#: later rounds are held to the looser bounds.
SHARD_LOSS0_RTOL = 1e-4
SHARD_LOSS_RTOL, SHARD_ACC_ATOL = 1e-2, 0.02


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def report(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields)}", flush=True)


def device_check(chips: int) -> dict:
    import jax
    from repro.kernels import ops as kops

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"default device is {d.platform!r} ({d.device_kind}), not a TPU")
    check("REPRO_PALLAS_INTERPRET" not in os.environ,
          "REPRO_PALLAS_INTERPRET is set: kernels would run interpreted")
    check(kops.kernel_mode(True, None) == (True, False),
          "kernels.ops does not dispatch to the compiled Pallas kernels")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devs)}
    report("device", **dev)
    return dev


def check_history(name: str, hist, rounds: int) -> dict:
    losses, acc = hist.train_loss, hist.accuracy
    check(len(losses) == rounds and len(acc) == rounds,
          f"{name}: {len(losses)} losses, {len(acc)} evals "
          f"for {rounds} rounds")
    check(all(math.isfinite(x) for x in losses + acc),
          f"{name}: non-finite loss or accuracy: {losses} {acc}")
    check(max(acc) >= 2 * CHANCE,
          f"{name}: best accuracy {max(acc)} is not above chance")
    check(losses[-1] < math.log(10),
          f"{name}: final loss {losses[-1]} is not below ln(10)")
    return {"loss": losses, "accuracy": acc}


def _norms(stack, glob, uk):
    import jax
    from repro.kernels import ops as kops
    return jax.tree.map(lambda x, g: jax.vmap(
        lambda w: kops.delta_norm(w, g, use_kernel=uk))(x), stack, glob)


def _merge(stack, glob, idx, w, uk):
    import jax
    from repro.kernels import ops as kops
    return jax.tree.map(lambda x, g: kops.gather_combine(
        x, idx, w, g, use_kernel=uk), stack, glob)


def kernels_vs_oracles(engine, winners) -> dict:
    """Eq. 2 and Eq. 1 kernels against their jnp oracles on a cohort
    stack trained by the round's own device program."""
    import jax
    import numpy as np
    from repro.core.priority import stacked_model_priorities

    check(len(winners) > 0, "paper: the last round had no winner")
    be, glob = engine.backend, engine.global_params
    st = be.sweep_init(glob, [engine.spec.seed])
    tr = be.sweep_train(st, be.sweep_batches(st), True)
    trained = jax.tree.map(lambda x: x[0], tr.trained)      # (U, ...)
    leaves = lambda t: [np.asarray(x) for x in jax.tree.leaves(t)]

    norms = jax.jit(_norms, static_argnums=2)
    worst = 0.0
    for got, want in zip(leaves(norms(trained, glob, True)),
                         leaves(norms(trained, glob, False))):
        np.testing.assert_allclose(got, want, rtol=DELTA_NORM_RTOL)
        worst = max(worst, float(np.max(
            np.abs(got - want)
            / np.maximum(np.abs(want), np.finfo(np.float32).tiny))))
    np.testing.assert_allclose(
        np.asarray(tr.priorities[0]),
        np.asarray(jax.jit(stacked_model_priorities)(trained, glob)),
        rtol=PRIORITY_RTOL)

    # the last round's winners plus one zero-weight pad row, then an
    # all-zero (winnerless) weight vector that must return glob itself
    idx = np.asarray(list(winners) + [0], np.int32)
    w = np.zeros(len(idx), np.float32)
    w[:len(winners)] = 1.0 / len(winners)
    merge = jax.jit(_merge, static_argnums=4)
    got, want = (leaves(merge(trained, glob, idx, w, uk))
                 for uk in (True, False))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=GATHER_RTOL, atol=GATHER_ATOL)
    kept = leaves(merge(trained, glob, idx, np.zeros_like(w), True))
    for a, b in zip(kept, leaves(glob)):
        np.testing.assert_array_equal(a, b)
    return {"delta_norm_max_rel_err": worst,
            "gather_bitwise_equal": all(
                np.array_equal(a, b) for a, b in zip(got, want))}


def paper_phase() -> None:
    from repro.engine import SweepSpec
    from repro.launch.train import build_paper_engine, build_parser

    args = build_parser().parse_args(
        ["--rounds", str(PAPER_ROUNDS), "--seed", str(SEED)])
    engine = build_paper_engine(args)
    hist = engine.run()
    report("paper/run", **check_history("paper/run", hist, PAPER_ROUNDS))
    sweep = engine.run_sweep(SweepSpec.grid(
        engine.spec, seed=range(SEED, SEED + SWEEP_SEEDS)))
    check(len(sweep) == SWEEP_SEEDS, f"sweep ran {len(sweep)} lanes")
    for label, h in zip(sweep.labels, sweep):
        report(f"paper/sweep[{label}]",
               **check_history(f"paper/sweep[{label}]", h, PAPER_ROUNDS))
    report("paper/kernels", **kernels_vs_oracles(engine, hist.winners[-1]))


def contention_phase(lanes: int = CONTENTION_LANES,
                     contenders: int = CONTENDERS,
                     k: int = CONTENTION_K) -> None:
    import numpy as np
    from repro.core.csma import CSMAConfig, CSMASimulator
    from repro.core.rngs import entropy_u64
    from repro.kernels.contention import device_contend_batch

    cfg = CSMAConfig()
    slot_s = cfg.slot_us * 1e-6
    rng = np.random.default_rng(SEED)
    # Eq. 3 with the CW base scaled to the population (the paper's fixed
    # 2048 slots starve 1e5 contenders): W = N / priority slots
    windows = contenders / rng.uniform(1.0, 1.2, (lanes, contenders))
    backoff_s = rng.uniform(0.0, 1.0, windows.shape) * windows * slot_s
    window_s = windows * slot_s

    sim = CSMASimulator(cfg, seed=SEED, backend="device")
    got = sim.contend_batch(backoff_s, window_s, k)
    want = device_contend_batch(
        backoff_s / slot_s, window_s / slot_s, k, None,
        entropy=entropy_u64(SEED), call_index=0, tx_slots=cfg.tx_slots,
        max_backoff_doublings=cfg.max_backoff_doublings,
        max_sim_slots=cfg.max_sim_slots, use_kernel=False)
    for name in ("winners", "finish_slots", "collisions", "elapsed_slots",
                 "n_delivered"):
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)
    check(bool((got.n_delivered == k).all()),
          f"contention: deliveries {got.n_delivered.tolist()}, wanted {k}")
    check(bool((got.collisions > 0).all()),
          "contention: no collisions, so no redraw was exercised")
    report("contention", lanes=lanes, contenders=contenders, k=k,
           collisions=got.collisions.tolist(),
           elapsed_slots=got.elapsed_slots.tolist(),
           first_winners=got.winners[:, :4].tolist())


def sparse_phase(users: int = SPARSE_USERS, k: int = SPARSE_K,
                 rounds: int = SPARSE_ROUNDS) -> None:
    from repro.launch.train import build_paper_engine, build_parser

    # 32 examples a user, one batch; CW base scaled to the population
    args = build_parser().parse_args(
        ["--users", str(users), "--k", str(k), "--rounds", str(rounds),
         "--n-train", str(users * 32), "--cw-base", str(users),
         "--contention-backend", "device", "--seed", str(SEED)])
    engine = build_paper_engine(args)
    check(engine.backend.sparse_capable(),
          "sparse: the builder did not select the winner-sparse path")
    hist = engine.run()
    losses, acc = hist.train_loss, hist.accuracy
    check(len(hist.winners) == rounds, f"sparse: {len(hist.winners)} rounds")
    check(all(len(w) == k for w in hist.winners),
          f"sparse: winners per round {[len(w) for w in hist.winners]}")
    check(len(losses) == rounds and len(acc) == rounds
          and all(math.isfinite(x) for x in losses + acc),
          f"sparse: losses {losses}, accuracy {acc}")
    report("sparse", users=users, k=k, loss=losses, accuracy=acc,
           collisions=hist.collisions)


def sharded_phase(users: int = SHARD_USERS, k: int = SHARD_K,
                  rounds: int = SHARD_ROUNDS,
                  chips: int = SHARD_CHIPS) -> None:
    import jax
    from repro.engine import build_host_engine
    from repro.launch.train import build_parser, paper_cell
    from repro.sharding.cohort import cohort_mesh

    # Fashion-MNIST's own split sizes: 60 examples a user at U=1000
    args = build_parser().parse_args(
        ["--users", str(users), "--k", str(k), "--rounds", str(rounds),
         "--n-train", "60000", "--n-test", "10000", "--seed", str(SEED)])
    cell = paper_cell(args)
    mesh = cohort_mesh(jax.devices()[:chips])
    sharded = build_host_engine(*cell, round_mode="fused", mesh=mesh)
    st = sharded.backend.sweep_init(cell[1], [SEED])
    for leaf in jax.tree.leaves(st.stack):
        check(len(leaf.sharding.device_set) == chips,
              f"sharded: stack on {len(leaf.sharding.device_set)} devices")
        check(leaf.sharding.shard_shape(leaf.shape)[1] == users // chips,
              f"sharded: shard {leaf.sharding.shard_shape(leaf.shape)} "
              f"of {leaf.shape} does not split the cohort axis")
    del st
    h4 = sharded.run()
    h1 = build_host_engine(*cell, round_mode="fused").run()
    for name, h in (("sharded", h4), ("one_chip", h1)):
        check(len(h.train_loss) == rounds and all(
            math.isfinite(x) for x in h.train_loss + h.accuracy),
              f"{name}: losses {h.train_loss}, accuracy {h.accuracy}")
    l4, l1 = h4.train_loss, h1.train_loss
    check(abs(l4[0] - l1[0]) <= SHARD_LOSS0_RTOL * abs(l1[0]),
          f"round-0 loss {l4[0]} (sharded) vs {l1[0]} (one chip)")
    for t in range(rounds):
        check(abs(l4[t] - l1[t]) <= SHARD_LOSS_RTOL * abs(l1[t]),
              f"round {t} loss {l4[t]} (sharded) vs {l1[t]} (one chip)")
        check(abs(h4.accuracy[t] - h1.accuracy[t]) <= SHARD_ACC_ATOL,
              f"round {t} accuracy {h4.accuracy[t]} (sharded) vs "
              f"{h1.accuracy[t]} (one chip)")
    same = sum(a == b for a, b in zip(h4.winners, h1.winners))
    report("sharded", users=users, k=k, chips=chips,
           loss_sharded=l4, loss_one_chip=l1,
           accuracy_sharded=h4.accuracy, accuracy_one_chip=h1.accuracy,
           rounds_with_identical_winners=same,
           tolerance={"loss0_rtol": SHARD_LOSS0_RTOL,
                      "loss_rtol": SHARD_LOSS_RTOL,
                      "accuracy_atol": SHARD_ACC_ATOL})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cohort-sharded round over four "
                         "chips against the same cell on one chip")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    report("compile_cache", dir=enable_compile_cache())
    dev = device_check(args.chips)
    phases = ([sharded_phase] if args.chips == 4
              else [paper_phase, contention_phase, sparse_phase])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        # host wall clock, compilation included
        report(phase.__name__, seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
