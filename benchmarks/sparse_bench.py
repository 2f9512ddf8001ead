"""Winner-sparse round scaling (DESIGN.md §9): rounds/sec and peak RSS
of the contention-first gather-K path (``round_mode="sparse"``, stale
priorities) vs the dense fused path over 1e3–1e6 users at K=64.

The dense path trains the FULL cohort every round just to pick K
winners; the sparse path runs contention over the full population
first, then trains ONLY the K winners in a compact (K, ...) program —
per-round train FLOPs and working set scale with K instead of U. The
acceptance bar (ISSUE 8): ≥5x rounds/sec AND lower peak memory at
U=1e5, K=64 on CPU.

Each (users, mode) cell runs in a SUBPROCESS so ``ru_maxrss`` reports
an honest per-config peak (a shared process would carry the largest
cell's high-water mark into every later reading). A child cannot take
an accelerator that the parent holds, so ``run`` refuses to start when
this process has already initialised a non-CPU backend. Contention itself is
the device engine (``contention_backend="device"``) for both modes —
the 1e5+ regimes are exactly what it exists for, and it cancels out of
the mode comparison. Timed rounds exclude the first (compile) round.

Writes ``BENCH_sparse.json`` at the repo root (CI uploads it).

  PYTHONPATH=src python -m benchmarks.run sparse              # full
  BENCH_SPARSE_SMOKE=1 ... python -m benchmarks.run sparse    # CI smoke
  python -m benchmarks.sparse_bench --smoke                   # ditto

Smoke runs write ``BENCH_sparse.smoke.json`` instead, so the
checked-in full-grid artifact can't be clobbered under its own name.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROUNDS = int(os.environ.get("BENCH_SPARSE_ROUNDS", "4"))
K_WINNERS = int(os.environ.get("BENCH_SPARSE_K", "64"))
SMOKE = (os.environ.get("BENCH_SPARSE_SMOKE") == "1"
         or "--smoke" in sys.argv)

# per-user data shape: small enough that the 1e6-user stacked dataset
# (~1 GB f32) still fits a CI host, big enough that full-cohort
# training dominates the dense round
N_PER_USER, DIM, CLASSES, BATCH = 8, 32, 4, 8

#: (users, modes) cells; the dense comparator stops at 1e5 (its 1e6
#: round would take minutes for a number the trend already gives) and
#: 1e6 demonstrates the sparse path alone
FULL_GRID = ((1_000, ("fused", "sparse")),
             (10_000, ("fused", "sparse")),
             (100_000, ("fused", "sparse")),
             (1_000_000, ("sparse",)))
SMOKE_GRID = ((2_000, ("fused", "sparse")),)

_JSON_PATH = os.path.join(
    os.path.dirname(__file__), "..",
    "BENCH_sparse.smoke.json" if SMOKE else "BENCH_sparse.json")


def _child(users: int, mode: str) -> None:
    """One (users, mode) cell: build, warm up one round, time the
    rest, report rounds/sec + this process's peak RSS as JSON."""
    import resource

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.engine import (ExperimentSpec, FLHistory,
                              build_host_engine)

    rng = np.random.default_rng(3)
    w_true = rng.normal(size=(DIM, CLASSES)).astype(np.float32)
    # one vectorized draw (a 1e6-iteration python loop would dominate
    # setup); per-user dicts hold views into the big arrays
    xs = rng.normal(size=(users, N_PER_USER, DIM)).astype(np.float32)
    ys = np.argmax(
        xs @ w_true + rng.normal(size=(users, N_PER_USER, CLASSES)),
        axis=-1).astype(np.int64)
    user_data = [{"x": xs[u], "y": ys[u]} for u in range(users)]

    def loss_fn(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        oh = jax.nn.one_hot(batch["y"], CLASSES)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    params = {"w": jnp.zeros((DIM, CLASSES), jnp.float32),
              "b": jnp.zeros((CLASSES,), jnp.float32)}
    # the paper's FIXED cw_base starves 1e5+ contenders; scale it so
    # rounds finish, identically for both modes
    spec = ExperimentSpec(
        rounds=ROUNDS + 1, k_per_round=K_WINNERS, batch_size=BATCH,
        strategy="priority-distributed", cw_base=float(max(2048, users)),
        contention_backend="device", round_mode=mode,
        sparse_priority="stale", seed=0)
    engine = build_host_engine(spec, params, loss_fn, user_data)

    hist = FLHistory(selections=np.zeros(users, np.int64))
    engine.run_round(0, hist)                      # compile + warmup
    jax.block_until_ready(engine.global_params)
    t0 = time.time()
    for t in range(1, ROUNDS + 1):
        engine.run_round(t, hist)
    jax.block_until_ready(engine.global_params)
    wall = time.time() - t0

    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    json.dump({"users": users, "mode": mode,
               "rounds_per_sec": round(ROUNDS / wall, 3),
               "us_per_round": round(wall / ROUNDS * 1e6, 1),
               "peak_rss_mb": round(peak_kb / 1024.0, 1),
               "mean_winners": round(float(np.mean(
                   [len(w) for w in hist.winners])), 2)},
              sys.stdout)


def _assert_chip_free() -> None:
    """Each cell runs in a child process, and a chip belongs to one
    process at a time: a child that needs the accelerator fails or
    hangs while this process holds it. Fail here instead."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "the sparse bench runs each cell in a child process, but "
                f"this process already holds the {jax.default_backend()} "
                "backend; run `python -m benchmarks.run sparse` on its own")


def run():
    _assert_chip_free()
    lines = []
    grid = SMOKE_GRID if SMOKE else FULL_GRID
    results = []
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    for users, modes in grid:
        for mode in modes:
            out = subprocess.run(
                [sys.executable, "-m", "benchmarks.sparse_bench",
                 "--cell", str(users), mode],
                capture_output=True, text=True, env=env,
                cwd=os.path.join(os.path.dirname(__file__), ".."))
            if out.returncode != 0:
                raise RuntimeError(
                    f"sparse bench cell ({users}, {mode}) failed:\n"
                    + out.stderr[-2000:])
            cell = json.loads(out.stdout)
            results.append(cell)
            lines.append(
                f"sparse/{mode}/u{users},{cell['us_per_round']:.0f},"
                f"rps={cell['rounds_per_sec']};"
                f"rss_mb={cell['peak_rss_mb']}")

    # headline: the ISSUE-8 acceptance ratio at the largest shared U
    shared = sorted({u for u, m in grid if len(m) > 1})
    if shared:
        u = shared[-1]
        dense = next(c for c in results
                     if c["users"] == u and c["mode"] == "fused")
        sp = next(c for c in results
                  if c["users"] == u and c["mode"] == "sparse")
        speed = sp["rounds_per_sec"] / max(dense["rounds_per_sec"], 1e-9)
        lines.append(
            f"sparse/speedup_u{u},0,x{speed:.1f};"
            f"rss_dense={dense['peak_rss_mb']};"
            f"rss_sparse={sp['peak_rss_mb']}")

    report = {
        "config": {"rounds": ROUNDS, "k_winners": K_WINNERS,
                   "n_per_user": N_PER_USER, "dim": DIM,
                   "batch_size": BATCH, "smoke": SMOKE,
                   "strategy": "priority-distributed",
                   "sparse_priority": "stale",
                   "contention_backend": "device"},
        "results": results,
    }
    with open(_JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    lines.append(f"sparse/json,0,wrote={os.path.abspath(_JSON_PATH)}")
    return lines


if __name__ == "__main__":
    if "--cell" in sys.argv:
        i = sys.argv.index("--cell")
        _child(int(sys.argv[i + 1]), sys.argv[i + 2])
    else:
        for line in run():
            print(line)
