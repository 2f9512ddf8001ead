"""Readings that the limits of ``correct`` are set from, at a cell's own
size on the chip (the benchmark's runs do not run this).

    python3 bench/control.py --workload paper-mlp --seeds 1,2,3 \
        --faults none,unchanged,half

    python3 bench/control.py --workload cohort4-cnn --seeds 1,2,3 \
        --faults none,exchange

For each seed it draws the cell's data and weights as a run does, runs
the plain reference's first rounds in float32 (matmuls at "highest")
and the control, the same rounds in bfloat16 (the nearest lower
precision; fault ``none``), or for each other fault named the float32
rounds with that fault planted (``exchange`` only where the cell splits
its users over several chips), and prints their numbers under the
comparison of ``bench/compare.py``: the upper readings. The lower
readings are the numbers that the benchmark's own runs print. It runs
on one chip, whatever the cell's program spreads over.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import compare, reference, run  # noqa: E402


def control_checks(cell, seed: int, fault=None, inputs=None) -> dict:
    """The comparison's numbers for the control of ``cell`` at ``seed``:
    the reference in bfloat16 in the program's place, or, with
    ``fault``, the float32 reference with that fault planted
    (``reference.run_job``). ``inputs``: the seed's ``run.make_inputs``,
    where already drawn."""
    import jax
    import jax.numpy as jnp
    users, test, init = inputs or run.make_inputs(cell, seed)
    init = jax.device_get(init)
    tr = cell.params
    job = run.job_seed(seed, 1)
    rounds = run.CHECKED_ROUNDS
    ctl = reference.run_job(cell.model, cell.config, init, users, test, tr,
                            job, rounds, fault=fault,
                            shards=int(cell.workload["chips"]),
                            dtype=jnp.float32 if fault else jnp.bfloat16)
    ref = reference.run_job(cell.model, cell.config, init, users, test, tr,
                            job, rounds, probe=ctl)
    return compare.compare(ctl, ref)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--faults", default="none",
                    help="comma-separated, of none (the bfloat16 "
                         "control), unchanged, half and exchange "
                         "(float32 with that fault planted)")
    args = ap.parse_args(argv)
    faults = [None if f == "none" else f for f in args.faults.split(",")]
    if not set(faults) <= {None, "unchanged", "half", "exchange"}:
        ap.error(f"unknown fault in {args.faults!r}")
    cell = run.load_cell(args.workload)
    if "exchange" in faults and int(cell.workload["chips"]) < 2:
        ap.error(f"{args.workload} keeps its users on one chip: no "
                 "exchange to leave out")
    run.enable_cache()
    run.require_devices(1)
    for s in args.seeds.split(","):
        inputs = run.make_inputs(cell, int(s))
        for fault in faults:
            out = control_checks(cell, int(s), fault, inputs)
            print(json.dumps({"workload": args.workload, "seed": int(s),
                              "dtype": "float32" if fault else "bfloat16",
                              "fault": fault, **out}), flush=True)


if __name__ == "__main__":
    main()
