"""Readings that the limits of ``correct`` are set from, at a cell's own
size on the chip (the benchmark's runs do not run this).

    python3 bench/control.py --workload paper-mlp --seeds 1,2,3

For each seed it draws the cell's data and weights as a run does, runs
the plain reference's first rounds in float32 (matmuls at "highest")
and the control, the same rounds in bfloat16 (the nearest lower
precision), or with ``--fault`` the float32 rounds with a fault
planted, and prints their numbers under the comparison of
``bench/compare.py``: the upper readings. The lower readings are the
numbers that the benchmark's own runs print.
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


def control_checks(cell, seed: int, fault=None) -> dict:
    """The comparison's numbers for the control of ``cell`` at ``seed``:
    the reference in bfloat16 in the program's place, or, with
    ``fault``, the float32 reference with that fault planted
    (``reference.run_job``)."""
    import jax
    import jax.numpy as jnp
    users, test, init = run.make_inputs(cell, seed)
    init = jax.device_get(init)
    tr = cell.params
    job = run.job_seed(seed, 1)
    rounds = run.CHECKED_ROUNDS
    ctl = reference.run_job(cell.model.apply, init, users, test, tr, job,
                            rounds, fault=fault,
                            dtype=jnp.float32 if fault else jnp.bfloat16)
    ref = reference.run_job(cell.model.apply, init, users, test, tr, job,
                            rounds, probe=ctl)
    return compare.compare(ctl, ref)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--fault", choices=("unchanged", "half"), default=None,
                    help="float32 with this fault planted, in place of "
                         "the lower precision")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.enable_cache()
    run.require_devices(int(cell.workload["chips"]))
    for s in args.seeds.split(","):
        dtype = "float32" if args.fault else "bfloat16"
        out = control_checks(cell, int(s), args.fault)
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          "dtype": dtype, "fault": args.fault, **out}),
              flush=True)


if __name__ == "__main__":
    main()
