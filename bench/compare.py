"""The comparison that decides a run's ``correct``: the first rounds of a
job as the program ran them against the plain reference
(``reference.run_job``), both from the same weights, data and seed.

Numbers, each held to the limit of its cell (``limits`` in the cell's
file):

- ``selection_faults``: rounds in which the program's winners are
  neither the reference's nor what the reference's selection gives for
  the program's own priorities. Exact: limit 0.
- ``loss_gap``: worst relative gap of a round's mean training loss.
- ``prio_gap``: worst gap of a user's Eq. 2 priority, taken on
  ``priority - 1`` against the larger of the reference's value and its
  median over the users that moved.
- ``acc_gap``: worst absolute gap of the merged model's eval value:
  accuracy for a ``classify`` configuration, held-out mean next-token
  loss for a ``next_token`` one.
- ``update_gap``: the first round's Eq. 1 update ``g1 - g0`` of the
  trained part, by the worst leaf: the gap between the two norms of
  that leaf, against the larger of the reference's norm of the leaf and
  of the median leaf. Globals whose leaves differ from the reference's
  (a frozen part trained, a leaf left out) cannot be compared: inf.
- ``change_gap``: the same for the change ``gT - g0`` over the rounds
  compared.

Where a winner list differs only because a backoff lies on a slot
boundary (the reference's selection, fed the program's priorities,
gives the program's winners), the reference, run with the program's
trajectory as its probe, selects as the program did and goes on
(``reference.run_job``). A pair of trajectories that still differs
there stops at that round: the rounds after start from other models and
are not compared; the numbers of the rounds before stand.
"""
from __future__ import annotations

import math

import jax
import numpy as np

CHECKS = ("selection_faults", "loss_gap", "prio_gap", "acc_gap",
          "update_gap", "change_gap")


def _worst(values, default=math.inf) -> float:
    """Largest value; NaN wins, so that a NaN fails its limit."""
    return float(np.max(values)) if len(values) else default


def _leaves(tree):
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _shapes(tree):
    return (jax.tree.structure(tree),
            [np.shape(x) for x in jax.tree.leaves(tree)])


def norm_gap(prog_a, prog_b, ref_a, ref_b) -> float:
    """Worst leaf gap of ``|b - a|`` between program and reference; inf
    where the program's leaves are not the reference's."""
    if not (_shapes(prog_a) == _shapes(prog_b) == _shapes(ref_a)):
        return math.inf
    pn = [np.linalg.norm(b - a) for a, b in zip(_leaves(prog_a),
                                                _leaves(prog_b))]
    rn = [np.linalg.norm(b - a) for a, b in zip(_leaves(ref_a),
                                                _leaves(ref_b))]
    med = float(np.median(rn))
    return _worst([abs(p - r) / max(r, med, 1e-30) for p, r in zip(pn, rn)])


def _prio_gap(p, r) -> float:
    p = np.asarray(p, np.float64) - 1.0
    r = np.asarray(r, np.float64) - 1.0
    moved = r[r > 0]
    if not moved.size:
        return float(np.max(np.abs(p - r)))
    den = np.maximum(r, np.median(moved))
    return float(np.max(np.abs(p - r) / den))


def compare(prog, ref) -> dict:
    """``{"checks": {name: value}, "rounds_compared": n,
    "rounds_followed": m}``, ``m`` the rounds in which the reference
    selected on the program's priorities; inf marks a number that could
    not be read (a missing round), NaN one that the program made NaN."""
    rounds = len(ref.winners)
    faults, stop = 0, rounds
    for t in range(rounds):
        if t >= len(prog.winners):
            faults += 1
            stop = t
            break
        if list(prog.winners[t]) == list(ref.winners[t]):
            continue
        probe = ref.probe_winners[t] if t < len(ref.probe_winners) else None
        if probe != list(prog.winners[t]):
            faults += 1
        stop = t
        break
    pre = min(stop + 1, rounds, len(prog.losses), len(prog.priorities))
    loss_gap = _worst([abs(prog.losses[t] - ref.losses[t])
                       / max(abs(ref.losses[t]), 1e-30)
                       for t in range(pre)])
    prio_gap = _worst([_prio_gap(prog.priorities[t], ref.priorities[t])
                       for t in range(pre)])
    post = min(stop, len(prog.accuracy), len(prog.globals) - 1)
    acc_gap = _worst([abs(prog.accuracy[t] - ref.accuracy[t])
                      for t in range(post)], 0.0)
    if post >= 1:
        update_gap = norm_gap(prog.globals[0], prog.globals[1],
                              ref.globals[0], ref.globals[1])
        change_gap = norm_gap(prog.globals[0], prog.globals[post],
                              ref.globals[0], ref.globals[post])
    else:
        update_gap = change_gap = 0.0
    values = (faults, loss_gap, prio_gap, acc_gap, update_gap, change_gap)
    checks = {k: float(v) for k, v in zip(CHECKS, values)}
    return {"checks": checks, "rounds_compared": post,
            "rounds_followed": ref.followed}


def judge(checks: dict, limits: dict) -> bool:
    """Every number at or under its limit (NaN fails); a limit of None
    marks a number the cell does not compare."""
    return all(checks[k] <= limits[k] for k in CHECKS
               if limits[k] is not None)
