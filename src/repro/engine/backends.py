"""Execution backends — where the round's *learning* happens.

A Backend owns model state + per-user data and exposes three moves to
the engine (DESIGN.md §2):

    init_state(init_params)          -> opaque global state
    train_round(state, t, train_ids, need_priority) -> TrainResult
    merge(state, train_result, winners)             -> new state
    global_params(state)             -> params pytree (for eval)

Two implementations:

  HostBackend  the paper's simulation. Three round paths, fastest
               applicable wins:

               fused    (default) ONE jitted, donated, device-resident
                        step per round: local_epochs folded into the
                        scanned batch axis, Eq. 2 priorities fused into
                        the same call via ``kernels.ops.delta_norm``,
                        and the merge a masked alpha-weighted reduction
                        over the full stacked cohort through
                        ``kernels.ops.fedavg_combine`` — the trained
                        stack is donated into the merge and the merged
                        stack stays device-resident for the next round
                        (no per-round broadcast rebuild). The cohort
                        axis optionally shards over a ``jax.sharding``
                        mesh (``sharding/cohort.py``; no-op on one
                        device). Requires a rectangular cohort (equal
                        per-user example counts) and a full-cohort
                        round.
               stacked  the PR-1 path: per-epoch vmap(scan) dispatch +
                        per-winner gather merge. Used for partial-cohort
                        rounds (``trains_before_selection`` strategies)
                        and kept as the benchmark baseline
                        (``benchmarks/round_bench.py``).
               ragged   per-user jitted training (the seed path), when
                        users' batch counts differ and nothing stacks.
               sparse   winner-sparse rounds (DESIGN.md §9): Eq. 2
                        priorities are produced BEFORE selection
                        (``sparse_priorities`` — an exact chunked
                        train-and-discard prepass, or cached stale
                        priorities), contention runs over the full
                        population, and only the K winners' params +
                        batches are gathered into a compact
                        (K_max, ...) fused train step
                        (``sparse_train``); the merge scatters the
                        compact deltas back into the device-resident
                        global. Per-round train FLOPs and peak memory
                        scale with K instead of U.

               All paths are draw-for-draw equivalent: epoch batching
               stays on host with each client's own rng stream, so
               fixed seeds give identical winner sequences
               (``tests/test_fused_round.py``; sparse-with-prepass vs
               fused is additionally bit-identical on merged globals —
               every Eq. 1 merge routes through ONE compact
               ``kernels.ops.gather_combine`` whose reduce sees the
               same (K, ...) gathered rows from either path,
               tests/test_sparse.py).
  SiloBackend  the cross-silo TPU path: wraps silo.make_fl_round_step,
               so each "user" is a pod-scale silo and the merge is the
               selection-gated cross-pod collective.

Contention stays on the host in both cases (physical-medium simulation,
DESIGN.md §3); backends never see the CSMA layer.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.fl_state import generator_state, restore_generator
from repro.core.client import Client, batch_epoch, sgd_epoch_scan
from repro.core.priority import model_priority, stacked_model_priorities
from repro.core.rngs import client_rng
from repro.core.server import winner_alphas
from repro.engine import spans
from repro.engine.types import TrainResult
from repro.faults.robust import robust_merge
from repro.kernels import ops as kops
from repro.objectives import build_objective_table, objective_epoch_scan
from repro.sharding.cohort import (cohort_sharding, replicated_sharding,
                                   shardable, sweep_global_sharding,
                                   sweep_sharding, sweep_shardable,
                                   winner_sharding, winner_shardable)


def label_heterogeneity(user_data: Sequence, num_classes: int = 10,
                        label_key: str = "y") -> np.ndarray:
    """Per-user total-variation distance to the population label mix.

    Returns (num_users,) scores in [0, 1]; zeros when the data carries
    no labels (token streams, unlabeled pytrees). Consumed by
    heterogeneity-aware strategies via ``SelectionContext.heterogeneity``.
    """
    labels = []
    for d in user_data:
        y = d.get(label_key) if isinstance(d, dict) else None
        if y is None:
            return np.zeros(len(user_data))
        labels.append(np.asarray(y, np.int64).ravel())
    # width follows the data when labels exceed the declared class count
    width = max(num_classes,
                1 + max((int(y.max()) for y in labels if y.size),
                        default=0))
    hists = np.stack([np.bincount(y, minlength=width).astype(np.float64)
                      for y in labels])
    rows = hists.sum(axis=1, keepdims=True)
    probs = hists / np.maximum(rows, 1.0)
    pop = hists.sum(axis=0) / max(hists.sum(), 1.0)
    tv = 0.5 * np.abs(probs - pop[None]).sum(axis=1)
    # a zero-example user has an all-zero probs row, which would score
    # TV 0.5 against any population mix — maximal apparent divergence
    # from NO evidence. Score empty users 0.0 instead.
    return np.where(rows[:, 0] > 0, tv, 0.0)


def compact_weights(k_pad: int, positions: Sequence[int],
                    sizes: Sequence[float]):
    """(idx, w) inputs of ``kernels.ops.gather_combine``: (k_pad,) int32
    row indices and (k_pad,) f32 Eq. 1 merge weights, delivery-ordered
    and zero-padded.

    The weight math mirrors ``core.server.winner_alphas`` exactly
    (float64 |D_k| normalization, then one cast), so the compact and
    dense-masked formulations feed bit-identical per-row weights. Pad
    rows carry index 0 and EXACT-zero weight — the masked reduce drops
    them, and appending exact +0.0 terms leaves an f32 sum's bits
    unchanged, so the pad width never leaks into the merged global.
    """
    idx = np.zeros(k_pad, np.int32)
    w = np.zeros(k_pad, np.float32)
    m = len(positions)
    if m:
        idx[:m] = positions
        s = np.asarray(sizes, np.float64)
        w[:m] = (s / s.sum()).astype(np.float32)
    return idx, w


#: one (8, 128) tile of the TPU's float32 vector registers. The resident
#: data stack keeps each example as whole tiles of 128 lanes, so the
#: device lays the stack out with the example index major and the round
#: program's gather moves whole tiles. Left in their own shapes, stacks
#: such as (U, n, 32, 32, 3) or (U, n, 784) are laid out with the
#: example index minor, and the gather reads them lane by lane.
TILE = (8, 128)


def data_rows(leaf):
    """A (U, n, ...) leaf of the data stack as the (U, n, R, 128) rows
    the device keeps: each example flattened, zero-padded to whole
    ``TILE``s and cut into rows of 128 lanes. A leaf of one value an
    example stays (U, n)."""
    if leaf.ndim == 2:
        return leaf
    flat = leaf.reshape(leaf.shape[:2] + (-1,))
    pad = -flat.shape[2] % math.prod(TILE)
    if pad:
        flat = np.pad(flat, ((0, 0), (0, 0), (0, pad)))
    return flat.reshape(flat.shape[:2] + (-1, TILE[1]))


def take_rows(rows, idx, shapes):
    """Examples ``idx`` of one user's ``rows`` (``data_rows`` leaves
    without the U axis), one gather per leaf, each cut back to its
    example shape in ``shapes``: a step's batch, gathered on the
    device."""
    def take(leaf, shape):
        b = jnp.take(leaf, idx, axis=0, mode="clip")
        if not shape:
            return b
        b = b.reshape(idx.shape + (-1,))[..., :math.prod(shape)]
        return b.reshape(idx.shape + shape)
    return jax.tree.map(take, rows, shapes)


def train_lanes(run, stack, data, idx, shapes, lane=(), user=()):
    """``run(params, idx, *lane, *user, take=...)`` (an epoch scan)
    vmapped over the (E, U) cohort: ``lane`` arguments carry a leading
    E axis, ``user`` arguments (E, U). ``idx`` is the (E, U, S, B) int32
    index tensor of ``HostBackend.sweep_batches``, ``data`` the resident
    stack (``data_rows``); each step gathers its batch (``take_rows``)."""
    def per_user(params, xs, rows, *args):
        return run(params, xs, *args,
                   take=lambda i: take_rows(rows, i, shapes))
    n, m = len(lane), len(user)
    users = jax.vmap(per_user, in_axes=(0, 0, 0) + (None,) * n + (0,) * m)
    return jax.vmap(users, in_axes=(0, 0, None) + (0,) * (n + m))(
        stack, idx, data, *lane, *user)


@dataclass
class SweepState:
    """Device + host state of one in-flight sweep (DESIGN.md §5).

    ``glob`` is the (E, ...) stacked per-lane globals, ``stack`` the
    (E, U, ...) cohort — both device-resident between rounds, chained
    through donation exactly like the single-experiment fused path.
    ``rngs[e][u]`` is lane e / user u's epoch-permutation stream, seeded
    from the LANE's spec seed (not the backend's), so each lane draws
    the identical batches a sequential run of that spec would.

    ``obj`` is the sweep's ``ObjectiveTable`` (None = every lane plain
    FedAvg → the pre-registry programs); ``m``/``v`` the (E, ...)
    server-opt moments and ``h`` the (E, U, ...) FedDyn state, all
    device-resident next to ``glob`` and chained through donation
    (DESIGN.md §10).
    """
    num_lanes: int
    glob: Any
    stack: Any
    rngs: List[List[np.random.Generator]]
    obj: Any = None
    m: Any = None
    v: Any = None
    h: Any = None


@dataclass
class SweepTrainResult:
    """One batched sweep training pass: device arrays, fetched lazily.

    ``losses``/``priorities`` are (E, U) device arrays — the ONLY
    values the engine syncs to host per round (the trained stack stays
    on device and is donated into the merge)."""
    trained: Any
    losses: Any
    priorities: Any


class Backend:
    """Contract only — see module docstring. Subclasses must set
    ``num_users`` and ``heterogeneity`` ((num_users,) in [0,1])."""
    num_users: int
    heterogeneity: np.ndarray

    def init_state(self, init_params):
        raise NotImplementedError

    def train_round(self, state, t: int, train_ids: List[int],
                    need_priority: bool) -> TrainResult:
        raise NotImplementedError

    def merge(self, state, train_result: TrainResult, winners: List[int],
              merge_ctx=None, fault_ctx=None, attempts=None):
        """Eq. 1 over ``winners``. ``merge_ctx`` (a
        ``repro.channel.MergeContext``) switches the digital FedAvg
        reduction to the AirComp analog superposition; ``fault_ctx`` (a
        ``repro.faults.FaultMergeContext``) routes it through the
        robust guard pass (quarantine / clip / stale groups) instead —
        backends that don't implement a context must reject it non-None.
        The two contexts are mutually exclusive (spec-validated).
        ``attempts`` is the round's ATTEMPT winner list (pre-channel
        gate) — consumed only by h-carrying objectives (DESIGN.md §10);
        backends without objective support may ignore it."""
        raise NotImplementedError

    def global_params(self, state):
        return state

    def num_examples(self, u: int) -> int:
        raise NotImplementedError

    # ---- checkpoint hooks (fault layer, DESIGN.md §8) ----------------
    def client_stream_states(self):
        """Per-client rng snapshots for checkpoint/resume, or None when
        the backend owns no client streams (SiloBackend's batches are a
        pure function of the round index)."""
        return None

    def restore_client_streams(self, states) -> None:
        if states is None:
            return
        raise NotImplementedError(
            f"{type(self).__name__} has no client streams to restore")

    # ---- sweep contract (optional; HostBackend's fused path implements
    # it, everything else reports unsupported and the engine refuses) --
    def sweep_capable(self) -> bool:
        return False

    # ---- winner-sparse contract (optional; HostBackend round_mode
    # "sparse" implements it — the engine then selects BEFORE training
    # and trains only the winners) ------------------------------------
    def sparse_capable(self) -> bool:
        return False

    def priority_cache_state(self):
        """Stale-priority cache snapshot for checkpoint/resume, or None
        when the backend keeps no such cache (everything but the sparse
        path's "stale" priority mode)."""
        return None

    def restore_priority_cache(self, state) -> None:
        if state is not None:
            raise NotImplementedError(
                f"{type(self).__name__} has no priority cache to restore")

    # ---- objectives contract (optional; HostBackend's fused / sparse
    # paths implement it — DESIGN.md §10) ------------------------------
    def objective_active(self) -> bool:
        """True when the backend was built with a non-plain objective
        (the engine refuses a non-plain spec on backends reporting
        False)."""
        return False

    def objective_needs_h(self) -> bool:
        """True when merges must run on h-carrying rounds even without
        deliveries (feddyn: attempts update h)."""
        return False

    def objective_state(self):
        """Host snapshot of the single-run objective state (server m/v,
        FedDyn h) for checkpoint/resume, or None."""
        return None

    def restore_objective_state(self, state) -> None:
        if state is not None:
            raise NotImplementedError(
                f"{type(self).__name__} has no objective state to restore")


class HostBackend(Backend):
    """Paper-scale simulation over host data. See module docstring for
    the fused / stacked / ragged round paths.

    ``round_mode``: "fused" (default), "stacked" (the PR-1 path, kept as
    the benchmark baseline), "ragged" (per-user jitted loop), or
    "sparse" (winner-sparse rounds; needs ``k_max``).
    ``k_max``: the round's winner budget (the spec's ``k_per_round``) —
    the compact merge pad width on every path, and the sparse path's
    compact train width. ``sparse_priority`` / ``sparse_chunk``
    configure the sparse path's Eq. 2 ordering (see
    ``sparse_priorities``).
    ``mesh``: optional 1-axis ``jax.sharding`` mesh from
    ``sharding.cohort_mesh`` — the fused stack, batches and per-user
    outputs shard their leading cohort axis over it when the user count
    divides the axis (no-op on one device); the sparse path shards its
    compact K axis instead (``sharding.winner_sharding``).
    """

    def __init__(self, loss_fn, user_data: Sequence, *, lr: float = 1e-2,
                 batch_size: int = 32, local_epochs: int = 1, seed: int = 0,
                 prefer_vmap: bool = True, num_classes: int = 10,
                 round_mode: Optional[str] = None, mesh=None,
                 k_max: Optional[int] = None,
                 sparse_priority: str = "prepass",
                 sparse_chunk: int = 256, objective=None):
        if round_mode is None:
            round_mode = "fused" if prefer_vmap else "ragged"
        if round_mode not in ("fused", "stacked", "ragged", "sparse"):
            raise ValueError(f"unknown round_mode {round_mode!r}")
        self._objective = objective
        obj_on = objective is not None and not objective.is_plain
        if obj_on and round_mode in ("stacked", "ragged"):
            raise ValueError(
                "non-plain objectives compile into the fused / sparse "
                f"device programs only; round_mode={round_mode!r} is the "
                "uncompiled fallback path (DESIGN.md §10)")
        if round_mode == "sparse" and not k_max:
            raise ValueError(
                "round_mode='sparse' needs k_max (the spec's "
                "k_per_round): it sizes the compact winner stack")
        if sparse_priority not in ("prepass", "stale"):
            raise ValueError(
                f"unknown sparse_priority {sparse_priority!r}; "
                "known: ('prepass', 'stale')")
        self.num_users = len(user_data)
        self.heterogeneity = label_heterogeneity(user_data, num_classes)
        self.seed = seed       # the clients' stream seed (engine checks
        #                        it before taking the E=1 sweep path)
        # an explicit round_mode subsumes the legacy prefer_vmap flag:
        # "stacked"/"fused" always stack what they can, "ragged" never
        self._mode = round_mode
        self._prefer_vmap = round_mode != "ragged"
        # Clients carry the per-user data, example counts and rng streams
        # (and the per-user jitted trainer for the ragged fallback path).
        self.clients = [
            Client(u, user_data[u], loss_fn, lr=lr, batch_size=batch_size,
                   local_epochs=local_epochs, seed=seed)
            for u in range(self.num_users)
        ]
        self._loss_fn = loss_fn
        self._lr = lr
        self._batch_size = batch_size
        self._local_epochs = local_epochs
        self._k_max = int(k_max) if k_max else None
        self._sparse_priority = sparse_priority
        self._sparse_chunk = int(sparse_chunk)
        self._mesh = mesh
        self._shard = shardable(self.num_users, mesh)
        # Pallas under GSPMD needs custom partitioning; when the cohort
        # actually shards over >1 device, route the fused reductions and
        # the local SGD update through the jnp oracles, which GSPMD
        # partitions on its own.
        # Single-partition execution (no mesh, 1-long axis, or an
        # unusable mesh) keeps the kernel path.
        self._use_kernel = (not self._shard) or mesh.size == 1

        epoch_run = sgd_epoch_scan(loss_fn, lr, self._use_kernel)
        self._epoch_run = epoch_run   # the shared local-SGD inner loop

        def train_one(params, batched):
            params, losses = epoch_run(params, batched)
            return params, losses.mean()

        # one compile for ALL users, vs one compile per user in the old
        # per-client loop
        self._train_stack = jax.jit(jax.vmap(train_one))
        self._prio_stack = jax.jit(stacked_model_priorities)
        self._prio_one = jax.jit(model_priority)

        # ---- fused-path state (built lazily on first fused round) ----
        ns = {c.num_examples for c in self.clients}
        self._rect = (len(ns) == 1
                      and batch_size <= self.clients[0].num_examples)
        if self._mode == "sparse" and not self._rect:
            raise ValueError(
                "round_mode='sparse' needs a rectangular cohort (equal "
                "per-user example counts >= batch_size): the prepass "
                "and compact gather-K train steps stack user data into "
                "one (U, n, ...) tensor; use round_mode=None (auto) or "
                "'ragged' for uneven cohorts")
        if obj_on and not self._rect:
            raise ValueError(
                "non-plain objectives need a rectangular cohort (equal "
                "per-user example counts >= batch_size): the objective "
                "grad law compiles into the fused / sparse stacked "
                "train steps only (DESIGN.md §10)")
        self._xstack = None        # (U, n, ...) pre-stacked user data
        self._fused_round = None
        self._fused_merge_fn = None
        self._fused_merge_air = None   # AirComp twin, built on first use
        self._bcast = None
        self._resident = None      # device-resident merged cohort stack
        self._resident_key = None  # the global-state object it mirrors
        self._sweep_fns = {}       # E -> jitted sweep (bcast, round, merge)
        self._sweep_data = {}      # placement -> resident data stack
        #                            (data_rows)
        self._sweep_air_fns = {}   # E -> jitted AirComp sweep merge
        # robust-guard merge twins (fault layer), keyed by the static
        # program shape: (stale count, quarantine, clip_norm) and the
        # sweep variant with a leading E — lazy, so a faults-off run
        # never traces them
        self._fused_fault_fns = {}
        self._sweep_fault_fns = {}
        # ---- sparse-path state (built lazily on first sparse round) --
        self._sparse_round = None     # compact (K_max, ...) train jit
        self._sparse_bcast = None
        self._prepass_fn = None       # chunked train-and-discard jit
        self._stale_prios = None      # (U,) f64 last-trained priorities
        self._pending_big = None      # this round's (U, ep*take) perms
        self._sweep_sparse_fns = {}   # E -> sparse sweep jits
        self._sweep_stale_prios = {}  # E -> (E, U) f64 cache
        self._pending_sweep_big = None
        # ---- objectives state (DESIGN.md §10; lazy) -------------------
        self._obj_run = None          # objective_epoch_scan closure
        self._obj_merge_fn = None     # jitted single-run objective merge
        self._obj_m = None            # server-opt first moment (~ glob)
        self._obj_v = None            # server-opt second moment
        self._obj_h = None            # (U, ...) per-user FedDyn h-state
        self._sweep_obj_round = {}    # (E, use_h) -> dense sweep round
        self._sweep_obj_merge_fns = {}  # (E, okey) -> sweep merge (the
        #                               one program both the dense and
        #                               sparse sweeps jit, by shape)
        self._sweep_sparse_obj = {}   # (E, use_h) -> (round, prepass)

    # ------------------------------------------------------------------
    def init_state(self, init_params):
        return init_params

    def num_examples(self, u):
        return self.clients[u].num_examples

    def _can_stack(self, train_ids) -> bool:
        if not self._prefer_vmap or len(train_ids) < 2:
            return False
        nbs = {max(1, self.clients[u].num_examples // self._batch_size)
               for u in train_ids}
        return len(nbs) == 1

    def _can_fuse(self, train_ids) -> bool:
        return (self._mode == "fused" and self._rect
                and len(train_ids) == self.num_users)

    # -------------------------------------- objectives helpers (§10)
    def objective_active(self) -> bool:
        return (self._objective is not None
                and not self._objective.is_plain)

    def objective_needs_h(self) -> bool:
        return self.objective_active() and self._objective.uses_h

    def _ensure_obj_run(self):
        if self._obj_run is None:
            self._obj_run = objective_epoch_scan(
                self._loss_fn, self._lr, self._objective.uses_h,
                self._use_kernel)
        return self._obj_run

    def _ensure_obj_h(self, state):
        """(U, ...) FedDyn h pytree, zero-initialized on first touch
        (no RNG — the objectives subsystem draws nothing)."""
        if self._obj_h is None:
            U = self.num_users
            self._obj_h = jax.tree.map(
                lambda p: jnp.zeros((U,) + jnp.shape(p),
                                    jnp.asarray(p).dtype), state)
        return self._obj_h

    def objective_state(self):
        """Checkpoint payload: host copies of the server-opt moments and
        the FedDyn h-state (None entries for pieces this objective never
        materialized — bit-identical resume re-zero-initializes them)."""
        if not self.objective_active():
            return None
        host = lambda x: None if x is None else jax.device_get(x)
        return {"m": host(self._obj_m), "v": host(self._obj_v),
                "h": host(self._obj_h)}

    def restore_objective_state(self, state) -> None:
        if state is None:
            return
        dev = lambda x: (None if x is None
                         else jax.tree.map(jnp.asarray, x))
        self._obj_m = dev(state.get("m"))
        self._obj_v = dev(state.get("v"))
        self._obj_h = dev(state.get("h"))

    def adopt_sweep_objective(self, st) -> None:
        """E=1 delegation continuity: when ``run()`` routes through the
        sweep path, strip the lane axis off the sweep objective state so
        a later single-run resume picks up the same moments/h."""
        if st.obj is None:
            return
        lane0 = lambda x: (None if x is None
                           else jax.tree.map(lambda p: p[0], x))
        self._obj_m = lane0(st.m)
        self._obj_v = lane0(st.v)
        self._obj_h = lane0(st.h)

    # ------------------------------------------------- fused round path
    def _ensure_xstack(self):
        """Pre-stack the rectangular per-user data to (U, n, ...)."""
        if self._xstack is not None:
            return
        self._nb = max(1, self.clients[0].num_examples // self._batch_size)
        self._xstack = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]),
            *[c.data for c in self.clients])
        # repoint each client at a VIEW of its stack row so the fallback
        # paths keep working while the dataset lives in host memory once,
        # not twice (np.stack copied; the originals can now be collected)
        for c in self.clients:
            c.data = jax.tree.map(lambda leaf: leaf[c.uid], self._xstack)

    def _merge_def(self, uk):
        """The ONE Eq. 1 merge program every digital path jits: gather
        the ``idx`` rows out of the trained stack (dense path: winner
        ids into (U, ...); sparse path: positions into (K_max, ...)),
        reduce under the compact weights, keep ``old_glob`` when no
        weight is nonzero. ``old_glob`` is NOT donated — on round 0 it
        may still be the caller's init_params."""
        def fused_merge(trained, idx, w, old_glob):
            new_glob = jax.tree.map(
                lambda l, g: kops.gather_combine(l, idx, w, g,
                                                 use_kernel=uk),
                trained, old_glob)
            new_stack = jax.tree.map(
                lambda g, l: jnp.broadcast_to(g[None], l.shape),
                new_glob, trained)
            return new_glob, new_stack
        return fused_merge

    def _build_fused(self):
        U = self.num_users
        self._ensure_xstack()
        nb = self._nb
        epoch_run, uk = self._epoch_run, self._use_kernel

        def bcast(g):
            return jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (U,) + p.shape), g)

        def _round_tail(trained, losses, glob, need_prio):
            # per-user loss = mean over the LAST epoch's batches, the
            # exact quantity the stacked / ragged paths report
            loss_u = losses[:, -nb:].mean(axis=1)
            if need_prio:
                prios = stacked_model_priorities(trained, glob,
                                                 use_kernel=uk)
            else:
                prios = jnp.ones((U,), jnp.float32)
            return trained, loss_u, prios

        obj_on = self.objective_active()
        use_h = obj_on and self._objective.uses_h
        if obj_on:
            obj_run = self._ensure_obj_run()
            # closed-over constant: the single path serves ONE spec, so
            # an inert coefficient constant-folds the guard away and the
            # compiled math is literally the plain program's
            prox = jnp.float32(self._objective.prox_coeff)
        if use_h:
            def fused_round(stack, batched, h, need_prio):
                glob = jax.tree.map(lambda p: p[0], stack)
                trained, losses = jax.vmap(
                    obj_run, in_axes=(0, 0, None, None, 0))(
                        stack, batched, glob, prox, h)
                return _round_tail(trained, losses, glob, need_prio)
            fr_static = 3
        elif obj_on:
            def fused_round(stack, batched, need_prio):
                glob = jax.tree.map(lambda p: p[0], stack)
                trained, losses = jax.vmap(
                    obj_run, in_axes=(0, 0, None, None))(
                        stack, batched, glob, prox)
                return _round_tail(trained, losses, glob, need_prio)
            fr_static = 2
        else:
            def fused_round(stack, batched, need_prio):
                # rows of `stack` are identical at round start (the
                # merged / broadcast global), so row 0 is the Eq. 2
                # reference model
                glob = jax.tree.map(lambda p: p[0], stack)
                trained, losses = jax.vmap(epoch_run)(stack, batched)
                return _round_tail(trained, losses, glob, need_prio)
            fr_static = 2

        fused_merge = self._merge_def(uk)
        if self._shard and obj_on:
            # objective runs don't take the explicit-sharding fast path
            # (the extra h operand has no spec); GSPMD still propagates
            # from the input shardings under a real mesh
            self._bcast = jax.jit(bcast)
            self._fused_round = jax.jit(fused_round,
                                        static_argnums=fr_static,
                                        donate_argnums=0)
            self._fused_merge_fn = jax.jit(fused_merge, donate_argnums=0)
        elif self._shard:
            cs = cohort_sharding(self._mesh)
            rep = replicated_sharding(self._mesh)
            self._bcast = jax.jit(bcast, out_shardings=cs)
            self._fused_round = jax.jit(
                fused_round, static_argnums=2, donate_argnums=0,
                in_shardings=(cs, cs), out_shardings=(cs, cs, cs))
            self._fused_merge_fn = jax.jit(
                fused_merge, donate_argnums=0,
                in_shardings=(cs, rep, rep, rep), out_shardings=(rep, cs))
        else:
            self._bcast = jax.jit(bcast)
            self._fused_round = jax.jit(fused_round,
                                        static_argnums=fr_static,
                                        donate_argnums=0)
            self._fused_merge_fn = jax.jit(fused_merge, donate_argnums=0)

    def _draw_big(self):
        """(U, ep*take) epoch-permutation index matrix for ONE round:
        every client draws one permutation per local epoch from ITS OWN
        rng stream — the exact draws of the stacked / ragged paths —
        laid out with each user's epochs concatenated."""
        U, bs, nb, E = (self.num_users, self._batch_size, self._nb,
                        self._local_epochs)
        n = self.clients[0].num_examples
        take = nb * bs
        perms = np.empty((E, U, take), np.int64)
        for e in range(E):
            for c in self.clients:
                perms[e, c.uid] = c._rng.permutation(n)[:take]
        return perms.transpose(1, 0, 2).reshape(U, E * take)

    def _gather_rows(self, rows, big_rows):
        """(R, ep*nb, bs, ...) round batches for the data rows ``rows``
        (user ids) under the per-row index matrix ``big_rows``
        ((R, ep*take) slice of ``_draw_big``'s output): one fancy-index
        over the pre-stacked data replaces R per-user gathers."""
        R = len(rows)
        bs, nb, E = self._batch_size, self._nb, self._local_epochs
        r = np.asarray(rows, np.int64)[:, None]
        return jax.tree.map(
            lambda leaf: leaf[r, big_rows].reshape(
                (R, E * nb, bs) + leaf.shape[2:]),
            self._xstack)

    def _fused_batches(self):
        """(U, E*nb, bs, ...) full-cohort round batches."""
        big = self._draw_big()
        return self._gather_rows(np.arange(self.num_users), big)

    def _build_fused_air(self):
        """AirComp twin of ``fused_merge``: gather the ``idx`` rows,
        then per-leaf noisy superposition through
        ``kernels.ops.aircomp_combine`` (per-leaf receiver noise from a
        fold_in of the round key), same donation / residency contract
        as the digital merge. The compact (k_pad,) alphas / coeffs are
        host-assembled identically for the dense and sparse paths, so
        the rescale ``Σa / Σ(a·c)`` — an order-sensitive f32 sum — is
        bit-identical between them. Built lazily — a fedavg-only run
        never traces it, keeping the no-channel program untouched."""
        uk = self._use_kernel

        def fused_merge_air(trained, idx, alphas, coeffs, sigma, key):
            leaves, treedef = jax.tree.flatten(trained)
            merged = []
            for i, leaf in enumerate(leaves):
                noise = sigma * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape[1:],
                    jnp.float32)
                rows = jnp.take(leaf, idx, axis=0)
                merged.append(kops.aircomp_combine(
                    rows, alphas, coeffs, noise, use_kernel=uk))
            new_glob = jax.tree.unflatten(treedef, merged)
            new_stack = jax.tree.map(
                lambda g, l: jnp.broadcast_to(g[None], l.shape),
                new_glob, trained)
            return new_glob, new_stack

        # under a real multi-device mesh GSPMD propagates shardings from
        # the (already sharded) trained stack; explicit specs are only
        # load-bearing on the hot fedavg path
        self._fused_merge_air = jax.jit(fused_merge_air, donate_argnums=0)

    def _train_round_fused(self, state, need_priority) -> TrainResult:
        if self._fused_round is None:
            self._build_fused()
        if self._resident is not None and self._resident_key is state:
            stack = self._resident          # device-resident since merge
        else:
            stack = self._bcast(state)      # first round / unmerged round
        # the stack buffer is donated into the trained stack below
        self._resident = self._resident_key = None
        if self.objective_needs_h():
            trained, loss_vec, prios = self._fused_round(
                stack, self._fused_batches(), self._ensure_obj_h(state),
                bool(need_priority))
        else:
            trained, loss_vec, prios = self._fused_round(
                stack, self._fused_batches(), bool(need_priority))
        priorities = (np.asarray(prios, np.float64).copy()
                      if need_priority else np.ones(self.num_users))
        # dense (U,) loss vector — a per-user dict would reintroduce the
        # O(U) Python conversion the fused path exists to kill
        return TrainResult(losses=np.asarray(loss_vec, np.float64),
                           priorities=priorities,
                           local_handle={"fused_stack": trained})

    # ------------------------------------------------------------------
    def train_round(self, state, t, train_ids, need_priority):
        priorities = np.ones(self.num_users)
        if not train_ids:
            return TrainResult(losses={}, priorities=priorities,
                               local_handle={})
        if self._can_fuse(train_ids):
            return self._train_round_fused(state, need_priority)
        if self.objective_active():
            raise RuntimeError(
                "non-plain objective on an unfused round (partial "
                "cohort?): objectives compile into the fused / sparse "
                "device programs only (DESIGN.md §10)")
        if self._mode != "ragged" and self._can_stack(train_ids):
            # PR-1 stacked path: epoch-batch on host with each client's
            # own rng stream, then train the whole (sub)cohort as one
            # stacked vmap(scan) per epoch
            stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None],
                                           (len(train_ids),) + p.shape),
                state)
            for _ in range(self._local_epochs):
                per_user = [batch_epoch(self.clients[u]._rng,
                                        self.clients[u].data,
                                        self._batch_size)
                            for u in train_ids]
                batched = jax.tree.map(
                    lambda *xs: np.stack(xs), *per_user)
                stacked, loss_vec = self._train_stack(stacked, batched)
            losses = {u: float(loss_vec[i])
                      for i, u in enumerate(train_ids)}
            if need_priority:
                prios = np.asarray(self._prio_stack(stacked, state))
                for i, u in enumerate(train_ids):
                    priorities[u] = float(prios[i])
            handle = {"stacked": stacked, "index": {u: i for i, u
                                                    in enumerate(train_ids)}}
            return TrainResult(losses=losses, priorities=priorities,
                               local_handle=handle)

        # ragged fallback: per-user jitted training (the seed path)
        locals_: Dict[int, object] = {}
        losses = {}
        for u in train_ids:
            locals_[u], loss = self.clients[u].train(state)
            losses[u] = float(loss)
            if need_priority:
                priorities[u] = float(self._prio_one(locals_[u], state))
        return TrainResult(losses=losses, priorities=priorities,
                           local_handle=locals_)

    def _local(self, handle, u):
        if isinstance(handle, dict) and "stacked" in handle:
            i = handle["index"][u]
            return jax.tree.map(lambda p: p[i], handle["stacked"])
        return handle[u]

    def extract_local(self, train_result, u):
        """User u's trained params as freshly materialized arrays, safe
        to hold across the merge (which donates the fused / stacked /
        sparse handle buffers) — the fault layer's stale-upload
        capture."""
        handle = train_result.local_handle
        if isinstance(handle, dict) and "fused_stack" in handle:
            return jax.tree.map(lambda p: p[u], handle["fused_stack"])
        if isinstance(handle, dict) and "sparse_stack" in handle:
            j = handle["winners"].index(int(u))
            return jax.tree.map(lambda p: p[j], handle["sparse_stack"])
        return self._local(handle, u)

    def _k_pad(self, m: int) -> int:
        """Compact merge width: ``k_max`` when set (so every round's
        merge — and the dense/sparse path pair — pads identically and
        the jitted programs never retrace on the delivery count), else
        the delivery count itself."""
        if self._k_max and m <= self._k_max:
            return self._k_max
        return max(m, 1)

    def merge(self, state, train_result, winners, merge_ctx=None,
              fault_ctx=None, attempts=None):
        handle = train_result.local_handle
        is_fused = isinstance(handle, dict) and "fused_stack" in handle
        is_sparse = isinstance(handle, dict) and "sparse_stack" in handle
        if is_fused or is_sparse:
            key = "fused_stack" if is_fused else "sparse_stack"
            trained = handle[key]
            winners = [int(u) for u in winners]
            # row indices into the trained stack: user ids for the dense
            # (U, ...) stack, delivery positions for the compact one
            pos = (winners if is_fused
                   else [handle["winners"].index(u) for u in winners])
            m = len(winners)
            k_pad = self._k_pad(m)
            if trained is None:
                # sparse round with no winners (all collided): nothing
                # trained; only a stale-only robust merge can land here
                assert fault_ctx is not None and not winners
                return self._gather_merge_faults(state, handle, [],
                                                 fault_ctx)
            if fault_ctx is not None:
                idx, _ = compact_weights(k_pad, pos, [1] * m)
                new_glob, new_stack = self._merge_fused_faults(
                    state, trained, idx, winners, fault_ctx)
            else:
                idx, w = compact_weights(
                    k_pad, pos,
                    [self.clients[u].num_examples for u in winners])
                if merge_ctx is None:
                    if self.objective_active():
                        new_glob, new_stack = self._objective_merge(
                            state, trained, idx, w, attempts, handle,
                            is_fused)
                    else:
                        new_glob, new_stack = self._fused_merge_fn(
                            trained, jnp.asarray(idx), jnp.asarray(w),
                            state)
                else:
                    if self._fused_merge_air is None:
                        self._build_fused_air()
                    # pad slots gather user 0's coefficient; their zero
                    # alpha masks it to an exact-zero term either way,
                    # and the vector is uid-built so dense and sparse
                    # assemble the SAME compact coeffs
                    uids = np.zeros(k_pad, np.int64)
                    uids[:m] = winners
                    coeffs = np.asarray(merge_ctx.coeffs,
                                        np.float32)[uids]
                    new_glob, new_stack = self._fused_merge_air(
                        trained, jnp.asarray(idx), jnp.asarray(w),
                        jnp.asarray(coeffs),
                        jnp.asarray(merge_ctx.noise_sigma, jnp.float32),
                        merge_ctx.key)
            handle[key] = None               # buffer donated into the stack
            self._resident = new_stack       # stays on device for round t+1
            self._resident_key = new_glob
            return new_glob
        # gather-merge (stacked / ragged handles): the produced state is
        # no longer mirrored by any resident stack — drop it so a
        # cohort-sized pytree can't stay pinned on device across a run
        # that switched to partial-cohort rounds
        self._resident = self._resident_key = None
        if fault_ctx is not None:
            return self._gather_merge_faults(state, handle, winners,
                                             fault_ctx)
        models = [self._local(handle, u) for u in winners]
        sizes = [self.clients[u].num_examples for u in winners]
        if merge_ctx is None:
            # same compact combine as the fused/sparse paths (positions
            # into the gathered stack), so partial-cohort rounds merge
            # bit-identically to the full-cohort formulations
            idx, w = compact_weights(self._k_pad(len(models)),
                                     list(range(len(models))), sizes)
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *models)
            return jax.tree.map(
                lambda l, g: kops.gather_combine(
                    l, idx, w, g, use_kernel=self._use_kernel),
                stacked, state)
        return self._gather_merge_air(models, sizes, winners, merge_ctx)

    # ------------------------------------ objective merge program (§10)
    def _build_obj_merge(self):
        """Objective twin of ``fused_merge`` (one program for the dense
        AND sparse handles — jit re-specializes on the trained stack's
        row count): Eq. 1 gather_combine per leaf, then the server-opt
        step on the pseudo-gradient, then the merge-time FedDyn h
        scatter. Argument layout after ``(trained, idx, w, old_glob)``:
        ``[m, v]`` when the aggregator carries state, then
        ``[h, hsrc, hdst]`` when the local objective carries h.
        ``trained`` and the m/v/h state are donated (device-resident
        chain); ``old_glob`` is NOT (round 0 may pass init_params)."""
        uk = self._use_kernel
        obj = self._objective
        use_h, use_srv = obj.uses_h, obj.uses_server
        consts = jnp.asarray(obj.server_consts())
        alpha = jnp.float32(obj.alpha_coeff)

        def obj_merge(trained, idx, w, old_glob, *rest):
            i = 0
            if use_srv:
                m, v = rest[0], rest[1]
                i = 2
            if use_h:
                h, hsrc, hdst = rest[i], rest[i + 1], rest[i + 2]
            avg = jax.tree.map(
                lambda l, g: kops.gather_combine(l, idx, w, g,
                                                 use_kernel=uk),
                trained, old_glob)
            if use_srv:
                # winnerless guard: a round with zero delivered mass
                # must not decay the server momentum — the plain path
                # skips its merge entirely on such rounds, so the
                # server-opt state freezes and the output stays the
                # (glob-keeping) average, bitwise
                has = jnp.any(w != 0.0)
                al, td = jax.tree.flatten(avg)
                ol = jax.tree.leaves(old_glob)
                ml = jax.tree.leaves(m)
                vl = jax.tree.leaves(v)
                go, gm, gv = [], [], []
                for a_l, o_l, m_l, v_l in zip(al, ol, ml, vl):
                    o2, m2, v2 = kops.server_opt_combine(
                        a_l, o_l, m_l, v_l, consts, use_kernel=uk)
                    go.append(jnp.where(has, o2, a_l))
                    gm.append(jnp.where(has, m2, m_l))
                    gv.append(jnp.where(has, v2, v_l))
                new_glob = jax.tree.unflatten(td, go)
                new_m = jax.tree.unflatten(td, gm)
                new_v = jax.tree.unflatten(td, gv)
            else:
                new_glob = avg
            if use_h:
                # h_u <- h_u - alpha * (w_u^end - w_glob), keyed to the
                # round's ATTEMPT winners (the clients that trained — a
                # channel drop doesn't undo a local h update). Pad
                # slots carry dst = U and drop out of bounds, so they
                # can't flip a -0.0 h entry; alpha == 0 keeps h bitwise.
                rows = jax.tree.map(
                    lambda l: jnp.take(l, hsrc, axis=0), trained)
                new_h = jax.tree.map(
                    lambda hh, r, wg: jnp.where(
                        alpha != 0.0,
                        hh.at[hdst].add(-alpha * (r - wg[None]),
                                        mode="drop"),
                        hh),
                    h, rows, old_glob)
            new_stack = jax.tree.map(
                lambda g, l: jnp.broadcast_to(g[None], l.shape),
                new_glob, trained)
            out = [new_glob, new_stack]
            if use_srv:
                out += [new_m, new_v]
            if use_h:
                out += [new_h]
            return tuple(out)

        donate = [0]
        if use_srv:
            donate += [4, 5]
        if use_h:
            donate += [4 + (2 if use_srv else 0)]
        self._obj_merge_fn = jax.jit(obj_merge,
                                     donate_argnums=tuple(donate))
        return self._obj_merge_fn

    def _objective_merge(self, state, trained, idx, w, attempts, handle,
                         is_fused):
        """Assemble the objective merge call: lazy zero-init of the m/v/h
        state, host-side (kh,) attempt gather/scatter vectors (row
        indices into the trained stack — user ids on the dense handle,
        delivery positions on the sparse one — and destination user
        ids, pads parked at U), then dispatch and re-own the donated
        state outputs."""
        obj = self._objective
        fn = self._obj_merge_fn or self._build_obj_merge()
        args = [trained, jnp.asarray(idx), jnp.asarray(w), state]
        if obj.uses_server:
            if self._obj_m is None:
                self._obj_m = jax.tree.map(
                    lambda p: jnp.zeros_like(jnp.asarray(p)), state)
                self._obj_v = jax.tree.map(
                    lambda p: jnp.zeros_like(jnp.asarray(p)), state)
            args += [self._obj_m, self._obj_v]
            self._obj_m = self._obj_v = None     # donated below
        if obj.uses_h:
            att = [int(u) for u in (attempts or [])]
            kh = self._k_pad(len(att))
            hsrc = np.zeros(kh, np.int32)
            hdst = np.full(kh, self.num_users, np.int32)
            if att:
                hsrc[:len(att)] = (att if is_fused
                                   else [handle["winners"].index(u)
                                         for u in att])
                hdst[:len(att)] = att
            args += [self._ensure_obj_h(state), jnp.asarray(hsrc),
                     jnp.asarray(hdst)]
            self._obj_h = None                   # donated below
        out = fn(*args)
        new_glob, new_stack = out[0], out[1]
        i = 2
        if obj.uses_server:
            self._obj_m, self._obj_v = out[i], out[i + 1]
            i += 2
        if obj.uses_h:
            self._obj_h = out[i]
        return new_glob, new_stack

    # ----------------------------------------- robust merge twins (§8)
    def _build_fused_fault(self, key):
        """Robust-guard twin of ``fused_merge``: gather the merge
        candidates' rows out of the trained stack, then the same
        donated, device-resident merge step routed through
        ``robust_merge`` over the compact (k_pad, ...) group. The old
        global is an extra input (delta-space guard reference) and is
        NOT donated — on round 0 it may still be the caller's
        init_params."""
        M, quarantine, clip = key
        uk = self._use_kernel

        def fused_fault(trained, idx, weights, corrupt, old_glob,
                        *stale_args):
            stale, stale_w = stale_args if M else (None, None)
            rows = jax.tree.map(lambda l: jnp.take(l, idx, axis=0),
                                trained)
            glob, nq = robust_merge(rows, weights, corrupt, old_glob,
                                    stale, stale_w, quarantine=quarantine,
                                    clip_norm=clip, use_kernel=uk)
            stack = jax.tree.map(
                lambda g, l: jnp.broadcast_to(g[None], l.shape),
                glob, trained)
            return glob, stack, nq

        fn = jax.jit(fused_fault, donate_argnums=0)
        self._fused_fault_fns[key] = fn
        return fn

    def _merge_fused_faults(self, state, trained, idx, winners, ctx):
        """Compact the dense (U,) fault-context weight / corruption
        vectors down to the (k_pad,) merge candidates (pads: exact-zero
        weight, corruption factor 1.0 = the bit-level passthrough
        branch) and dispatch the robust merge twin."""
        m = len(winners)
        k_pad = idx.shape[0]
        w = np.zeros(k_pad, np.float32)
        c = np.ones(k_pad, np.float32)
        if m:
            sel = [int(u) for u in winners]
            w[:m] = np.asarray(ctx.weights, np.float32)[sel]
            c[:m] = np.asarray(ctx.corrupt, np.float32)[sel]
        key = (len(ctx.stale), bool(ctx.quarantine), float(ctx.clip_norm))
        fn = self._fused_fault_fns.get(key) or self._build_fused_fault(key)
        args = [trained, jnp.asarray(idx), jnp.asarray(w),
                jnp.asarray(c), state]
        if ctx.stale:
            args.append(jax.tree.map(
                lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]),
                *[p for p, _ in ctx.stale]))
            args.append(jnp.asarray([w_ for _, w_ in ctx.stale],
                                    jnp.float32))
        new_glob, new_stack, nq = fn(*args)
        ctx.n_quarantined = int(nq)
        return new_glob, new_stack

    def _gather_merge_faults(self, state, handle, winners, ctx):
        """Eager robust merge over the gathered candidates (stacked /
        ragged handles); also covers the stale-only round, where there
        are no fresh winners at all."""
        trained = weights = corrupt = None
        if winners:
            models = [self._local(handle, u) for u in winners]
            trained = jax.tree.map(lambda *ls: jnp.stack(ls), *models)
            idx = [int(u) for u in winners]
            weights = np.asarray(ctx.weights, np.float32)[idx]
            corrupt = np.asarray(ctx.corrupt, np.float32)[idx]
        stale = stale_w = None
        if ctx.stale:
            stale = jax.tree.map(
                lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]),
                *[p for p, _ in ctx.stale])
            stale_w = np.asarray([w for _, w in ctx.stale], np.float32)
        glob, nq = robust_merge(trained, weights, corrupt, state,
                                stale, stale_w,
                                quarantine=ctx.quarantine,
                                clip_norm=ctx.clip_norm,
                                use_kernel=self._use_kernel)
        ctx.n_quarantined = int(nq)
        return glob

    def _gather_merge_air(self, models, sizes, winners, merge_ctx):
        """AirComp over the gathered winner models (stacked / ragged
        round paths) — rare, so per-call tracing is acceptable."""
        w = np.asarray(sizes, np.float64)
        alphas = jnp.asarray(w / w.sum(), jnp.float32)
        coeffs = jnp.asarray(
            np.asarray(merge_ctx.coeffs, np.float32)[
                [int(u) for u in winners]])
        stacked_tree = jax.tree.map(lambda *ls: jnp.stack(ls), *models)
        leaves, treedef = jax.tree.flatten(stacked_tree)
        merged = []
        for i, leaf in enumerate(leaves):
            noise = jnp.asarray(merge_ctx.noise_sigma, jnp.float32) * \
                jax.random.normal(jax.random.fold_in(merge_ctx.key, i),
                                  leaf.shape[1:], jnp.float32)
            merged.append(kops.aircomp_combine(
                leaf, alphas, coeffs, noise,
                use_kernel=self._use_kernel))
        return jax.tree.unflatten(treedef, merged)

    # ------------------------------------------- winner-sparse path (§9)
    # Contention-first rounds: Eq. 2 priorities are produced BEFORE
    # selection, then only the K winners' params + batches are gathered
    # into a compact (K_max, ...) fused train step and the merged delta
    # scatters back into the device-resident global. Per-round train
    # FLOPs and peak memory scale with K, not U.
    def sparse_capable(self) -> bool:
        return (self._mode == "sparse" and self._rect
                and bool(self._k_max))

    def _build_sparse(self):
        K = self._k_max
        self._ensure_xstack()
        nb = self._nb
        obj_on = self.objective_active()
        # objective programs skip the explicit sharding annotations
        # (same rule as the fused path: plain jit, GSPMD propagates)
        shard = (self._mesh is not None
                 and winner_shardable(K, self._mesh) and not obj_on)
        # same rule as the fused path: Pallas under real GSPMD
        # partitioning needs custom partitioning, so a >1-way K split
        # routes the reductions and the SGD update through the oracles
        uk = (not shard) or self._mesh.size == 1
        self._sparse_uk = uk
        epoch_run = sgd_epoch_scan(self._loss_fn, self._lr, uk)
        use_h = obj_on and self._objective.uses_h
        if obj_on:
            obj_run = self._ensure_obj_run()
            prox = jnp.float32(self._objective.prox_coeff)

            def train_rows(stack, batched, glob, h_rows):
                if use_h:
                    return jax.vmap(obj_run,
                                    in_axes=(0, 0, None, None, 0))(
                        stack, batched, glob, prox, h_rows)
                return jax.vmap(obj_run, in_axes=(0, 0, None, None))(
                    stack, batched, glob, prox)

        def bcast_k(g):
            return jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (K,) + p.shape), g)

        def _round_body(stack, batched, h_rows):
            # rows are identical at round start (broadcast global), so
            # row 0 is the Eq. 2 reference — same trick as fused_round.
            # Priorities are always computed: K rows are cheap, and the
            # "stale" mode feeds them back into its cache.
            glob = jax.tree.map(lambda p: p[0], stack)
            if obj_on:
                trained, losses = train_rows(stack, batched, glob, h_rows)
            else:
                trained, losses = jax.vmap(epoch_run)(stack, batched)
            loss_k = losses[:, -nb:].mean(axis=1)
            prios = stacked_model_priorities(trained, glob, use_kernel=uk)
            return trained, loss_k, prios

        def _prepass_body(glob, batched, h_rows):
            # exact Eq. 2 over one chunk: train-and-discard — only the
            # (C,) losses/priorities leave the call, so peak memory is
            # O(chunk · params) regardless of U. Per-row results of a
            # width-C vmap are bitwise equal to the width-U dense vmap's
            # rows, which is what makes prepass priorities (and the
            # winner retrain below) bit-identical to the fused path.
            C = jax.tree.leaves(batched)[0].shape[0]
            stack = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (C,) + p.shape), glob)
            if obj_on:
                trained, losses = train_rows(stack, batched, glob, h_rows)
            else:
                trained, losses = jax.vmap(epoch_run)(stack, batched)
            loss_c = losses[:, -nb:].mean(axis=1)
            prios = stacked_model_priorities(trained, glob, use_kernel=uk)
            return loss_c, prios

        # the h-carrying variants take the winners' h rows as a third
        # traced argument; the others keep the original 2-arg signature
        # (no retrace churn for plain/fedprox specs)
        if use_h:
            sparse_round = _round_body
            prepass_chunk = _prepass_body
        else:
            sparse_round = lambda stack, batched: _round_body(
                stack, batched, None)
            prepass_chunk = lambda glob, batched: _prepass_body(
                glob, batched, None)

        fused_merge = self._merge_def(uk)
        if shard:
            ks = winner_sharding(self._mesh)
            rep = replicated_sharding(self._mesh)
            self._sparse_bcast = jax.jit(bcast_k, out_shardings=ks)
            self._sparse_round = jax.jit(
                sparse_round, donate_argnums=0,
                in_shardings=(ks, ks), out_shardings=(ks, rep, rep))
            self._fused_merge_fn = jax.jit(
                fused_merge, donate_argnums=0,
                in_shardings=(ks, rep, rep, rep), out_shardings=(rep, ks))
        else:
            self._sparse_bcast = jax.jit(bcast_k)
            self._sparse_round = jax.jit(sparse_round, donate_argnums=0)
            self._fused_merge_fn = jax.jit(fused_merge, donate_argnums=0)
        self._prepass_fn = jax.jit(prepass_chunk)

    def sparse_priorities(self, state, need_priority: bool):
        """Pre-selection Eq. 2: ``(priorities (U,) f64, losses | None)``.

        "prepass" mode draws the round's FULL epoch permutations (every
        client's stream, the dense path's exact draws — cached for the
        winner retrain) and, when priorities are needed, runs the
        chunked train-and-discard prepass for bit-exact priorities and
        losses. "stale" mode serves each user's last-trained priority
        from the cache (ones before first contact) at O(K) FLOPs and
        O(winners) stream draws — distributional parity only.
        """
        if self._sparse_round is None:
            self._build_sparse()
        U = self.num_users
        if self._sparse_priority == "stale":
            if not need_priority:
                return np.ones(U), None
            if self._stale_prios is None:
                self._stale_prios = np.ones(U, np.float64)
            return self._stale_prios.copy(), None
        self._pending_big = big = self._draw_big()
        if not need_priority:
            return np.ones(U), None
        C = max(1, min(self._sparse_chunk, U))
        losses = np.empty(U)
        prios = np.empty(U)
        needs_h = self.objective_needs_h()
        h = self._ensure_obj_h(state) if needs_h else None
        for lo in range(0, U, C):
            rows = np.arange(lo, min(lo + C, U))
            batched = self._gather_rows(rows, big[rows])
            if needs_h:
                hc = jax.tree.map(
                    lambda hh: hh[lo:lo + len(rows)], h)
                l, p = self._prepass_fn(state, batched, hc)
            else:
                l, p = self._prepass_fn(state, batched)
            losses[lo:lo + len(rows)] = np.asarray(l, np.float64)
            prios[lo:lo + len(rows)] = np.asarray(p, np.float64)
        return prios, losses

    def sparse_train(self, state, winners: List[int]) -> TrainResult:
        """Compact winner training: gather the K winners' batches (from
        the prepass draws when present, else fresh winner-only draws)
        and run the (K_max, ...) fused step. Pad rows re-train user 0's
        data and ride with zero merge weight. Returns a
        ``{"sparse_stack", "winners"}`` handle for ``merge``."""
        if self._sparse_round is None:
            self._build_sparse()
        K, m = self._k_max, len(winners)
        if m > K:
            raise ValueError(f"{m} winners exceed k_max={K}")
        big, self._pending_big = self._pending_big, None
        if not m and big is None:
            # nothing to train and no streams were consumed: keep the
            # resident stack (if any) for the next round
            return TrainResult(losses={}, priorities=np.ones(
                self.num_users), local_handle={"sparse_stack": None,
                                               "winners": []})
        rows = np.zeros(K, np.int64)
        rows[:m] = [int(u) for u in winners]
        if big is not None:
            big_rows = big[rows]
        else:
            # "stale" mode: only the WINNERS' streams advance — pad
            # rows ride on index 0 (example-0 batches, zero-weight)
            bs, nb, E = self._batch_size, self._nb, self._local_epochs
            n = self.clients[0].num_examples
            take = nb * bs
            big_rows = np.zeros((K, E * take), np.int64)
            for j in range(m):
                u = rows[j]
                for e in range(E):
                    big_rows[j, e * take:(e + 1) * take] = \
                        self.clients[u]._rng.permutation(n)[:take]
        batched = self._gather_rows(rows, big_rows)
        if self._resident is not None and self._resident_key is state:
            stack = self._resident
        else:
            stack = self._sparse_bcast(state)
        self._resident = self._resident_key = None
        if self.objective_needs_h():
            # pad rows gather user 0's h alongside its batches —
            # harmless (zero merge weight, output row discarded)
            h_rows = jax.tree.map(lambda hh: hh[rows],
                                  self._ensure_obj_h(state))
            trained, loss_k, prios_k = self._sparse_round(
                stack, batched, h_rows)
        else:
            trained, loss_k, prios_k = self._sparse_round(stack, batched)
        if self._sparse_priority == "stale" and m:
            if self._stale_prios is None:
                self._stale_prios = np.ones(self.num_users, np.float64)
            self._stale_prios[rows[:m]] = \
                np.asarray(prios_k, np.float64)[:m]
        lk = np.asarray(loss_k, np.float64)
        return TrainResult(
            losses={int(u): float(lk[j]) for j, u in enumerate(winners)},
            priorities=np.ones(self.num_users),
            local_handle={"sparse_stack": trained,
                          "winners": [int(u) for u in winners]})

    def priority_cache_state(self):
        return (None if self._stale_prios is None
                else self._stale_prios.copy())

    def restore_priority_cache(self, state) -> None:
        if state is not None:
            self._stale_prios = np.asarray(state, np.float64).copy()

    # -------------------------------------------------- sweep round path
    # E independent experiments as ONE device program (DESIGN.md §5):
    # the fused round step vmapped over a leading experiment axis, so
    # every array gains an (E, ...) prefix and the per-round device
    # traffic is one train call + one merge call for the whole sweep.
    def sweep_capable(self) -> bool:
        """Sweeps need the fused full-cohort shape: fused mode and a
        rectangular cohort (equal per-user example counts)."""
        return self._mode == "fused" and self._rect

    def _build_sweep_fns(self, E: int):
        U, uk = self.num_users, self._use_kernel
        self._ensure_xstack()
        nb = self._nb
        shard = (self._mesh is not None
                 and sweep_shardable(E, U, self._mesh))
        if shard:
            # mirror the fused-path rule: Pallas under real GSPMD
            # partitioning needs custom partitioning, so a >1-way split
            # routes the reductions and the SGD update through the jnp
            # oracles
            uk = uk and self._mesh.size == 1
        epoch_run = sgd_epoch_scan(self._loss_fn, self._lr, uk)
        shapes = jax.tree.map(lambda leaf: leaf.shape[2:], self._xstack)

        def bcast(g):
            glob = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (E,) + p.shape), g)
            stack = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None, None],
                                           (E, U) + p.shape), g)
            return glob, stack

        def sweep_round(stack, data, idx, need_prio):
            # per-lane rows are identical at round start, so lane e's
            # Eq. 2 reference model is its row 0 — same trick as the
            # single-experiment fused step, one axis up
            glob = jax.tree.map(lambda p: p[:, 0], stack)
            trained, losses = train_lanes(epoch_run, stack, data, idx,
                                          shapes)
            loss_u = losses[:, :, -nb:].mean(axis=2)          # (E, U)
            if need_prio:
                prios = jax.vmap(
                    lambda tr, g: stacked_model_priorities(
                        tr, g, use_kernel=uk))(trained, glob)
            else:
                prios = jnp.ones((E, U), jnp.float32)
            return trained, loss_u, prios

        def sweep_merge(trained, idx, w, old_glob):
            # compact Eq. 1 per lane — the vmapped twin of the single
            # path's gather_combine merge; its in-op all-zero-weight
            # guard keeps a winnerless lane's old global per-lane (the
            # in-graph twin of "skip merge, rebuild from state")
            def one(tr_e, i_e, w_e, g_e):
                return jax.tree.map(
                    lambda l, g: kops.gather_combine(l, i_e, w_e, g,
                                                     use_kernel=uk),
                    tr_e, g_e)
            glob = jax.vmap(one)(trained, idx, w, old_glob)
            stack = jax.tree.map(
                lambda g, tr: jnp.broadcast_to(g[:, None], tr.shape),
                glob, trained)
            return glob, stack

        if shard:
            ss = sweep_sharding(self._mesh, E, U)
            gs = sweep_global_sharding(self._mesh, E)
            # the data argument keeps the resident stack's placement
            # (_data_sharding)
            fns = (
                jax.jit(bcast, out_shardings=(gs, ss)),
                jax.jit(sweep_round, static_argnums=3, donate_argnums=0,
                        in_shardings=(ss, None, ss),
                        out_shardings=(ss, ss, ss)),
                jax.jit(sweep_merge, donate_argnums=(0, 3),
                        in_shardings=(ss, gs, gs, gs),
                        out_shardings=(gs, ss)),
            )
        else:
            fns = (
                jax.jit(bcast),
                jax.jit(sweep_round, static_argnums=3, donate_argnums=0),
                jax.jit(sweep_merge, donate_argnums=(0, 3)),
            )
        self._sweep_fns[E] = fns
        return fns

    # --------------------------------- objective sweep programs (§10)
    # The objective is a sweep AXIS: lanes with different objectives
    # share ONE superset program built from the union of their
    # structural flags; per-lane (E,) prox/alpha vectors and (E, 5)
    # server consts arrive as traced arguments, so inert lanes pass
    # through bitwise via the same runtime guards the single path
    # constant-folds. Unsharded (plain jit, GSPMD propagates) — same
    # rule as the single-run objective programs.
    def _build_sweep_obj_round(self, E: int, use_h: bool):
        U = self.num_users
        self._ensure_xstack()
        nb, uk = self._nb, self._use_kernel
        obj_run = objective_epoch_scan(self._loss_fn, self._lr, use_h, uk)
        shapes = jax.tree.map(lambda leaf: leaf.shape[2:], self._xstack)

        def _tail(trained, losses, glob, need_prio):
            loss_u = losses[:, :, -nb:].mean(axis=2)          # (E, U)
            if need_prio:
                prios = jax.vmap(
                    lambda tr, g: stacked_model_priorities(
                        tr, g, use_kernel=uk))(trained, glob)
            else:
                prios = jnp.ones((E, U), jnp.float32)
            return trained, loss_u, prios

        if use_h:
            def sweep_obj_round(stack, data, idx, prox, h, need_prio):
                glob = jax.tree.map(lambda p: p[:, 0], stack)
                trained, losses = train_lanes(
                    obj_run, stack, data, idx, shapes, lane=(glob, prox),
                    user=(h,))
                return _tail(trained, losses, glob, need_prio)
            static = 5
        else:
            def sweep_obj_round(stack, data, idx, prox, need_prio):
                glob = jax.tree.map(lambda p: p[:, 0], stack)
                trained, losses = train_lanes(
                    obj_run, stack, data, idx, shapes, lane=(glob, prox))
                return _tail(trained, losses, glob, need_prio)
            static = 4
        fn = jax.jit(sweep_obj_round, static_argnums=static,
                     donate_argnums=0)
        self._sweep_obj_round[(E, use_h)] = fn
        return fn

    def _build_sweep_obj_merge(self, E: int, okey):
        """Objective twin of the sweep merge — ONE program for the
        dense AND sparse sweeps (jit re-specializes on the trained
        stack's row count). Per-lane Eq. 1 gather_combine, the vmapped
        server-opt step under per-lane (E, 5) consts rows, then the
        per-lane FedDyn h scatter under (E,) alphas."""
        use_h, use_srv = okey
        uk = self._use_kernel

        def sweep_obj_merge(trained, idx, w, old_glob, *rest):
            i = 0
            if use_srv:
                m, v, consts = rest[0], rest[1], rest[2]
                i = 3
            if use_h:
                h, hsrc, hdst, alphav = rest[i], rest[i + 1], \
                    rest[i + 2], rest[i + 3]

            def one(tr_e, i_e, w_e, g_e):
                return jax.tree.map(
                    lambda l, g: kops.gather_combine(l, i_e, w_e, g,
                                                     use_kernel=uk),
                    tr_e, g_e)
            avg = jax.vmap(one)(trained, idx, w, old_glob)
            if use_srv:
                # per-lane winnerless guard (see _build_obj_merge)
                has = jnp.any(w != 0.0, axis=1)               # (E,)
                al, td = jax.tree.flatten(avg)
                ol = jax.tree.leaves(old_glob)
                ml = jax.tree.leaves(m)
                vl = jax.tree.leaves(v)
                go, gm, gv = [], [], []
                for a_l, o_l, m_l, v_l in zip(al, ol, ml, vl):
                    o2, m2, v2 = jax.vmap(
                        lambda a, o, mm, vv, c: kops.server_opt_combine(
                            a, o, mm, vv, c, use_kernel=uk))(
                        a_l, o_l, m_l, v_l, consts)
                    hb = has.reshape((E,) + (1,) * (a_l.ndim - 1))
                    go.append(jnp.where(hb, o2, a_l))
                    gm.append(jnp.where(hb, m2, m_l))
                    gv.append(jnp.where(hb, v2, v_l))
                new_glob = jax.tree.unflatten(td, go)
                new_m = jax.tree.unflatten(td, gm)
                new_v = jax.tree.unflatten(td, gv)
            else:
                new_glob = avg
            if use_h:
                rows = jax.tree.map(
                    lambda l: jax.vmap(
                        lambda le, se: jnp.take(le, se, axis=0))(l, hsrc),
                    trained)

                def upd(h_e, r_e, g_e, d_e, a_e):
                    return jnp.where(
                        a_e != 0.0,
                        h_e.at[d_e].add(-a_e * (r_e - g_e[None]),
                                        mode="drop"),
                        h_e)
                new_h = jax.tree.map(
                    lambda hh, r, wg: jax.vmap(upd)(hh, r, wg, hdst,
                                                    alphav),
                    h, rows, old_glob)
            new_stack = jax.tree.map(
                lambda g, tr: jnp.broadcast_to(g[:, None], tr.shape),
                new_glob, trained)
            out = [new_glob, new_stack]
            if use_srv:
                out += [new_m, new_v]
            if use_h:
                out += [new_h]
            return tuple(out)

        donate = [0, 3]
        if use_srv:
            donate += [4, 5]
        if use_h:
            donate += [4 + (3 if use_srv else 0)]
        fn = jax.jit(sweep_obj_merge, donate_argnums=tuple(donate))
        self._sweep_obj_merge_fns[(E, okey)] = fn
        return fn

    def _attach_sweep_objective(self, st: SweepState, objectives,
                                init_params, payload=None) -> None:
        """Install the sweep's ObjectiveTable + device-resident m/v/h
        state on a fresh/restored SweepState. No-op when every lane is
        plain (None table) — the untouched pre-registry programs run."""
        table = build_objective_table(objectives or [])
        if table is None:
            return
        st.obj = table
        E, U = st.num_lanes, self.num_users

        def zeros(lead):
            return jax.tree.map(
                lambda p: jnp.zeros(lead + np.shape(p),
                                    jnp.asarray(p).dtype), init_params)
        payload = payload or {}

        def load(key, lead):
            x = payload.get(key)
            return (zeros(lead) if x is None
                    else jax.tree.map(jnp.asarray, x))
        if table.use_srv:
            st.m = load("m", (E,))
            st.v = load("v", (E,))
        if table.use_h:
            st.h = load("h", (E, U))

    def sweep_objective_state(self, st: SweepState):
        """Checkpoint payload twin of ``objective_state`` for sweeps."""
        if st.obj is None:
            return None
        host = lambda x: None if x is None else jax.device_get(x)
        return {"m": host(st.m), "v": host(st.v), "h": host(st.h)}

    def _dispatch_obj_sweep_merge(self, st: SweepState, trained, idx, w,
                                  attempts) -> None:
        """Assemble + dispatch the objective sweep merge. ``attempts``
        is ``(att_uids, att_pos)``: per-lane attempt-winner user ids and
        the matching row positions into the trained stack (== the uids
        on the dense sweep, delivery positions on the sparse one)."""
        E, table = st.num_lanes, st.obj
        use_h, use_srv = table.okey
        fn = (self._sweep_obj_merge_fns.get((E, table.okey))
              or self._build_sweep_obj_merge(E, table.okey))
        glob, st.glob = st.glob, None                # donated below
        args = [trained, jnp.asarray(idx), jnp.asarray(w), glob]
        if use_srv:
            m, st.m = st.m, None
            v, st.v = st.v, None
            args += [m, v, jnp.asarray(table.consts)]
        if use_h:
            att_uids, att_pos = (attempts if attempts is not None
                                 else ([[]] * E, [[]] * E))
            kh = self._k_pad(max((len(a) for a in att_uids), default=0))
            hsrc = np.zeros((E, kh), np.int32)
            hdst = np.full((E, kh), self.num_users, np.int32)
            for e in range(E):
                n = len(att_uids[e])
                if n:
                    hsrc[e, :n] = [int(p) for p in att_pos[e]]
                    hdst[e, :n] = [int(u) for u in att_uids[e]]
            h, st.h = st.h, None
            args += [h, jnp.asarray(hsrc), jnp.asarray(hdst),
                     jnp.asarray(table.alpha)]
        out = fn(*args)
        st.glob, st.stack = out[0], out[1]
        i = 2
        if use_srv:
            st.m, st.v = out[i], out[i + 1]
            i += 2
        if use_h:
            st.h = out[i]

    def sweep_init(self, init_params, seeds: Sequence[int],
                   objectives=None) -> SweepState:
        """Fresh device (glob, stack) + per-lane client rng streams.

        ``seeds[e]`` is lane e's experiment seed; user u's stream is
        ``core.rngs.client_rng(seed, u)`` — exactly the stream a
        dedicated per-spec backend (``Client``'s seeding rule) would
        own, which is what makes sweep lanes batch-draw-identical to
        sequential runs. ``objectives[e]`` is lane e's ObjectiveSpec
        (None = plain); all-plain sweeps attach no objective state."""
        if not self.sweep_capable():
            raise ValueError(
                "sweep needs round_mode='fused' and a rectangular "
                "cohort (equal per-user example counts)")
        E = len(seeds)
        bcast, _, _ = self._sweep_fns.get(E) or self._build_sweep_fns(E)
        glob, stack = bcast(init_params)
        rngs = [[client_rng(s, u) for u in range(self.num_users)]
                for s in seeds]
        st = SweepState(num_lanes=E, glob=glob, stack=stack, rngs=rngs)
        self._attach_sweep_objective(st, objectives, init_params)
        return st

    def _draw_sweep_big(self, st: SweepState):
        """(E, U, ep*take) epoch-permutation index tensor for one sweep
        round: per (lane, user) one permutation per local epoch from
        that lane/user's OWN stream, in epoch order — the draws a
        sequential fused run of the lane would make."""
        E, U = st.num_lanes, self.num_users
        bs, nb, ep = self._batch_size, self._nb, self._local_epochs
        n = self.clients[0].num_examples
        take = nb * bs
        perms = np.empty((E, ep, U, take), np.int64)
        with spans.span("fl.round.batches.draw"):
            for e in range(E):
                for k in range(ep):
                    for u in range(U):
                        perms[e, k, u] = \
                            st.rngs[e][u].permutation(n)[:take]
        return perms.transpose(0, 2, 1, 3).reshape(E, U, ep * take)

    def _data_sharding(self, E: int):
        """Placement of the resident data stack for an E-lane sweep:
        where the sweep splits its users over the mesh the stack's U
        axis splits the same way, where it splits its lanes every
        device holds it all, and without such a mesh (None) it lives on
        the default device."""
        if self._mesh is None or not sweep_shardable(E, self.num_users,
                                                     self._mesh):
            return None
        if sweep_sharding(self._mesh, E, self.num_users).spec[0]:
            return replicated_sharding(self._mesh)
        return cohort_sharding(self._mesh)

    def _resident_data(self, E: int):
        """The data stack on the device (``data_rows``), where the dense
        E-lane sweep's round programs gather each round's batches. Put
        there once per placement (``_data_sharding``) and kept for the
        backend's life; the data is read-only, so no program donates
        it."""
        sharding = self._data_sharding(E)
        key = None if sharding is None else sharding.spec
        if key not in self._sweep_data:
            self._ensure_xstack()
            rows = jax.tree.map(data_rows, self._xstack)
            self._sweep_data[key] = jax.device_put(rows, sharding)
            spans.count("batches.resident_bytes", spans.host_nbytes(rows))
        return self._sweep_data[key]

    def sweep_batches(self, st: SweepState):
        """One round's (E, U, epochs*nb, bs) int32 index tensor, from
        each lane's and user's permutation draws: the round program
        gathers the batches out of the resident data stack
        (``_resident_data``) with it (the data is read-only and shared;
        only the index tensor is per-lane)."""
        big = self._draw_sweep_big(st)
        spans.count("batches.device_gathers")
        return big.astype(np.int32).reshape(
            big.shape[:2] + (-1, self._batch_size))

    def sweep_train(self, st: SweepState, idx,
                    need_priority: bool) -> SweepTrainResult:
        """Dispatch ONE jitted train call for all E lanes on
        ``sweep_batches``'s index tensor and the resident data stack;
        the incoming stack is donated into the trained stack (residency
        chain)."""
        if spans.on:
            spans.count("batches.h2d_bytes", spans.host_nbytes(idx))
        E = st.num_lanes
        data = self._resident_data(E)
        stack, st.stack = st.stack, None      # donated below
        if st.obj is not None:
            key = (E, st.obj.use_h)
            rnd = (self._sweep_obj_round.get(key)
                   or self._build_sweep_obj_round(*key))
            prox = jnp.asarray(st.obj.prox)
            if st.obj.use_h:
                trained, loss_u, prios = rnd(stack, data, idx, prox, st.h,
                                             bool(need_priority))
            else:
                trained, loss_u, prios = rnd(stack, data, idx, prox,
                                             bool(need_priority))
        else:
            _, rnd, _ = self._sweep_fns[E]
            trained, loss_u, prios = rnd(stack, data, idx,
                                         bool(need_priority))
        return SweepTrainResult(trained=trained, losses=loss_u,
                                priorities=prios)

    def sweep_merge(self, st: SweepState, tr: SweepTrainResult,
                    idx: np.ndarray, w: np.ndarray, merge_ctx=None,
                    uids=None, attempts=None) -> None:
        """Dispatch the batched compact merge; the trained stack is
        donated in, and the merged (glob, stack) become the resident
        device state for the next round.

        ``idx`` / ``w``: (E, k_pad) per-lane row indices into the
        trained stack (user ids on the dense sweep, positions on the
        sparse one) + compact Eq. 1 weights, zero-padded. ``merge_ctx``
        is the sweep MergeContext (stacked (E, U) coeffs / (E,) sigmas
        / (E, 2) keys) routing every lane through the AirComp program;
        ``uids`` then carries the (E, k_pad) USER ids backing each
        compact slot (== idx on the dense sweep) for the host-side
        coefficient gather. ``attempts``: the per-lane attempt-winner
        (uids, positions) pair routed to the objective merge when the
        sweep carries an ObjectiveTable (ignored otherwise)."""
        trained, tr.trained = tr.trained, None
        if merge_ctx is None and st.obj is not None:
            self._dispatch_obj_sweep_merge(st, trained, idx, w, attempts)
            return
        if merge_ctx is None:
            if self._mode == "sparse":
                mrg = (self._sweep_sparse_fns.get(st.num_lanes)
                       or self._build_sweep_sparse_fns(st.num_lanes))[2]
            else:
                _, _, mrg = self._sweep_fns.get(st.num_lanes) or \
                    self._build_sweep_fns(st.num_lanes)
            st.glob, st.stack = mrg(trained, jnp.asarray(idx),
                                    jnp.asarray(w), st.glob)
            return
        mrg = (self._sweep_air_fns.get(st.num_lanes)
               or self._build_sweep_air(st.num_lanes))
        E = st.num_lanes
        coeffs = np.asarray(merge_ctx.coeffs, np.float32)[
            np.arange(E)[:, None], np.asarray(uids, np.int64)]
        st.glob, st.stack = mrg(
            trained, jnp.asarray(idx), jnp.asarray(w),
            jnp.asarray(coeffs),
            jnp.asarray(merge_ctx.noise_sigma, jnp.float32),
            merge_ctx.key, st.glob)

    def _build_sweep_air(self, E: int):
        """AirComp twin of the sweep merge: vmap the per-leaf noisy
        superposition over the lane axis (per-lane compact winner rows,
        power-control coeffs, receiver sigma and noise key), with the
        same all-zero-alpha keep-old-global guard and donation chain as
        the digital merge."""
        U, uk = self.num_users, self._use_kernel
        if (self._mesh is not None and sweep_shardable(E, U, self._mesh)):
            uk = uk and self._mesh.size == 1

        def one_lane(trained, idx, alphas, coeffs, sigma, key):
            leaves, treedef = jax.tree.flatten(trained)
            merged = []
            for i, leaf in enumerate(leaves):
                rows = jnp.take(leaf, idx, axis=0)
                noise = sigma * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape[1:],
                    jnp.float32)
                merged.append(kops.aircomp_combine(
                    rows, alphas, coeffs, noise, use_kernel=uk))
            return jax.tree.unflatten(treedef, merged)

        def sweep_merge_air(trained, idx, alphas, coeffs, sigmas, keys,
                            old_glob):
            merged = jax.vmap(one_lane)(trained, idx, alphas, coeffs,
                                        sigmas, keys)
            has = alphas.sum(axis=1) > 0                      # (E,)
            glob = jax.tree.map(
                lambda m, o: jnp.where(
                    has.reshape((E,) + (1,) * (m.ndim - 1)), m, o),
                merged, old_glob)
            stack = jax.tree.map(
                lambda g, tr: jnp.broadcast_to(g[:, None], tr.shape),
                glob, trained)
            return glob, stack

        fn = jax.jit(sweep_merge_air, donate_argnums=(0, 6))
        self._sweep_air_fns[E] = fn
        return fn

    def sweep_extract(self, tr: SweepTrainResult, e: int, u: int):
        """Lane e / row u's trained params as freshly materialized
        arrays (the trained stack is donated into the merge) — the
        sweep twin of ``extract_local`` for stale-upload capture. On
        the sparse sweep ``u`` is a compact POSITION, not a user id."""
        return jax.tree.map(lambda p: p[e, u], tr.trained)

    # ------------------------------------ sweep twin of the sparse path
    def sweep_sparse_capable(self) -> bool:
        """Sparse sweeps need exactly what the single sparse path
        needs: round_mode='sparse' (k_max set) + a rectangular cohort."""
        return self.sparse_capable()

    def _gather_sweep_rows(self, rows, big_rows):
        """(E, R, ep*nb, bs, ...) round batches: one lane-wise fancy
        index of the shared (U, n, ...) data stack. ``rows`` holds the
        user ids, broadcastable against ``big_rows``'s (E, R, T) draw
        tensor."""
        E, R = big_rows.shape[0], big_rows.shape[1]
        bs, nb, ep = self._batch_size, self._nb, self._local_epochs
        with spans.span("fl.round.batches.gather"):
            return jax.tree.map(
                lambda leaf: leaf[rows, big_rows].reshape(
                    (E, R, ep * nb, bs) + leaf.shape[2:]), self._xstack)

    def _build_sweep_sparse_fns(self, E: int):
        """(bcast_k, round, merge, prepass) jits for E lanes over the
        compact (E, K_max, ...) winner stack — the dense sweep programs
        one axis down on the user dimension. Unsharded: K_max rows are
        too few to split usefully across a mesh."""
        K = self._k_max
        self._ensure_xstack()
        nb, epoch_run = self._nb, self._epoch_run
        uk = self._use_kernel

        def lane_prios(tr, g):
            return stacked_model_priorities(tr, g, use_kernel=uk)

        def bcast_k(g):
            return jax.tree.map(
                lambda p: jnp.broadcast_to(p[:, None],
                                           (E, K) + p.shape[1:]), g)

        def round_fn(stack, batched):
            glob = jax.tree.map(lambda p: p[:, 0], stack)
            trained, losses = jax.vmap(jax.vmap(epoch_run))(stack, batched)
            loss_k = losses[:, :, -nb:].mean(axis=2)          # (E, K)
            prios = jax.vmap(lane_prios)(trained, glob)
            return trained, loss_k, prios

        def prepass_chunk(glob, batched):
            C = jax.tree.leaves(batched)[0].shape[1]
            stack = jax.tree.map(
                lambda p: jnp.broadcast_to(p[:, None],
                                           (E, C) + p.shape[1:]), glob)
            trained, losses = jax.vmap(jax.vmap(epoch_run))(stack, batched)
            loss_c = losses[:, :, -nb:].mean(axis=2)
            prios = jax.vmap(lane_prios)(trained, glob)
            return loss_c, prios

        def sweep_merge(trained, idx, w, old_glob):
            def one(tr_e, i_e, w_e, g_e):
                return jax.tree.map(
                    lambda l, g: kops.gather_combine(l, i_e, w_e, g,
                                                     use_kernel=uk),
                    tr_e, g_e)
            glob = jax.vmap(one)(trained, idx, w, old_glob)
            stack = jax.tree.map(
                lambda g, tr: jnp.broadcast_to(g[:, None], tr.shape),
                glob, trained)
            return glob, stack

        fns = (jax.jit(bcast_k),
               jax.jit(round_fn, donate_argnums=0),
               jax.jit(sweep_merge, donate_argnums=(0, 3)),
               jax.jit(prepass_chunk))
        self._sweep_sparse_fns[E] = fns
        return fns

    def _build_sweep_sparse_obj(self, E: int, use_h: bool):
        """(round, prepass) objective twins of the sparse sweep jits:
        same compact shapes, objective local steps under per-lane (E,)
        prox, the h-carrying variants taking the gathered winner h rows
        as an extra traced argument. The merge is NOT here — the
        objective sweep merge program is shared with the dense sweep
        (``_build_sweep_obj_merge``; jit re-specializes by shape)."""
        self._ensure_xstack()
        nb, uk = self._nb, self._use_kernel
        obj_run = objective_epoch_scan(self._loss_fn, self._lr, use_h, uk)

        def lane_prios(tr, g):
            return stacked_model_priorities(tr, g, use_kernel=uk)

        def train_rows(stack, batched, glob, prox, h_rows):
            if use_h:
                return jax.vmap(
                    lambda s, b, g, p, hh: jax.vmap(
                        obj_run, in_axes=(0, 0, None, None, 0))(
                            s, b, g, p, hh))(stack, batched, glob,
                                             prox, h_rows)
            return jax.vmap(
                lambda s, b, g, p: jax.vmap(
                    obj_run, in_axes=(0, 0, None, None))(s, b, g, p))(
                stack, batched, glob, prox)

        def _round_body(stack, batched, prox, h_rows):
            glob = jax.tree.map(lambda p: p[:, 0], stack)
            trained, losses = train_rows(stack, batched, glob, prox,
                                         h_rows)
            loss_k = losses[:, :, -nb:].mean(axis=2)
            prios = jax.vmap(lane_prios)(trained, glob)
            return trained, loss_k, prios

        def _prepass_body(glob, batched, prox, h_rows):
            C = jax.tree.leaves(batched)[0].shape[1]
            stack = jax.tree.map(
                lambda p: jnp.broadcast_to(p[:, None],
                                           (E, C) + p.shape[1:]), glob)
            trained, losses = train_rows(stack, batched, glob, prox,
                                         h_rows)
            loss_c = losses[:, :, -nb:].mean(axis=2)
            prios = jax.vmap(lane_prios)(trained, glob)
            return loss_c, prios

        if use_h:
            round_fn, prepass = _round_body, _prepass_body
        else:
            round_fn = lambda stack, batched, prox: _round_body(
                stack, batched, prox, None)
            prepass = lambda glob, batched, prox: _prepass_body(
                glob, batched, prox, None)
        fns = (jax.jit(round_fn, donate_argnums=0), jax.jit(prepass))
        self._sweep_sparse_obj[(E, use_h)] = fns
        return fns

    def sweep_sparse_init(self, init_params, seeds: Sequence[int],
                          objectives=None) -> SweepState:
        """SweepState with NO cohort stack: (E, ...) lane globals + the
        per-lane client streams (the dense sweep's exact seeding rule);
        the compact (E, K_max, ...) winner stack only materializes
        inside each round."""
        if not self.sweep_sparse_capable():
            raise ValueError(
                "sparse sweep needs round_mode='sparse' (k_max set) "
                "and a rectangular cohort")
        E = len(seeds)
        self._sweep_sparse_fns.get(E) or self._build_sweep_sparse_fns(E)
        glob = jax.tree.map(
            lambda p: jnp.broadcast_to(jnp.asarray(p)[None],
                                       (E,) + np.shape(p)), init_params)
        rngs = [[client_rng(s, u) for u in range(self.num_users)]
                for s in seeds]
        st = SweepState(num_lanes=E, glob=glob, stack=None, rngs=rngs)
        self._attach_sweep_objective(st, objectives, init_params)
        return st

    def sweep_sparse_priorities(self, st: SweepState,
                                need_priority: bool):
        """(E, U) pre-selection Eq. 2 across every lane (+ (E, U)
        prepass losses, or None) — the sweep twin of
        ``sparse_priorities``, same prepass/stale split and the same
        bit-parity contract per lane."""
        E, U = st.num_lanes, self.num_users
        fns = (self._sweep_sparse_fns.get(E)
               or self._build_sweep_sparse_fns(E))
        if self._sparse_priority == "stale":
            if not need_priority:
                return np.ones((E, U)), None
            if self._sweep_stale_prios.get(E) is None:
                self._sweep_stale_prios[E] = np.ones((E, U), np.float64)
            return self._sweep_stale_prios[E].copy(), None
        self._pending_sweep_big = big = self._draw_sweep_big(st)
        if not need_priority:
            return np.ones((E, U)), None
        C = max(1, min(self._sparse_chunk, U))
        losses = np.empty((E, U))
        prios = np.empty((E, U))
        if st.obj is not None:
            key = (E, st.obj.use_h)
            pfn = (self._sweep_sparse_obj.get(key)
                   or self._build_sweep_sparse_obj(*key))[1]
            prox = jnp.asarray(st.obj.prox)
        for lo in range(0, U, C):
            rows = np.arange(lo, min(lo + C, U))
            batched = self._gather_sweep_rows(rows[None, :, None],
                                              big[:, rows])
            if spans.on:
                spans.count("batches.h2d_bytes", spans.host_nbytes(batched))
            if st.obj is None:
                l, p = fns[3](st.glob, batched)
            elif st.obj.use_h:
                hc = jax.tree.map(
                    lambda hh: hh[:, lo:lo + len(rows)], st.h)
                l, p = pfn(st.glob, batched, prox, hc)
            else:
                l, p = pfn(st.glob, batched, prox)
            losses[:, lo:lo + len(rows)] = np.asarray(l, np.float64)
            prios[:, lo:lo + len(rows)] = np.asarray(p, np.float64)
        return prios, losses

    def sweep_sparse_train(self, st: SweepState,
                           winners_all) -> SweepTrainResult:
        """Compact winner training for every lane at once:
        ``winners_all[e]`` is lane e's delivery-ordered winner list.
        The returned arrays are (E, K_max) POSITION-indexed (not
        user-indexed — the sparse lane runner owns the mapping); pad
        rows retrain row 0's gather and ride with zero merge weight."""
        E, U, K = st.num_lanes, self.num_users, self._k_max
        fns = (self._sweep_sparse_fns.get(E)
               or self._build_sweep_sparse_fns(E))
        big, self._pending_sweep_big = self._pending_sweep_big, None
        rows = np.zeros((E, K), np.int64)
        for e, ws in enumerate(winners_all):
            if len(ws) > K:
                raise ValueError(f"{len(ws)} winners exceed k_max={K}")
            rows[e, :len(ws)] = [int(u) for u in ws]
        if big is not None:
            big_rows = big[np.arange(E)[:, None], rows]
        else:
            # "stale" mode: only the winners' streams advance; pad rows
            # ride on index 0 (example-0 batches, zero-weight)
            bs, nb, ep = self._batch_size, self._nb, self._local_epochs
            n = self.clients[0].num_examples
            take = nb * bs
            big_rows = np.zeros((E, K, ep * take), np.int64)
            with spans.span("fl.round.batches.draw"):
                for e, ws in enumerate(winners_all):
                    for j, u in enumerate(ws):
                        for k in range(ep):
                            big_rows[e, j, k * take:(k + 1) * take] = \
                                st.rngs[e][int(u)].permutation(n)[:take]
        batched = self._gather_sweep_rows(rows[:, :, None], big_rows)
        spans.count("batches.host_gathers")
        if spans.on:
            spans.count("batches.h2d_bytes", spans.host_nbytes(batched))
        stack = st.stack if st.stack is not None else fns[0](st.glob)
        st.stack = None
        if st.obj is not None:
            key = (E, st.obj.use_h)
            rfn = (self._sweep_sparse_obj.get(key)
                   or self._build_sweep_sparse_obj(*key))[0]
            prox = jnp.asarray(st.obj.prox)
            if st.obj.use_h:
                # pad rows gather user 0's h — zero-weight, discarded
                h_rows = jax.tree.map(
                    lambda hh: hh[np.arange(E)[:, None], rows], st.h)
                trained, loss_k, prios_k = rfn(stack, batched, prox,
                                               h_rows)
            else:
                trained, loss_k, prios_k = rfn(stack, batched, prox)
        else:
            trained, loss_k, prios_k = fns[1](stack, batched)
        if self._sparse_priority == "stale":
            cache = self._sweep_stale_prios.get(E)
            if cache is None:
                cache = self._sweep_stale_prios[E] = \
                    np.ones((E, U), np.float64)
            pk = np.asarray(prios_k, np.float64)
            for e, ws in enumerate(winners_all):
                if ws:
                    cache[e, rows[e, :len(ws)]] = pk[e, :len(ws)]
        return SweepTrainResult(trained=trained, losses=loss_k,
                                priorities=prios_k)

    def _build_sweep_fault(self, key):
        """Robust-guard twin of the sweep merge: ``robust_merge``
        vmapped over the lane axis, same donation chain and the same
        keep-old-global guard (a lane with zero surviving mass —
        winnerless, all-quarantined, or λ=0 stale-only — keeps its
        global, per-lane)."""
        E, M, quarantine, clip = key
        U, uk = self.num_users, self._use_kernel
        if self._mesh is not None and sweep_shardable(E, U, self._mesh):
            uk = uk and self._mesh.size == 1

        def one_lane(tr_e, i_e, w_e, c_e, g_e, *stale_e):
            stale, stale_w = stale_e if M else (None, None)
            rows = jax.tree.map(
                lambda l: jnp.take(l, i_e, axis=0), tr_e)
            return robust_merge(rows, w_e, c_e, g_e, stale, stale_w,
                                quarantine=quarantine, clip_norm=clip,
                                use_kernel=uk)

        def sweep_fault(trained, idx, weights, corrupt, old_glob,
                        *stale_args):
            glob, nq = jax.vmap(one_lane)(trained, idx, weights,
                                          corrupt, old_glob, *stale_args)
            stack = jax.tree.map(
                lambda g, t: jnp.broadcast_to(g[:, None], t.shape),
                glob, trained)
            return glob, stack, nq

        fn = jax.jit(sweep_fault, donate_argnums=(0, 4))
        self._sweep_fault_fns[key] = fn
        return fn

    def sweep_merge_faults(self, st: SweepState, tr: SweepTrainResult,
                           idx: np.ndarray, weights: np.ndarray,
                           corrupt: np.ndarray,
                           stale_stack=None, stale_weights=None, *,
                           quarantine: bool = True,
                           clip_norm: float = 0.0) -> np.ndarray:
        """Dispatch the robust-guard sweep merge.

        ``idx``: (E, k_pad) per-lane compact row indices into the
        trained stack (pads index row 0); ``weights`` / ``corrupt``:
        (E, k_pad) f32 host arrays (joint fresh-mass weights from
        ``fault_alphas`` gathered down to the compact slots, and per-row
        corruption factors — pads ride weight 0 / corrupt 1.0, the
        bit-level passthrough); ``stale_stack``: (E, M, ...) stacked
        stale-update pytree, rows beyond a lane's stale count
        zero-padded and riding with zero weight in ``stale_weights``
        (E, M). Returns the (E,) per-lane quarantine counts."""
        trained, tr.trained = tr.trained, None
        M = (0 if stale_weights is None
             else int(np.shape(stale_weights)[1]))
        key = (st.num_lanes, M, bool(quarantine), float(clip_norm))
        fn = self._sweep_fault_fns.get(key) or self._build_sweep_fault(key)
        args = [trained, jnp.asarray(idx, jnp.int32),
                jnp.asarray(weights, jnp.float32),
                jnp.asarray(corrupt, jnp.float32), st.glob]
        if M:
            args += [stale_stack,
                     jnp.asarray(stale_weights, jnp.float32)]
        st.glob, st.stack, nq = fn(*args)
        return np.asarray(nq)

    # ---------------------------------------- checkpoint hooks (§8)
    def client_stream_states(self):
        return [generator_state(c._rng) for c in self.clients]

    def restore_client_streams(self, states) -> None:
        if states is None:
            return
        for c, s in zip(self.clients, states):
            restore_generator(c._rng, s)

    def sweep_stream_states(self, st: SweepState):
        """Per-lane / per-user batch-stream snapshots. The engine takes
        this BEFORE drawing the next round's batches, so a resumed run
        replays the exact permutations the uninterrupted run drew."""
        return [[generator_state(g) for g in lane] for lane in st.rngs]

    def sweep_restore(self, glob, stream_states, seeds: Sequence[int],
                      objectives=None, objective_state=None) -> SweepState:
        """Rebuild a ``SweepState`` from checkpoint payload: ``glob``
        the host copy of the (E, ...) stacked lane globals,
        ``stream_states`` the matching ``sweep_stream_states``
        snapshot, ``seeds`` the lane seeds (stream identity only — the
        restored positions override the origin)."""
        if not self.sweep_capable():
            raise ValueError(
                "sweep needs round_mode='fused' and a rectangular "
                "cohort (equal per-user example counts)")
        E = len(seeds)
        self._sweep_fns.get(E) or self._build_sweep_fns(E)
        g = jax.tree.map(jnp.asarray, glob)
        # rebuild the cohort stack exactly as a post-merge round leaves
        # it: every user row = the lane's global
        stack = jax.tree.map(
            lambda p: jnp.broadcast_to(
                p[:, None], (E, self.num_users) + p.shape[1:]), g)
        rngs = [[client_rng(s, u) for u in range(self.num_users)]
                for s in seeds]
        for lane_rngs, lane_states in zip(rngs, stream_states):
            for gen, gs in zip(lane_rngs, lane_states):
                restore_generator(gen, gs)
        st = SweepState(num_lanes=E, glob=g, stack=stack, rngs=rngs)
        self._attach_sweep_objective(st, objectives,
                                     jax.tree.map(lambda p: p[0], g),
                                     payload=objective_state)
        return st

    def sweep_global(self, st: SweepState, e: int):
        """Lane e's current global params (for eval / extraction)."""
        return jax.tree.map(lambda p: p[e], st.glob)

    def sweep_adopt_streams(self, st: SweepState, e: int) -> None:
        """Adopt lane e's batch rng streams as the clients' own.

        A lane stream is the SAME seeded stream a client would have
        consumed through the per-round path (same seed rule, one
        permutation per epoch per round), so after an E=1 delegated
        ``run`` this hands the advanced generators back — continuing
        the engine per-round afterwards draws exactly where a pure
        per-round run would, instead of replaying from the origin."""
        for u, c in enumerate(self.clients):
            c._rng = st.rngs[e][u]


class SiloBackend(Backend):
    """Cross-silo path: one FL "user" per pod-scale silo.

    Wraps the silo round machinery: training + Eq. 2 priorities run
    once per round as a merge-free ``make_fl_round_step`` pass
    (vmapped over the silo axis on-device, zero cross-silo traffic);
    ``merge`` then applies ``make_silo_merge`` to the *already trained*
    local stack with the selection's alpha weights, so only winners'
    deltas cross the pod boundary. Because the whole cohort trains
    inside one fused step, ``trains_before_selection`` strategies still
    train every silo — selection gates only the merge traffic (exactly
    the quantity the paper meters).
    """

    def __init__(self, model_cfg, token_data: Sequence[np.ndarray], *,
                 lr: float = 1e-2, batch_size: int = 4,
                 long_context: bool = False, merge_dtype: str = "float32"):
        from repro.core.silo import (make_fl_round_step, make_silo_merge,
                                     stack_for_silos)
        self.num_users = len(token_data)
        self.heterogeneity = np.zeros(self.num_users)
        self._data = [np.asarray(d) for d in token_data]
        self._batch_size = batch_size
        self._stack = stack_for_silos
        self._train = jax.jit(make_fl_round_step(
            model_cfg, lr=lr, long_context=long_context, do_merge=False))
        merge_stacked = make_silo_merge(merge_dtype)
        self._merge = jax.jit(
            lambda state, local, alphas: merge_stacked(
                local, jax.tree.map(lambda p: p[0], state), alphas))

    def init_state(self, init_params):
        return self._stack(init_params, self.num_users)

    def num_examples(self, u):
        return len(self._data[u])

    def global_params(self, state):
        return jax.tree.map(lambda p: p[0], state)

    def _round_batch(self, t):
        B = self._batch_size
        rows = []
        for d in self._data:
            idx = np.arange(t * B, (t + 1) * B) % len(d)
            rows.append(d[idx])
        return {"tokens": jnp.asarray(np.stack(rows))}

    def train_round(self, state, t, train_ids, need_priority):
        batch = self._round_batch(t)
        # merge-free pass: per-silo losses + trained locals + priorities,
        # zero cross-silo traffic; the locals are kept for the merge step
        loss_vec, local, prios = self._train(
            state, batch, jnp.zeros((self.num_users,), jnp.float32))
        priorities = np.ones(self.num_users)
        if need_priority:
            priorities = np.asarray(prios, np.float64).copy()
        loss_np = np.asarray(loss_vec)
        return TrainResult(losses={u: float(loss_np[u]) for u in train_ids},
                           priorities=priorities, local_handle=local)

    def merge(self, state, train_result, winners, merge_ctx=None,
              fault_ctx=None, attempts=None):
        if merge_ctx is not None:
            raise ValueError(
                "SiloBackend implements only the digital cross-pod "
                "merge; merge_backend='aircomp' needs HostBackend")
        if fault_ctx is not None:
            raise ValueError(
                "SiloBackend implements no robust merge guard; "
                "FaultSpec merge guards need HostBackend")
        alphas = winner_alphas(self.num_users, winners,
                               [self.num_examples(u) for u in winners])
        return self._merge(state, train_result.local_handle,
                           jnp.asarray(alphas))
