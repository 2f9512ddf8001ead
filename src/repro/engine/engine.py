"""FLEngine — the single public orchestrator for FL rounds (Fig. 1).

One round, regardless of strategy or backend:

  1. counter refrain mask (Step 4) — upload shares are computed ONCE
     per round and passed through (mask + SelectionContext.counter_values);
  2. if the strategy selects before training (capability flag, e.g.
     classic FedAvg), select now and train only winners — otherwise
     train everyone (Step 2) and compute Eq. 2 priorities (Step 3);
  3. strategy.select over the SelectionContext (Step 4/5 contention);
  4. backend.merge of the winners (Eq. 1 / the gated collective);
  5. counter + history update — including the contention's collision
     and airtime stats, which pre-engine code silently dropped.

There is deliberately no strategy-name branching here: behaviour
differences ride entirely on the Strategy capability flags and the
Backend contract.

**Sweeps are the native unit** (DESIGN.md §5): ``run_sweep`` stacks E
independent experiment cells into one device program — the backend's
fused round step vmapped over a leading experiment axis — and runs all
E host-side selection layers per round through one batched pass
(``select_grouped`` -> ``contend_batch``). The round loop is a small
async pipeline: while the device trains round t, the host pre-draws
round t+1's epoch batches; only the tiny (E, U) priority matrix syncs
per round, and the next train call is dispatched before the host
settles round t's bookkeeping. ``run`` on a sweep-capable backend is
the E=1 special case of the same code path.

Sweep lanes are bit-faithful to sequential runs: each lane owns its
strategy instance (its contention rng), its engine rng, its fairness
counter column, and its per-user batch streams, all seeded from the
lane's spec — winner sequences match E separate ``run`` calls
winner-for-winner (tests/test_sweep.py). One documented exception:
``trains_before_selection`` lanes train the full cohort inside the
sweep step (selection still gates the merge, like SiloBackend), so
their loss traces cover all users, not just the pre-selected winners —
winners/selections/merged params are unaffected.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from repro.channel.model import ChannelModel, MergeContext
from repro.checkpoint.fl_state import (generator_state, load_fl_checkpoint,
                                       restore_generator, run_fingerprint,
                                       save_fl_checkpoint)
from repro.core.counter import FairnessCounter, SweepFairnessCounter
from repro.core.rngs import channel_noise_entropy, engine_rng, strategy_seed
from repro.engine import spans
from repro.engine.backends import Backend, compact_weights
from repro.engine.registry import create_strategy, select_grouped
from repro.engine.spec import ExperimentSpec, SweepSpec
from repro.engine.types import (FLHistory, SelectionContext, SweepResult,
                                TrainResult)
from repro.faults.injectors import FaultInjector
from repro.faults.robust import FaultMergeContext, fault_alphas


class _Lane:
    """Host-side state of ONE experiment cell inside a (possibly E=1)
    sweep: spec, strategy instance, engine rng, channel model, history.
    The fairness counter lives outside (one vectorized
    ``SweepFairnessCounter`` row per lane) so Step 5 stays a single
    numpy update across lanes."""

    __slots__ = ("spec", "strategy", "rng", "channel", "faults", "history")

    def __init__(self, spec: ExperimentSpec, num_users: int, *,
                 strategy=None, rng=None, channel=None, faults=None):
        self.spec = spec
        # engine rng and strategy/simulator rng are INDEPENDENT spawn
        # children of the spec seed (core.rngs) — seeding both with the
        # raw seed used to hand Eq. 3 backoff draws and collision
        # redraws the identical stream
        self.strategy = strategy if strategy is not None else \
            create_strategy(spec.strategy, csma_config=spec.csma,
                            seed=strategy_seed(spec.seed),
                            contention_backend=spec.contention_backend,
                            **spec.strategy_options)
        self.rng = rng if rng is not None else engine_rng(spec.seed)
        # channel streams are further spawn children of the spec seed,
        # so building (or not building) the model never perturbs the
        # engine / strategy / client streams above
        self.channel = channel if channel is not None else (
            ChannelModel(spec.channel, num_users, spec.seed)
            if spec.channel is not None else None)
        # fault streams are stream-4 spawn children of the spec seed —
        # same opt-in rule as the channel: building the injector never
        # perturbs the streams above
        self.faults = faults if faults is not None else (
            FaultInjector(spec.faults, spec.seed, cw_base=spec.cw_base,
                          tx_slots=spec.csma.tx_slots)
            if spec.faults is not None else None)
        self.history = FLHistory(
            selections=np.zeros(num_users, np.int64))


def _gate_round(channel, attempted):
    """PER-gate one lane's attempted uploads: (delivered, failures)."""
    if channel is None or not attempted:
        return list(attempted), 0
    delivered = channel.gate(attempted)
    return delivered, len(attempted) - len(delivered)


def _record_time(history, spec, channel, elapsed_slots, attempted,
                 retry_slots: int = 0, retry_uploads=()):
    """Append the round's wall-clock / energy accounting: contention
    slots at ``slot_duration_s`` plus, with a channel, the attempted
    uploads' payload airtime and transmit energy. HARQ retransmissions
    charge their backoff + tx slots (``retry_slots``) and, per retry
    attempt, another payload airtime / energy unit (``retry_uploads``,
    one uid per attempt) — a lost retry still spent the air."""
    secs = (elapsed_slots + retry_slots) * spec.slot_seconds()
    energy = 0.0
    if channel is not None:
        secs += channel.round_airtime_s(attempted)
        energy = channel.round_energy_j(attempted)
        if len(retry_uploads):
            secs += channel.round_airtime_s(retry_uploads)
            energy += channel.round_energy_j(retry_uploads)
    history.round_seconds.append(secs)
    history.cumulative_seconds.append(
        (history.cumulative_seconds[-1] if history.cumulative_seconds
         else 0.0) + secs)
    history.round_energy_j.append(energy)


class FLEngine:
    """One FL run (or one E-cell sweep): spec x strategy (registry) x
    backend."""

    def __init__(self, spec: ExperimentSpec, backend: Backend, init_params,
                 eval_fn: Optional[Callable] = None):
        self.spec = spec
        self.backend = backend
        self.eval_fn = eval_fn
        self.num_users = backend.num_users
        self.counter = FairnessCounter(self.num_users,
                                       spec.counter_threshold)
        self.strategy = create_strategy(
            spec.strategy, csma_config=spec.csma,
            seed=strategy_seed(spec.seed),
            contention_backend=spec.contention_backend,
            **spec.strategy_options)
        self._rng = engine_rng(spec.seed)
        self.channel = (ChannelModel(spec.channel, self.num_users,
                                     spec.seed)
                        if spec.channel is not None else None)
        self.faults = (FaultInjector(spec.faults, spec.seed,
                                     cw_base=spec.cw_base,
                                     tx_slots=spec.csma.tx_slots)
                       if spec.faults is not None else None)
        self._init_params = init_params
        self.state = backend.init_state(init_params)
        obj = spec.objective
        if obj is not None and not obj.is_plain:
            if not backend.objective_active():
                raise ValueError(
                    "spec.objective is non-plain but the backend was "
                    "built without it; construct HostBackend with "
                    "objective=spec.objective (build_host_engine wires "
                    "this automatically)")
            if self.strategy.trains_before_selection:
                raise ValueError(
                    "non-plain objectives need the full-cohort fused/"
                    "sparse round programs; trains_before_selection "
                    f"strategy {spec.strategy!r} runs partial-cohort "
                    "rounds")

    # ------------------------------------------------------------------
    @property
    def global_params(self):
        return self.backend.global_params(self.state)

    def _context(self, priorities: np.ndarray, participating: np.ndarray,
                 t: int, shares: np.ndarray) -> SelectionContext:
        return SelectionContext(
            priorities=priorities, participating=participating,
            k_target=self.spec.k_per_round, rng=self._rng,
            cw_base=self.spec.cw_base,
            counter_values=shares,
            heterogeneity=self.backend.heterogeneity,
            snr_db=(self.channel.snr_db if self.channel is not None
                    else None),
            round_index=t)

    @staticmethod
    def _lane_merge_ctx(spec, channel, t: int, num_users: int):
        """AirComp merge inputs for one lane's round-t merge, or None
        for the digital ("fedavg") Eq. 1 — the None path is the
        pre-channel program, untouched (bit-identity contract)."""
        if spec.merge_backend != "aircomp":
            return None
        import jax
        if channel is not None:
            coeffs, sigma = channel.aircomp_coeffs()
            entropy = channel.noise_entropy
        else:
            # channel-less aircomp lane: perfect superposition
            coeffs = np.ones(num_users, np.float32)
            sigma = 0.0
            entropy = channel_noise_entropy(spec.seed)
        key = jax.random.fold_in(jax.random.PRNGKey(entropy), t)
        return MergeContext(coeffs=coeffs, noise_sigma=sigma, key=key)

    def _lane_fault_ctx(self, spec, rf, stale_in, merged_now):
        """Robust-merge inputs for one lane's round, or None when the
        merge program stays the plain Eq. 1 (faults off, or
        failure-only fault modes that never alter the merge math)."""
        fs = spec.faults
        if fs is None or not fs.merge_guarded:
            return None
        weights, stale_w = fault_alphas(
            self.num_users, merged_now,
            [self.backend.num_examples(u) for u in merged_now],
            [n for _, _, n in stale_in], fs.staleness_discount)
        corrupt = np.ones(self.num_users, np.float32)
        for u, fac in rf.corrupt.items():
            corrupt[int(u)] = fac
        stale = [(p, float(w))
                 for (_, p, _), w in zip(stale_in, stale_w)]
        return FaultMergeContext(weights=weights, corrupt=corrupt,
                                 quarantine=fs.quarantine,
                                 clip_norm=fs.clip_norm, stale=stale)

    # ------------------------------------------------------------------
    def run_round(self, t: int, history: FLHistory) -> List[int]:
        """One single-experiment round through the per-lane backend
        contract (train_round/merge) — the path for silo, stacked,
        ragged and partial-cohort rounds, and the sequential reference
        the sweep path is pinned against."""
        spec, strat = self.spec, self.strategy
        if self.channel is not None:
            self.channel.begin_round()     # block fading, pre-selection
        # upload shares: computed once, reused for the refrain mask AND
        # the SelectionContext (they used to be derived independently)
        shares = self.counter.values()
        participating = (self.counter.participating(shares)
                         if spec.use_counter
                         else np.ones(self.num_users, bool))
        if not participating.any():      # degenerate threshold: reset mask
            participating = np.ones(self.num_users, bool)

        if strat.trains_before_selection:
            sel = strat.select(self._context(
                np.ones(self.num_users), participating, t, shares))
            train_ids = list(sel.winners)
            tr = self.backend.train_round(
                self.state, t, train_ids,
                need_priority=strat.uses_priority)
        elif self.backend.sparse_capable():
            # winner-sparse round (DESIGN.md §9): Eq. 2 priorities come
            # BEFORE selection (exact chunked prepass, or the stale
            # cache), then only the contention winners train in the
            # compact (K_max, ...) step. Loss traces: prepass rounds
            # report the full-cohort prepass losses (the dense path's
            # numbers); stale rounds report winner losses only.
            train_ids = list(range(self.num_users))
            prios, pre_losses = self.backend.sparse_priorities(
                self.state, strat.uses_priority)
            sel = strat.select(self._context(
                prios, participating, t, shares))
            tr = self.backend.sparse_train(
                self.state, [int(u) for u in sel.winners])
            tr = TrainResult(
                losses=(pre_losses if pre_losses is not None
                        else tr.losses),
                priorities=prios, local_handle=tr.local_handle)
        else:
            train_ids = list(range(self.num_users))
            tr = self.backend.train_round(
                self.state, t, train_ids,
                need_priority=strat.uses_priority)
            sel = strat.select(self._context(
                tr.priorities, participating, t, shares))

        # contention winners are upload ATTEMPTS; the channel (when
        # enabled) gates which of them actually reach the Eq. 1 merge.
        # Counters / selections / uploads_total see the attempt (the
        # airtime was spent either way); merge weights see deliveries.
        # With faults on, the injector post-processes the gate's output:
        # ``delivered`` then records the post-fault/post-retry arrivals
        # and ``upload_failures`` the losses that survived every retry.
        winners = [int(u) for u in sel.winners]
        faults = self.faults
        if faults is not None:
            faults.begin_round()            # burst-outage process
        delivered, failures = _gate_round(self.channel, winners)
        rf, stale_in, merged_now = None, [], delivered
        if faults is not None:
            rf = faults.process_uploads(
                winners, delivered,
                self.channel.per if self.channel is not None else None)
            delivered, failures = rf.arrived, len(rf.failed)
            merged_now = rf.merged_now
            stale_in = faults.pop_stale()
            # capture this round's stragglers BEFORE the merge donates
            # the trained handle
            for u in rf.stragglers:
                faults.push_stale(u, self.backend.extract_local(tr, u),
                                  self.backend.num_examples(u))
        # FedDyn's h-state is keyed to the round's ATTEMPT winners (they
        # trained, so their local h advanced even if the channel dropped
        # the upload) — such rounds still dispatch the merge, whose
        # all-zero-weight guard keeps the global while h updates
        needs_h = self.backend.objective_needs_h()
        if merged_now or stale_in or (winners and needs_h):
            fault_ctx = self._lane_fault_ctx(spec, rf, stale_in,
                                             merged_now)
            self.state = self.backend.merge(
                self.state, tr, merged_now,
                merge_ctx=self._lane_merge_ctx(spec, self.channel, t,
                                               self.num_users),
                fault_ctx=fault_ctx, attempts=winners)
            if fault_ctx is not None:
                history.quarantined_updates += int(fault_ctx.n_quarantined)
        if winners:
            self.counter.update(winners, len(winners))
            history.uploads_total += len(winners)
            for u in winners:
                history.selections[u] += 1
        history.winners.append(winners)
        history.delivered.append(delivered)
        history.upload_failures += failures
        history.collisions += sel.collisions
        retry_slots = rf.retry_slots if rf is not None else 0
        history.contention_slots += sel.elapsed_slots + retry_slots
        if rf is not None:
            history.retries += rf.retries
            history.dropped_clients += len(rf.crashed)
            history.stale_merges += len(stale_in)
        _record_time(history, spec, self.channel, sel.elapsed_slots,
                     winners, retry_slots=retry_slots,
                     retry_uploads=(rf.retry_uploads if rf is not None
                                    else ()))
        if strat.uses_priority:
            # one vectorized conversion — per-element float() is O(U)
            # Python overhead at 1e4+ users
            history.priorities.append(
                np.asarray(tr.priorities, np.float64)[train_ids].tolist())
        if tr.losses is not None and len(tr.losses):
            # dict (partial-cohort rounds) or dense (U,) vector (fused)
            vals = (list(tr.losses.values())
                    if isinstance(tr.losses, dict) else tr.losses)
            history.train_loss.append(float(np.mean(vals)))
        return winners

    # ------------------------------------------------------------------
    def run(self, verbose: bool = False, *,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0) -> FLHistory:
        """Run the spec's rounds. With ``checkpoint_dir`` set, the run
        persists its full host+device state every ``checkpoint_every``
        rounds (atomic file, DESIGN.md §8) and — when the directory
        already holds a checkpoint for THIS spec — resumes from it,
        bit-identically to the uninterrupted run."""
        spec = self.spec
        # The E=1 sweep delegation re-derives the per-user batch streams
        # from spec.seed, so it is only bit-faithful to the per-round
        # path on a PRISTINE engine (state untouched since init — after
        # any merged round the per-round path would continue consumed
        # client streams) whose backend was seeded with the same spec
        # seed. Anything else takes the per-lane loop.
        if (self.backend.sweep_capable()
                and not self.strategy.trains_before_selection
                and self.state is self._init_params
                and getattr(self.backend, "seed", None) == spec.seed):
            # E=1 special case of the sweep code path: same lane loop,
            # same device program shape, bound to THIS engine's
            # strategy/rng so repeated-attribute access stays coherent
            lane = _Lane(spec, self.num_users, strategy=self.strategy,
                         rng=self._rng, channel=self.channel,
                         faults=self.faults)
            result, st, counters = self._run_lanes(
                [lane], init_state=self.state, overlap=True,
                verbose=verbose, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every)
            self.state = self.backend.sweep_global(st, 0)
            self.counter.uploads[:] = counters.uploads[0]
            self.counter.total_merged = int(counters.total_merged[0])
            # the lane consumed spec-seeded batch streams; hand them to
            # the clients so continued per-round training picks up the
            # stream where a pure per-round run would be
            self.backend.sweep_adopt_streams(st, 0)
            self.backend.adopt_sweep_objective(st)
            return result.histories[0]

        # per-lane path: silo / stacked / ragged backends and
        # partial-cohort (trains_before_selection) rounds
        history = FLHistory(
            selections=np.zeros(self.num_users, np.int64))
        start = 0
        fp = run_fingerprint([spec], self.num_users)
        if checkpoint_dir is not None:
            payload = load_fl_checkpoint(checkpoint_dir)
            if payload is not None:
                history, start = self._load_run_payload(payload, fp)
        for t in range(start, spec.rounds):
            self.run_round(t, history)
            if self.eval_fn is not None and (
                    t % spec.eval_every == 0 or t == spec.rounds - 1):
                acc = float(self.eval_fn(self.global_params))
                history.accuracy.append(acc)
                history.eval_round.append(t)
                if verbose:
                    print(f"[{spec.strategy}] round {t:4d} "
                          f"acc {acc:.4f}"
                          + (f" loss {history.train_loss[-1]:.4f}"
                             if history.train_loss else ""))
            if (checkpoint_dir is not None and checkpoint_every > 0
                    and (t + 1) % checkpoint_every == 0
                    and t + 1 < spec.rounds):
                save_fl_checkpoint(checkpoint_dir,
                                   self._run_payload(fp, t, history))
        return history

    # ------------------------------------------- checkpoint plumbing
    def _run_payload(self, fp, t, history):
        import jax
        return {
            "kind": "run", "fingerprint": fp, "round": t,
            "state": jax.device_get(self.state),
            "history": history,
            "engine_rng": generator_state(self._rng),
            "strategy": (self.strategy._sim.state_dict()
                         if hasattr(self.strategy, "_sim") else None),
            "channel": (self.channel.state_dict()
                        if self.channel is not None else None),
            "faults": (self.faults.state_dict()
                       if self.faults is not None else None),
            "counter": self.counter.state_dict(),
            "client_streams": self.backend.client_stream_states(),
            # sparse "stale" runs carry last-trained Eq. 2 priorities
            # across rounds; None everywhere else
            "priority_cache": self.backend.priority_cache_state(),
            # server-opt moments + FedDyn h (DESIGN.md §10); None for
            # plain objectives
            "objective": self.backend.objective_state(),
        }

    def _load_run_payload(self, payload, fp):
        import jax
        import jax.numpy as jnp
        if payload["fingerprint"] != fp:
            raise ValueError(
                "checkpoint was written by a different experiment "
                "configuration; refusing to resume (point checkpoint_dir "
                "at a fresh directory or match the original spec)")
        if payload["kind"] != "run":
            raise ValueError(
                "checkpoint was written by the sweep path; resume it "
                "through the same sweep-capable configuration")
        self.state = jax.tree.map(jnp.asarray, payload["state"])
        restore_generator(self._rng, payload["engine_rng"])
        if payload["strategy"] is not None:
            self.strategy._sim.load_state_dict(payload["strategy"])
        if self.channel is not None and payload["channel"] is not None:
            self.channel.load_state_dict(payload["channel"])
        if self.faults is not None and payload["faults"] is not None:
            self.faults.load_state_dict(payload["faults"])
        self.counter.load_state_dict(payload["counter"])
        self.backend.restore_client_streams(payload["client_streams"])
        self.backend.restore_priority_cache(
            payload.get("priority_cache"))
        self.backend.restore_objective_state(payload.get("objective"))
        return payload["history"], payload["round"] + 1

    # ------------------------------------------------------- sweep path
    def run_sweep(self, sweep: Union[SweepSpec, Sequence[ExperimentSpec]],
                  *, overlap: Optional[bool] = None,
                  verbose: bool = False,
                  checkpoint_dir: Optional[str] = None,
                  checkpoint_every: int = 0) -> SweepResult:
        """Run E experiment cells as ONE stacked device program.

        ``sweep``: a ``SweepSpec`` or a plain sequence of
        ``ExperimentSpec`` cells (validated into one). Every cell starts
        from the engine's initial params and its own spec seed, exactly
        like E fresh sequential ``run`` calls. ``overlap`` overrides the
        sweep's async-pipeline flag (results are bit-identical either
        way; off is only useful for debugging and the pipeline bench).
        ``checkpoint_dir`` / ``checkpoint_every`` persist + resume the
        whole sweep (every lane's host state and the stacked device
        globals) exactly like ``run``'s flags.
        """
        if not isinstance(sweep, SweepSpec):
            sweep = SweepSpec(specs=list(sweep))
        if overlap is None:
            overlap = sweep.overlap
        lanes = [_Lane(spec, self.num_users) for spec in sweep.specs]
        sparse = getattr(self.backend, "sweep_sparse_capable",
                         lambda: False)()
        if not sparse and not self.backend.sweep_capable():
            raise ValueError(
                "run_sweep needs a sweep-capable backend (HostBackend "
                "round_mode='fused' or 'sparse' over a rectangular "
                "cohort); run the cells sequentially through "
                "FLEngine.run instead")
        with spans.span("fl.sweep", job=spans.next_job()):
            if sparse:
                # winner-sparse sweeps run the contention-first lane
                # loop: every lane selects, then ONE compact
                # (E, K_max, ...) train call covers all lanes' winners
                result, _, _ = self._run_lanes_sparse(
                    lanes, init_state=self._init_params, verbose=verbose,
                    labels=sweep.labels, checkpoint_dir=checkpoint_dir)
            else:
                result, _, _ = self._run_lanes(
                    lanes, init_state=self._init_params, overlap=overlap,
                    verbose=verbose, labels=sweep.labels,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every)
        return result

    # ------------------------------------------------------------------
    def _select_lanes(self, lanes, counters, prios64, t):
        """Host selection for all lanes: ONE shares/mask computation,
        one grouped (batched) select dispatch."""
        U = self.num_users
        shares = counters.values()                 # (E, U), once per round
        masks = counters.participating(shares)
        het = self.backend.heterogeneity
        ones = np.ones(U)
        strategies, ctxs = [], []
        for e, lane in enumerate(lanes):
            spec, strat = lane.spec, lane.strategy
            if lane.channel is not None:
                lane.channel.begin_round()         # block fading
            mask = (masks[e] if spec.use_counter
                    else np.ones(U, bool))
            if not mask.any():                     # degenerate threshold
                mask = np.ones(U, bool)
            prios = (prios64[e]
                     if strat.uses_priority
                     and not strat.trains_before_selection else ones)
            strategies.append(strat)
            ctxs.append(SelectionContext(
                priorities=prios, participating=mask,
                k_target=spec.k_per_round, rng=lane.rng,
                cw_base=spec.cw_base, counter_values=shares[e],
                heterogeneity=het,
                snr_db=(lane.channel.snr_db if lane.channel is not None
                        else None),
                round_index=t))
        sels = select_grouped(strategies, ctxs)
        winners_all = [[int(u) for u in sel.winners] for sel in sels]
        return winners_all, sels

    def _record_lane(self, lane, sel, winners, delivered, failures,
                     loss_row, prios_row, rf=None):
        h = lane.history
        if winners:
            h.uploads_total += len(winners)
            for u in winners:
                h.selections[u] += 1
        h.winners.append(winners)
        h.delivered.append(delivered)
        h.upload_failures += failures
        h.collisions += sel.collisions
        retry_slots = rf.retry_slots if rf is not None else 0
        h.contention_slots += sel.elapsed_slots + retry_slots
        if rf is not None:
            h.retries += rf.retries
            h.dropped_clients += len(rf.crashed)
        _record_time(h, lane.spec, lane.channel, sel.elapsed_slots,
                     winners, retry_slots=retry_slots,
                     retry_uploads=(rf.retry_uploads if rf is not None
                                    else ()))
        if (lane.strategy.uses_priority
                and not lane.strategy.trains_before_selection):
            h.priorities.append(prios_row.tolist())
        if np.size(loss_row):      # sparse stale + winnerless: no losses
            h.train_loss.append(float(np.mean(loss_row)))

    def _sweep_merge_ctx(self, lanes, t: int):
        """Stacked (E, ...) AirComp merge inputs, or None for the
        digital merge (``merge_backend`` is sweep-shared, so one check
        of the lead lane decides for all)."""
        if lanes[0].spec.merge_backend != "aircomp":
            return None
        import jax
        import jax.numpy as jnp
        U = self.num_users
        coeffs = np.ones((len(lanes), U), np.float32)
        sigmas = np.zeros(len(lanes), np.float32)
        keys = []
        for e, lane in enumerate(lanes):
            if lane.channel is not None:
                coeffs[e], sigmas[e] = lane.channel.aircomp_coeffs()
                entropy = lane.channel.noise_entropy
            else:
                entropy = channel_noise_entropy(lane.spec.seed)
            keys.append(jax.random.fold_in(
                jax.random.PRNGKey(entropy), t))
        return MergeContext(coeffs=coeffs, noise_sigma=sigmas,
                            key=jnp.stack(keys))

    def _sweep_merge_faults(self, lanes, st, tr, rfs, stales, fs, idx):
        """Assemble the compact (E, k_pad) joint fresh-weight /
        corruption matrices (``fault_alphas`` gathered down to each
        lane's merge slots; pads ride exact-zero weight and corruption
        1.0, the bit-level passthrough) and the zero-padded (E, M, ...)
        stale stack, then dispatch the robust sweep merge. ``idx`` is
        the (E, k_pad) row-index matrix into the trained stack, slot
        order = each lane's ``rf.merged_now`` delivery order. Returns
        the (E,) per-lane quarantine counts."""
        import jax
        import jax.numpy as jnp
        backend, U, E = self.backend, self.num_users, len(lanes)
        k_pad = idx.shape[1]
        weights = np.zeros((E, k_pad), np.float32)
        corrupt = np.ones((E, k_pad), np.float32)
        M = max(len(s) for s in stales)
        stale_w = np.zeros((E, M), np.float32) if M else None
        for e, (rf, stale_in) in enumerate(zip(rfs, stales)):
            w, sw = fault_alphas(
                U, rf.merged_now,
                [backend.num_examples(u) for u in rf.merged_now],
                [n for _, _, n in stale_in], fs.staleness_discount)
            sel = [int(u) for u in rf.merged_now]
            if sel:
                weights[e, :len(sel)] = w[sel]
                cu = np.ones(U, np.float32)
                for u, fac in rf.corrupt.items():
                    cu[int(u)] = fac
                corrupt[e, :len(sel)] = cu[sel]
            if len(sw):
                stale_w[e, :len(sw)] = sw
        stale_stack = None
        if M:
            # pad rows are zeros_like of a real stale update; they ride
            # with zero weight, so the masked reduction drops them
            template = None
            for stale_in in stales:
                if stale_in:
                    template = jax.tree.map(
                        lambda p: jnp.zeros_like(jnp.asarray(p)),
                        stale_in[0][1])
                    break
            per_lane = []
            for stale_in in stales:
                rows = [p for _, p, _ in stale_in]
                rows += [template] * (M - len(rows))
                per_lane.append(jax.tree.map(
                    lambda *ls: jnp.stack([jnp.asarray(x) for x in ls]),
                    *rows))
            stale_stack = jax.tree.map(lambda *ls: jnp.stack(ls),
                                       *per_lane)
        return backend.sweep_merge_faults(
            st, tr, idx, weights, corrupt, stale_stack, stale_w,
            quarantine=fs.quarantine, clip_norm=fs.clip_norm)

    def _dispatch_sweep_merge(self, lanes, st, tr, merged_all, pos_all,
                              rfs, stales, lead_faults, k_pad, t,
                              attempts=None):
        """One compact (E, k_pad) merge dispatch shared by the dense
        and sparse sweep loops. ``merged_all[e]`` are lane e's merge
        candidates (user ids, delivery order); ``pos_all[e]`` their row
        indices into the trained stack (== the user ids on the dense
        sweep, compact positions on the sparse one). ``attempts`` is
        the per-lane attempt-winner (uids, positions) pair for the
        objective merge's FedDyn h scatter (pre-channel-gate — the
        attempt trained even when the upload dropped). Routes through
        the robust-guard, AirComp, or plain digital sweep merge;
        returns the (E,) quarantine counts, or None off the fault
        path."""
        backend, E = self.backend, len(lanes)
        idx = np.zeros((E, k_pad), np.int32)
        w = np.zeros((E, k_pad), np.float32)
        uids = np.zeros((E, k_pad), np.int64)
        for e in range(E):
            idx[e], w[e] = compact_weights(
                k_pad, pos_all[e],
                [backend.num_examples(u) for u in merged_all[e]])
            uids[e, :len(merged_all[e])] = merged_all[e]
        if lead_faults is not None and lead_faults.merge_guarded:
            return self._sweep_merge_faults(lanes, st, tr, rfs, stales,
                                            lead_faults, idx)
        backend.sweep_merge(st, tr, idx, w,
                            merge_ctx=self._sweep_merge_ctx(lanes, t),
                            uids=uids, attempts=attempts)
        return None

    def _sweep_payload(self, fp, t, st, stream_snap, counters, lanes):
        import jax
        return {
            "kind": "sweep", "fingerprint": fp, "round": t,
            "glob": jax.device_get(st.glob),
            "client_streams": stream_snap,
            "counters": counters.state_dict(),
            # sweep objective state (m/v/h with the lane axis); None
            # for all-plain sweeps
            "objective": self.backend.sweep_objective_state(st),
            "lanes": [{
                "history": lane.history,
                "engine_rng": generator_state(lane.rng),
                "strategy": (lane.strategy._sim.state_dict()
                             if hasattr(lane.strategy, "_sim") else None),
                "channel": (lane.channel.state_dict()
                            if lane.channel is not None else None),
                "faults": (lane.faults.state_dict()
                           if lane.faults is not None else None),
            } for lane in lanes],
        }

    @staticmethod
    def _load_sweep_payload(payload, fp, lanes, counters):
        if payload["fingerprint"] != fp:
            raise ValueError(
                "checkpoint was written by a different sweep "
                "configuration; refusing to resume (point checkpoint_dir "
                "at a fresh directory or match the original specs)")
        if payload["kind"] != "sweep":
            raise ValueError(
                "checkpoint was written by the per-round path; resume "
                "it through the same non-sweep configuration")
        counters.load_state_dict(payload["counters"])
        for lane, lst in zip(lanes, payload["lanes"]):
            lane.history = lst["history"]
            restore_generator(lane.rng, lst["engine_rng"])
            if lst["strategy"] is not None:
                lane.strategy._sim.load_state_dict(lst["strategy"])
            if lane.channel is not None and lst["channel"] is not None:
                lane.channel.load_state_dict(lst["channel"])
            if lane.faults is not None and lst["faults"] is not None:
                lane.faults.load_state_dict(lst["faults"])
        return payload["round"] + 1

    def _run_lanes(self, lanes, *, init_state, overlap, verbose,
                   labels=None, checkpoint_dir=None, checkpoint_every=0):
        """The sweep round loop: one batched device program, one batched
        host selection layer, async host/device overlap.

        Pipeline shape per round t (device work in brackets):

            [train t in flight]  host pre-draws round t+1 batches
            sync (E, U) priorities                       <- only sync
            host: refrain masks + grouped CSMA contention
            dispatch [merge t] then [train t+1]
            host: counters, history, eval — device already busy

        Turning ``overlap`` off moves the batch pre-draw after the
        contention; every per-lane rng stream is consumed in the same
        order either way, so the two schedules are bit-identical
        (pinned in tests/test_sweep.py).
        """
        backend, U, E = self.backend, self.num_users, len(lanes)
        rounds = lanes[0].spec.rounds
        need_prio = any(l.strategy.uses_priority for l in lanes)
        lead_faults = lanes[0].spec.faults       # sweep-shared field
        counters = SweepFairnessCounter(
            E, U, np.array([l.spec.counter_threshold for l in lanes]))
        fp = run_fingerprint([l.spec for l in lanes], U)
        seeds = [l.spec.seed for l in lanes]
        objs = [l.spec.objective for l in lanes]
        t0 = time.perf_counter()
        start, st = 0, None
        if checkpoint_dir is not None:
            payload = load_fl_checkpoint(checkpoint_dir)
            if payload is not None:
                start = self._load_sweep_payload(payload, fp, lanes,
                                                 counters)
                st = backend.sweep_restore(
                    payload["glob"], payload["client_streams"], seeds,
                    objectives=objs,
                    objective_state=payload.get("objective"))
        if st is None:
            st = backend.sweep_init(init_state, seeds, objectives=objs)
        with spans.span("fl.round.batches", for_round=start):
            batched = backend.sweep_batches(st)
        with spans.span("fl.round.train", for_round=start):
            tr = backend.sweep_train(st, batched, need_prio)
        del batched
        for t in range(start, rounds):
            with spans.span("fl.round", round=t):
                last = t + 1 >= rounds
                want_ckpt = (checkpoint_dir is not None
                             and checkpoint_every > 0
                             and (t + 1) % checkpoint_every == 0 and not last)
                # the client-stream snapshot must precede ANY round-t+1
                # batch draw (overlapped or not): a resumed run re-draws
                # round t+1 from exactly this position
                stream_snap = (backend.sweep_stream_states(st) if want_ckpt
                               else None)
                next_batched = None
                if overlap and not last:
                    # host: round t+1's epoch permutations, drawn while the
                    # dispatched round-t train call runs on device
                    with spans.span("fl.round.batches", for_round=t + 1):
                        next_batched = backend.sweep_batches(st)
                with spans.span("fl.round.wait"):
                    prios64 = np.asarray(tr.priorities, np.float64)  # sync
                with spans.span("fl.round.select"):
                    winners_all, sels = self._select_lanes(
                        lanes, counters, prios64, t)
                # channel gate + fault pipeline: merge weights are computed
                # over the post-fault merge candidates (renormalized Eq. 1
                # over survivors); counters and histories keep seeing the
                # attempts. Stragglers' rows are captured BEFORE the merge
                # donates the trained stack.
                delivered_all, failures_all, rfs, stales = [], [], [], []
                for e, lane in enumerate(lanes):
                    if lane.faults is not None:
                        lane.faults.begin_round()
                    d, f = _gate_round(lane.channel, winners_all[e])
                    rf, stale_in = None, []
                    if lane.faults is not None:
                        rf = lane.faults.process_uploads(
                            winners_all[e], d,
                            lane.channel.per if lane.channel is not None
                            else None)
                        d, f = rf.arrived, len(rf.failed)
                        stale_in = lane.faults.pop_stale()
                        for u in rf.stragglers:
                            lane.faults.push_stale(
                                u, backend.sweep_extract(tr, e, u),
                                backend.num_examples(u))
                    delivered_all.append(d)
                    failures_all.append(f)
                    rfs.append(rf)
                    stales.append(stale_in)
                merged_all = [[int(u) for u in
                               (rf.merged_now if rf is not None else d)]
                              for rf, d in zip(rfs, delivered_all)]
                # dense sweep: user ids ARE the row indices into the
                # (E, U, ...) trained stack (for attempts too)
                k_pad = backend._k_pad(max(len(m) for m in merged_all))
                with spans.span("fl.round.merge"):
                    nq = self._dispatch_sweep_merge(
                        lanes, st, tr, merged_all, merged_all, rfs, stales,
                        lead_faults, k_pad, t,
                        attempts=(winners_all, winners_all))
                next_tr = None
                if not last:
                    if next_batched is None:
                        with spans.span("fl.round.batches", for_round=t + 1):
                            next_batched = backend.sweep_batches(st)
                    with spans.span("fl.round.train", for_round=t + 1):
                        next_tr = backend.sweep_train(st, next_batched,
                                                      need_prio)
                # deferred bookkeeping: overlaps the in-flight train call
                with spans.span("fl.round.record"):
                    counters.update(winners_all)
                    losses64 = np.asarray(tr.losses, np.float64)
                    for e, lane in enumerate(lanes):
                        if rfs[e] is not None:
                            lane.history.stale_merges += len(stales[e])
                        if nq is not None:
                            lane.history.quarantined_updates += int(nq[e])
                        self._record_lane(lane, sels[e], winners_all[e],
                                          delivered_all[e], failures_all[e],
                                          losses64[e], prios64[e], rf=rfs[e])
                if self.eval_fn is not None:
                    for e, lane in enumerate(lanes):
                        spec = lane.spec
                        if t % spec.eval_every == 0 or t == spec.rounds - 1:
                            with spans.span("fl.round.eval", lane=e):
                                acc = float(self.eval_fn(
                                    backend.sweep_global(st, e)))
                            lane.history.accuracy.append(acc)
                            lane.history.eval_round.append(t)
                            if verbose:
                                tag = (labels[e] if labels
                                       else f"{spec.strategy}/{e}")
                                loss = lane.history.train_loss[-1]
                                print(f"[{tag}] round {t:4d} acc {acc:.4f}"
                                      f" loss {loss:.4f}")
                if want_ckpt:
                    save_fl_checkpoint(
                        checkpoint_dir,
                        self._sweep_payload(fp, t, st, stream_snap,
                                            counters, lanes))
                tr = next_tr
        result = SweepResult(
            histories=[l.history for l in lanes],
            specs=[l.spec for l in lanes], labels=labels,
            overlap=overlap, wall_s=time.perf_counter() - t0,
            final_globals=st.glob)
        return result, st, counters

    def _run_lanes_sparse(self, lanes, *, init_state, verbose,
                          labels=None, checkpoint_dir=None):
        """Winner-sparse sweep loop (DESIGN.md §9): per round, Eq. 2
        priorities for every lane (exact prepass or stale cache), ONE
        grouped host contention pass, ONE compact (E, K_max, ...) train
        call over the winners only, then the compact merge. Synchronous
        — no overlap pipeline: the next round's winner draws depend on
        this round's contention, and the K-compact train step is too
        small for overlap to pay."""
        if checkpoint_dir is not None:
            raise NotImplementedError(
                "sparse sweeps don't checkpoint; use round_mode='fused' "
                "for checkpointed sweeps")
        backend, U, E = self.backend, self.num_users, len(lanes)
        rounds = lanes[0].spec.rounds
        need_prio = any(l.strategy.uses_priority for l in lanes)
        lead_faults = lanes[0].spec.faults       # sweep-shared field
        counters = SweepFairnessCounter(
            E, U, np.array([l.spec.counter_threshold for l in lanes]))
        seeds = [l.spec.seed for l in lanes]
        objs = [l.spec.objective for l in lanes]
        t0 = time.perf_counter()
        st = backend.sweep_sparse_init(init_state, seeds,
                                       objectives=objs)
        for t in range(rounds):
            with spans.span("fl.round", round=t):
                with spans.span("fl.round.prepass"):
                    prios, pre_losses = backend.sweep_sparse_priorities(
                        st, need_prio)
                with spans.span("fl.round.wait"):
                    prios64 = np.asarray(prios, np.float64)
                with spans.span("fl.round.select"):
                    winners_all, sels = self._select_lanes(
                        lanes, counters, prios64, t)
                with spans.span("fl.round.train", for_round=t):
                    tr = backend.sweep_sparse_train(st, winners_all)
                delivered_all, failures_all, rfs, stales = [], [], [], []
                for e, lane in enumerate(lanes):
                    if lane.faults is not None:
                        lane.faults.begin_round()
                    d, f = _gate_round(lane.channel, winners_all[e])
                    rf, stale_in = None, []
                    if lane.faults is not None:
                        rf = lane.faults.process_uploads(
                            winners_all[e], d,
                            lane.channel.per if lane.channel is not None
                            else None)
                        d, f = rf.arrived, len(rf.failed)
                        stale_in = lane.faults.pop_stale()
                        for u in rf.stragglers:
                            lane.faults.push_stale(
                                u, backend.sweep_extract(
                                    tr, e, winners_all[e].index(int(u))),
                                backend.num_examples(u))
                    delivered_all.append(d)
                    failures_all.append(f)
                    rfs.append(rf)
                    stales.append(stale_in)
                merged_all = [[int(u) for u in
                               (rf.merged_now if rf is not None else d)]
                              for rf, d in zip(rfs, delivered_all)]
                # sparse sweep: row indices are compact DELIVERY positions
                # into the (E, K_max, ...) winner stack; a lane's attempts
                # ARE its trained rows, in order
                pos_all = [[winners_all[e].index(u) for u in merged_all[e]]
                           for e in range(E)]
                att_pos = [list(range(len(ws))) for ws in winners_all]
                k_pad = int(np.shape(tr.priorities)[1])       # = k_max
                with spans.span("fl.round.merge"):
                    nq = self._dispatch_sweep_merge(
                        lanes, st, tr, merged_all, pos_all, rfs, stales,
                        lead_faults, k_pad, t,
                        attempts=(winners_all, att_pos))
                with spans.span("fl.round.record"):
                    counters.update(winners_all)
                    losses64 = (np.asarray(pre_losses, np.float64)
                                if pre_losses is not None
                                else np.asarray(tr.losses, np.float64))
                    for e, lane in enumerate(lanes):
                        if rfs[e] is not None:
                            lane.history.stale_merges += len(stales[e])
                        if nq is not None:
                            lane.history.quarantined_updates += int(nq[e])
                        # prepass rounds report full-cohort losses (the
                        # dense sweep's numbers); stale rounds report
                        # winner losses
                        loss_row = (losses64[e] if pre_losses is not None
                                    else losses64[e, :len(winners_all[e])])
                        self._record_lane(lane, sels[e], winners_all[e],
                                          delivered_all[e], failures_all[e],
                                          loss_row, prios64[e], rf=rfs[e])
                if self.eval_fn is not None:
                    for e, lane in enumerate(lanes):
                        spec = lane.spec
                        if t % spec.eval_every == 0 or t == spec.rounds - 1:
                            with spans.span("fl.round.eval", lane=e):
                                acc = float(self.eval_fn(
                                    backend.sweep_global(st, e)))
                            lane.history.accuracy.append(acc)
                            lane.history.eval_round.append(t)
                            if verbose:
                                tag = (labels[e] if labels
                                       else f"{spec.strategy}/{e}")
                                print(f"[{tag}] round {t:4d} acc {acc:.4f}")
        result = SweepResult(
            histories=[l.history for l in lanes],
            specs=[l.spec for l in lanes], labels=labels,
            overlap=False, wall_s=time.perf_counter() - t0,
            final_globals=st.glob)
        return result, st, counters


#: auto-select the winner-sparse path when the winner budget is at
#: least this many times smaller than the cohort (K ≪ U): below the
#: ratio the compact gather-K round wins on FLOPs and memory, above it
#: the dense fused round's single full-width step is at least as good.
SPARSE_AUTO_RATIO = 8


def build_host_engine(spec: ExperimentSpec, init_params, loss_fn,
                      user_data, eval_fn=None, *,
                      prefer_vmap: bool = True, round_mode: str = None,
                      mesh=None) -> FLEngine:
    """Convenience: spec + host data -> engine over HostBackend.

    ``round_mode`` (argument, else ``spec.round_mode``) picks the
    backend round path ("fused" / "stacked" / "ragged" / "sparse");
    when BOTH are None the factory auto-selects: "sparse" (the
    contention-first gather-K path, DESIGN.md §9) when the cohort is
    rectangular and ``k_per_round * SPARSE_AUTO_RATIO <= num_users``,
    else the dense default ("fused" / "ragged" per ``prefer_vmap``).
    ``mesh`` optionally shards the fused cohort axis — or the sparse
    path's compact K axis — over devices (``repro.sharding.cohort``).
    """
    import jax
    from repro.engine.backends import HostBackend
    mode = round_mode if round_mode is not None else spec.round_mode
    if mode is None and prefer_vmap:
        ns = {jax.tree.leaves(d)[0].shape[0] for d in user_data}
        rect = len(ns) == 1 and spec.batch_size <= next(iter(ns))
        if (rect and spec.k_per_round * SPARSE_AUTO_RATIO
                <= len(user_data)):
            mode = "sparse"
    backend = HostBackend(
        loss_fn, user_data, lr=spec.lr, batch_size=spec.batch_size,
        local_epochs=spec.local_epochs, seed=spec.seed,
        prefer_vmap=prefer_vmap, round_mode=mode, mesh=mesh,
        k_max=spec.k_per_round, sparse_priority=spec.sparse_priority,
        objective=spec.objective)
    return FLEngine(spec, backend, init_params, eval_fn)
