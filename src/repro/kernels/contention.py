"""Device-resident slotted CSMA/CA contention engine (DESIGN.md §6).

Ports ``CSMASimulator.contend_batch``'s event loop to JAX: a
``lax.while_loop`` over medium events whose per-event inner op — the
masked min-scan over the (B, N) backoff counters, expiry detection and
the collision redraw — runs as Pallas TPU kernels (jnp oracle on CPU,
interpret-mode validation in tests, matching the ``delta_norm`` /
``fedavg`` dispatch pattern in ``kernels.ops``).

Protocol parity with the numpy reference is exact; *stream* parity is
not: collision redraws come from counter-based threefry keys
(``fold_in(base_key, event_index)``) instead of numpy ``Generator``
streams, so the device path is validated distributionally (winner-rank
histograms, collision counts, airtime quantiles —
tests/test_contention_device.py), never draw-for-draw.

The per-event op is split into three Pallas passes because the
transition needs two full-row reductions first:

  1. ``_min_kernel``      step  = min over live counters   (row min-scan)
  2. ``_expiry_kernel``   nexp  = |{live: counter == step}|,
                          winner = min expiring index      (row reductions)
  3. ``_transition_kernel`` decrement / deliver / redraw    (elementwise)

Grid: (B, N/BLOCK_N) over rows laid out as (B, 1, N) so each (1, BLOCK_N)
row block is TPU-aligned for any B. TPU grid steps run sequentially per
core, so the per-row accumulators — (B,) outputs held whole in SMEM and
indexed by the row's program id — are well-defined across the N-blocks.

All slot arithmetic is int32 — counters, redraws and the horizon are
clamped to ``ref.CONTENTION_BIG`` (2^29) so ``t + step + tx_slots``
can never overflow; ``device_contend_batch`` asserts the config fits.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import CONTENTION_BIG

BLOCK_N = 2048   # lanes per grid step: 8 KiB per i32/f32 operand row


def _block(n_padded: int) -> int:
    return min(BLOCK_N, n_padded)


def _pad_to_block(n: int) -> int:
    b = _block(-(-n // 128) * 128)
    return -(-n // b) * b


# ---------------------------------------------------------------- pass 1
def _min_kernel(cnt_ref, live_ref, step_ref):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        step_ref[b] = jnp.int32(CONTENTION_BIG)

    live = live_ref[...] != 0
    masked = jnp.where(live, cnt_ref[...], jnp.int32(CONTENTION_BIG))
    step_ref[b] = jnp.minimum(step_ref[b], jnp.min(masked))


# ---------------------------------------------------------------- pass 2
def _expiry_kernel(cnt_ref, live_ref, step_ref, nexp_ref, winner_ref, *,
                   sentinel: int):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        nexp_ref[b] = jnp.int32(0)
        winner_ref[b] = jnp.int32(sentinel)

    live = live_ref[...] != 0
    # step is the row's masked min, so a live counter expires iff it
    # EQUALS step — no decrement pass needed before the detection
    exp = live & (cnt_ref[...] == step_ref[b])
    nexp_ref[b] += jnp.sum(exp.astype(jnp.int32))
    col = (j * cnt_ref.shape[1]
           + jax.lax.broadcasted_iota(jnp.int32, exp.shape, 1))
    winner_ref[b] = jnp.minimum(
        winner_ref[b],
        jnp.min(jnp.where(exp, col, jnp.int32(sentinel))))


# ---------------------------------------------------------------- pass 3
def _transition_kernel(cnt_ref, live_ref, dbl_ref, win_ref, rand_ref,
                       step_ref, nexp_ref, ncnt_ref, ndbl_ref, nact_ref,
                       *, max_doublings: int):
    b = pl.program_id(0)
    live = live_ref[...] != 0
    step = step_ref[b]
    nexp = nexp_ref[b]
    cnt2 = jnp.where(live, cnt_ref[...] - step, cnt_ref[...])
    exp = live & (cnt2 == 0)
    deliver = nexp == 1
    collide = nexp >= 2
    nd = jnp.minimum(dbl_ref[...] + 1, jnp.int32(max_doublings))
    redraw = jnp.clip(
        jnp.round(rand_ref[...] * win_ref[...]
                  * jnp.exp2(nd.astype(jnp.float32))),
        1.0, jnp.float32(CONTENTION_BIG)).astype(jnp.int32)
    coll_exp = exp & collide
    ncnt_ref[...] = jnp.where(coll_exp, redraw, cnt2)
    ndbl_ref[...] = jnp.where(coll_exp, nd, dbl_ref[...])
    nact_ref[...] = (live & ~(exp & deliver)).astype(jnp.int32)


def contention_event_pallas(counters, live, doublings, windows, rand,
                            max_doublings: int, *, interpret=False):
    """Pallas twin of ``ref.contention_event_ref`` (same signature and
    return contract); pads N up to the block size with dead lanes."""
    B, N = counters.shape
    npad = _pad_to_block(N)
    blk = _block(npad)
    grid = (B, npad // blk)
    pad = [(0, 0), (0, npad - N)]
    # rows ride as (B, 1, npad) so a row block's last two dims
    # (1, blk) span the full middle dim — TPU-aligned for any B
    def rows(x, fill=0):
        return jnp.pad(x, pad, constant_values=fill).reshape(B, 1, npad)

    cnt = rows(counters.astype(jnp.int32), CONTENTION_BIG)
    liv = rows(live.astype(jnp.int32))
    dbl = rows(doublings.astype(jnp.int32))
    win = rows(windows.astype(jnp.float32), 1.0)
    rnd = rows(rand.astype(jnp.float32))

    row_blk = pl.BlockSpec((pl.Squeezed(), 1, blk),
                           lambda i, j: (i, 0, j))
    # per-row (B,) scalars live whole in SMEM (a vector store of a
    # scalar to VMEM does not compile); the kernels index them by row
    acc = pl.BlockSpec(memory_space=pltpu.SMEM)
    i32 = jnp.int32
    row_out = jax.ShapeDtypeStruct((B, 1, npad), i32)

    step = pl.pallas_call(
        _min_kernel, grid=grid,
        in_specs=[row_blk, row_blk], out_specs=acc,
        out_shape=jax.ShapeDtypeStruct((B,), i32),
        interpret=interpret)(cnt, liv)

    nexp, winner = pl.pallas_call(
        functools.partial(_expiry_kernel, sentinel=npad), grid=grid,
        in_specs=[row_blk, row_blk, acc],
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((B,), i32),
                   jax.ShapeDtypeStruct((B,), i32)],
        interpret=interpret)(cnt, liv, step)

    ncnt, ndbl, nact = pl.pallas_call(
        functools.partial(_transition_kernel,
                          max_doublings=max_doublings), grid=grid,
        in_specs=[row_blk, row_blk, row_blk, row_blk, row_blk,
                  acc, acc],
        out_specs=[row_blk, row_blk, row_blk],
        out_shape=[row_out, row_out, row_out],
        interpret=interpret)(cnt, liv, dbl, win, rnd, step, nexp)

    # padded lanes are dead (live=0), so a winner == sentinel beyond N
    # means "none expiring"; report the numpy-oracle sentinel N instead
    winner = jnp.minimum(winner, jnp.int32(N))
    return (step, nexp, winner, ncnt[:, 0, :N], ndbl[:, 0, :N],
            nact[:, 0, :N] != 0)


# ------------------------------------------------------- the event loop
#
# Candidate-pool formulation.  A medium event only ever touches the
# counters that achieve the running minimum, so the event loop runs on
# the M smallest initial counters per row (one ``lax.top_k`` gather),
# in ABSOLUTE idle-time coordinates (a pool member's value is the total
# idle time at which it expires — no per-event decrement of the full
# (B, N) state).  Collision redraws re-enter the pool at
# ``tau + redraw``.  Validity: every excluded counter is >= the
# (M+1)-th smallest initial value (``threshold``), so events are
# provably exact while ``tau_min < threshold``; a row that exhausts its
# pool raises an ``invalid`` flag and the host retries the batch with a
# larger M (exact when M == N, which is also the small-N test regime).
# This turns the per-event cost from O(B*N) into O(B*M), M ~ hundreds —
# the difference between matching the numpy loop and beating it 10x+.
@functools.partial(
    jax.jit, static_argnames=("k_max", "tx_slots", "max_doublings",
                              "max_sim_slots", "use_kernel", "interpret"))
def _contend_device(pool_exp, pool_win, pool_idx, threshold, k_arr, key,
                    *, k_max: int, tx_slots: int, max_doublings: int,
                    max_sim_slots: int, use_kernel: bool,
                    interpret: bool):
    from repro.kernels import ops

    B, Mw = pool_exp.shape
    big = jnp.int32(CONTENTION_BIG)
    cap = jnp.int32(max_sim_slots)
    pool_act = pool_exp < big
    pool_dbl = jnp.zeros_like(pool_exp)

    t = jnp.zeros((B,), jnp.int32)
    idle = jnp.zeros((B,), jnp.int32)             # idle slots consumed
    wins = jnp.zeros((B,), jnp.int32)
    cols = jnp.zeros((B,), jnp.int32)
    invalid = jnp.zeros((B,), bool)
    winners = jnp.full((B, k_max), -1, jnp.int32)
    finish = jnp.full((B, k_max), -1, jnp.int32)
    rows = jnp.arange(B)

    def running_of(pool_act, t, wins, invalid):
        return ((wins < k_arr) & pool_act.any(axis=1) & (t < cap)
                & ~invalid)

    def cond(state):
        (pool_exp, pool_act, pool_dbl, t, idle, wins, cols, winners,
         finish, invalid, ev) = state
        return running_of(pool_act, t, wins, invalid).any()

    def body(state):
        (pool_exp, pool_act, pool_dbl, t, idle, wins, cols, winners,
         finish, invalid, ev) = state
        running = running_of(pool_act, t, wins, invalid)
        live = pool_act & running[:, None]
        # counter-based threefry: event ev's redraw material, same for
        # every retrace of the same (key, ev) — no carried rng state
        rand = jax.random.uniform(jax.random.fold_in(key, ev), (B, Mw),
                                  jnp.float32)
        # the event op sees ABSOLUTE expiries; its "step" is tau (the
        # pool min) and expiry detection (== min) is unchanged.  The
        # decremented counters it returns are relative to tau — shift
        # back by tau to stay in absolute coordinates.
        tau, nexp, wslot, ncnt, ndbl, nact = ops.contention_event(
            pool_exp, live, pool_dbl, pool_win, rand, max_doublings,
            use_kernel=use_kernel, interpret=interpret)
        tau = jnp.minimum(tau, big)
        # pool-exhaustion guard: an excluded counter could expire first
        bad = running & (tau >= threshold)
        running = running & ~bad
        step = tau - idle
        finish_t = t + step + jnp.int32(tx_slots)
        # horizon clamp (the max_sim_slots bugfix, device twin): an
        # event whose airtime can't complete by the cap freezes the row
        # at exactly the cap
        overrun = running & (finish_t > cap)
        apply = running & ~overrun
        deliver = apply & (nexp == 1)
        collide = apply & (nexp >= 2)
        t = jnp.where(overrun, cap, jnp.where(apply, finish_t, t))
        idle = jnp.where(apply, tau, idle)
        winner = jnp.take_along_axis(
            pool_idx, jnp.minimum(wslot, Mw - 1)[:, None], axis=1)[:, 0]
        slot = jnp.minimum(wins, k_max - 1)
        winners = winners.at[rows, slot].set(
            jnp.where(deliver, winner, winners[rows, slot]))
        finish = finish.at[rows, slot].set(
            jnp.where(deliver, finish_t, finish[rows, slot]))
        wins = wins + deliver.astype(jnp.int32)
        cols = cols + collide.astype(jnp.int32)
        # redraws come back relative to tau; re-absolutize and clamp
        nexp_abs = jnp.minimum(tau[:, None] + ncnt, big)
        pool_exp = jnp.where(apply[:, None], nexp_abs, pool_exp)
        pool_dbl = jnp.where(apply[:, None], ndbl, pool_dbl)
        pool_act = jnp.where(apply[:, None], nact, pool_act)
        invalid = invalid | bad
        return (pool_exp, pool_act, pool_dbl, t, idle, wins, cols,
                winners, finish, invalid, ev + 1)

    state = (pool_exp, pool_act, pool_dbl, t, idle, wins, cols,
             winners, finish, invalid, jnp.int32(0))
    state = jax.lax.while_loop(cond, body, state)
    (_, _, _, t, _, wins, cols, winners, finish, invalid, _) = state
    return winners, finish, cols, t, wins, invalid


def device_contend_batch(backoff_slots, window_slots, k_arr,
                         participating, *, entropy: int, call_index: int,
                         tx_slots: int, max_backoff_doublings: int,
                         max_sim_slots: int,
                         use_kernel: bool = True,
                         interpret: Optional[bool] = None):
    """Run B contention rounds on device; returns ``BatchCSMAResult``.

    Inputs are in SLOT units (the numpy path's second-based surface is
    converted by ``CSMASimulator``). ``entropy``/``call_index`` seed
    the counter-based threefry stream: one base key per simulator, one
    fold per ``contend_batch`` call, one more per medium event — same
    (entropy, call order) => bit-identical results, with zero mutable
    rng state inside the loop. ``use_kernel=False`` runs the per-event
    op through its jnp oracle, as every ``kernels.ops`` wrapper does.
    """
    from repro.core.csma import BatchCSMAResult
    from repro.kernels.ops import kernel_mode

    if max_sim_slots > CONTENTION_BIG:
        raise ValueError(
            f"device contention runs int32 slot arithmetic: "
            f"max_sim_slots={max_sim_slots} exceeds {CONTENTION_BIG}")
    if not 0 < tx_slots < (1 << 20):
        raise ValueError(f"tx_slots={tx_slots} out of device range")
    backoff_slots = np.atleast_2d(np.asarray(backoff_slots, np.float64))
    B, N = backoff_slots.shape
    k_arr = np.broadcast_to(np.asarray(k_arr, np.int64), (B,))
    k_max = int(k_arr.max(initial=0))
    part = (np.ones((B, N), bool) if participating is None
            else np.broadcast_to(np.asarray(participating, bool), (B, N)))
    if k_max == 0:
        z = np.zeros(B, np.int64)
        return BatchCSMAResult(
            winners=np.zeros((B, 0), np.int64),
            finish_slots=np.zeros((B, 0), np.int64),
            collisions=z, elapsed_slots=z.copy(), n_delivered=z.copy())

    use_kernel, interp = kernel_mode(use_kernel, interpret)
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(entropy) & (2 ** 63 - 1)),
        int(call_index))
    windows = np.broadcast_to(
        np.asarray(window_slots, np.float64), (B, N))
    counters = np.minimum(
        np.maximum(0, np.round(backoff_slots)), CONTENTION_BIG
    ).astype(np.int32)
    counters = np.where(part, counters, np.int32(CONTENTION_BIG))

    def gather_pool(M: int):
        """Host-side O(B*N) candidate selection: the M smallest
        expiries per row plus the (M+1)-th value as the validity
        threshold.  The device program then only ever sees (B, M)
        pool arrays — its compile cache is independent of N."""
        if M >= N:
            idx = np.broadcast_to(np.arange(N, dtype=np.int32), (B, N))
            thr = np.full((B,), np.iinfo(np.int32).max, np.int32)
            return counters, idx, thr
        cand = np.argpartition(counters, M, axis=1)[:, :M + 1]
        vals = np.take_along_axis(counters, cand, axis=1)
        order = np.argsort(vals, axis=1, kind="stable")
        pool_cols = order[:, :M]
        idx = np.take_along_axis(cand, pool_cols, axis=1).astype(np.int32)
        thr = np.take_along_axis(vals, order[:, M:M + 1], axis=1)[:, 0]
        return (np.take_along_axis(counters, idx, axis=1), idx, thr)

    # candidate-pool sizing with exactness retry: start small (the
    # usual k + colliders regime), grow geometrically on the rare pool
    # exhaustion, land on the exact full-cohort loop at M >= N.  The
    # retry decision is data-dependent but deterministic, so a given
    # (inputs, entropy, call_index) always yields the same result.
    M = min(N, max(128, 8 * k_max))
    while True:
        pool_exp, pool_idx, threshold = gather_pool(M)
        pool_win = np.take_along_axis(windows, pool_idx, axis=1) \
            if pool_idx.shape[1] < N else windows
        winners, finish, cols, t, wins, invalid = _contend_device(
            jnp.asarray(pool_exp), jnp.asarray(pool_win, jnp.float32),
            jnp.asarray(pool_idx), jnp.asarray(threshold),
            jnp.asarray(k_arr, jnp.int32), key,
            k_max=k_max, tx_slots=int(tx_slots),
            max_doublings=int(max_backoff_doublings),
            max_sim_slots=int(max_sim_slots),
            use_kernel=use_kernel, interpret=interp)
        if M >= N or not bool(np.asarray(invalid).any()):
            break
        M = min(N, M * 8)
    return BatchCSMAResult(
        winners=np.asarray(winners, np.int64),
        finish_slots=np.asarray(finish, np.int64),
        collisions=np.asarray(cols, np.int64),
        elapsed_slots=np.asarray(t, np.int64),
        n_delivered=np.asarray(wins, np.int64))
