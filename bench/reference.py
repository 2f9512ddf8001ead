"""Plain reference of one FL job, written from the paper and the
system's documented stream contract, importing nothing of the program.

One round of the paper's scheme (arXiv:2307.03758, Fig. 1):

1. every user trains the global model with one epoch of SGD on its own
   batches (fused rounds), or only last round's winners do (stale
   winner-sparse rounds, whose priorities are the cached ones);
2. Eq. 2 priority: prod over weight tensors of (1 + min(1, |w_k - w| /
   |w|));
3. fairness counter: a user whose upload share has reached the
   threshold stays silent;
4. Eq. 3: window W = N / priority, backoff R * W with R ~ U(0, 1),
   slotted CSMA/CA (20 us slots, 50-slot uploads, binary exponential
   backoff on collision) until K deliveries;
5. Eq. 1: the global model is the example-weighted mean of the winners'
   models; the counter advances; the new global is evaluated.

Random streams follow the system's documented seeding contract: each
is a ``SeedSequence(seed)`` spawn child (engine draws at path (0,), the
contention simulator at (1,), user ``u``'s batch permutations at
(2, u)). The device contention engine draws its collision redraws from
a counter-based threefry stream: key ``fold_in(PRNGKey(entropy),
call)``, one more fold per medium event, indexed by a contender's place
in the candidate pool of the smallest counters.

Model arithmetic is float32 under ``jax.default_matmul_precision
("highest")``; ``dtype=bfloat16`` gives the control, the same job in the
nearest lower precision.

A configuration's reference module gives ``init`` and may give three
hooks; where one is absent, a classifier whose weights are all trained
is assumed:

- ``split(weights) -> (trained, frozen)``: SGD, Eq. 2 and Eq. 1 run
  over the trained part alone; the frozen part is shared by every user
  and never moves (default: all trained);
- ``loss(trained, frozen, batch, cfg)``: a batch's mean training loss
  (default: cross-entropy of ``apply(trained, batch["x"])`` against
  ``batch["y"]``);
- ``evaluate(trained, frozen, test, cfg, dtype)``: the value the eval
  reports each round (default: accuracy on ``test["x"]``,
  ``test["y"]``).
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

SLOT_S = 20.0 * 1e-6
TX_SLOTS = 50
MAX_DOUBLINGS = 5
MAX_SIM_SLOTS = 2_000_000
CONTENTION_BIG = 1 << 29


def _seq(seed, *path):
    return np.random.SeedSequence(entropy=int(seed), spawn_key=tuple(path))


def engine_rng(seed):
    return np.random.default_rng(_seq(seed, 0))


def contention_rng(seed):
    return np.random.default_rng(_seq(seed, 1))


def client_rng(seed, uid):
    return np.random.default_rng(_seq(seed, 2, int(uid)))


def contention_entropy(seed) -> int:
    lo, hi = _seq(seed, 1).generate_state(2, np.uint32)
    return int(hi) << 32 | int(lo)


# ------------------------------------------------------------- CSMA/CA
def csma_numpy(backoff_s, window_s, k, part, rng) -> List[int]:
    """Slotted CSMA/CA until ``k`` deliveries; collision redraws from
    ``rng``, colliders in ascending user order."""
    counters = np.maximum(0, np.round(backoff_s / SLOT_S)).astype(np.int64)
    active = np.asarray(part, bool).copy()
    dbl = np.zeros(len(counters), np.int64)
    winners, t = [], 0
    while len(winners) < k and active.any() and t < MAX_SIM_SLOTS:
        live = np.where(active)[0]
        step = int(counters[live].min())
        if t + step + TX_SLOTS > MAX_SIM_SLOTS:
            break
        t += step
        counters[live] -= step
        expiring = live[counters[live] == 0]
        t += TX_SLOTS
        if len(expiring) == 1:
            winners.append(int(expiring[0]))
            active[expiring[0]] = False
            continue
        for u in expiring:
            dbl[u] = min(dbl[u] + 1, MAX_DOUBLINGS)
            w = window_s[u] * 2.0 ** dbl[u]
            counters[u] = max(1, int(round(rng.uniform(0.0, w) / SLOT_S)))
    return winners


@jax.jit
def _event_uniform(key, ev, like):
    return jax.random.uniform(jax.random.fold_in(key, ev), like.shape,
                              jnp.float32)


def csma_device_stream(backoff_s, window_s, k, part, entropy: int,
                       call: int) -> List[int]:
    """The same protocol with the device engine's redraw stream: the
    ``M`` smallest counters form the pool (M = max(128, 8k), grown 8x
    when a counter outside could expire first), event ``ev`` draws
    ``uniform(fold_in(key, ev), (1, M))`` and a colliding pool entry at
    place ``i`` redraws ``round(u[i] * W * 2**d)`` slots, in float32."""
    bslots = np.asarray(backoff_s, np.float64) / SLOT_S
    wslots = np.asarray(window_s, np.float64) / SLOT_S
    n = len(bslots)
    counters = np.minimum(np.maximum(0, np.round(bslots)),
                          CONTENTION_BIG).astype(np.int32)
    counters = np.where(np.asarray(part, bool), counters,
                        np.int32(CONTENTION_BIG))
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(entropy) & (2 ** 63 - 1)), int(call))
    m = min(n, max(128, 8 * k))
    while True:
        if m >= n:
            idx = np.arange(n)
            thr = np.iinfo(np.int32).max
        else:
            cand = np.argpartition(counters[None], m, axis=1)[0, :m + 1]
            vals = counters[cand]
            order = np.argsort(vals, kind="stable")
            idx = cand[order[:m]]
            thr = int(vals[order[m]])
        winners, invalid = _device_events(
            counters[idx].astype(np.int64), idx,
            wslots[idx].astype(np.float32), thr, k, key)
        if m >= n or not invalid:
            return winners
        m = min(n, m * 8)


def _device_events(exp, idx, win, thr, k, key):
    act = exp < CONTENTION_BIG
    dbl = np.zeros(len(exp), np.int64)
    like = np.zeros((1, len(exp)), np.float32)
    winners, t, idle, ev = [], 0, 0, 0
    while len(winners) < k and act.any() and t < MAX_SIM_SLOTS:
        tau = int(np.where(act, exp, CONTENTION_BIG).min())
        if tau >= thr:
            return winners, True
        expiring = np.where(act & (exp == tau))[0]
        finish = t + (tau - idle) + TX_SLOTS
        if finish > MAX_SIM_SLOTS:
            break
        t, idle = finish, tau
        if len(expiring) == 1:
            winners.append(int(idx[expiring[0]]))
            act[expiring[0]] = False
        else:
            u = np.asarray(_event_uniform(key, ev, like))[0, expiring]
            nd = np.minimum(dbl[expiring] + 1, MAX_DOUBLINGS)
            draw = np.clip(np.round(
                u * win[expiring] * np.exp2(nd).astype(np.float32)),
                1.0, np.float32(CONTENTION_BIG)).astype(np.int64)
            exp[expiring] = np.minimum(tau + draw, CONTENTION_BIG)
            dbl[expiring] = nd
        ev += 1
    return winners, False


class Selector:
    """Counter refrain + Eq. 3 windows + CSMA/CA, with the streams of
    one job seed."""

    def __init__(self, seed, num_users, wl):
        self.k = int(wl["k"])
        self.cw = float(wl["cw_base"])
        self.threshold = float(wl["counter_threshold"])
        self.use_counter = bool(wl["use_counter"])
        self.device = wl["contention_backend"] == "device"
        self.rng = engine_rng(seed)
        self.csma_rng = contention_rng(seed)
        self.entropy = contention_entropy(seed)
        self.calls = 0
        self.uploads = np.zeros(num_users, np.int64)
        self.total = 0

    def select(self, prios) -> List[int]:
        shares = self.uploads / max(self.total, 1)
        part = (shares < self.threshold if self.use_counter
                else np.ones(len(shares), bool))
        if not part.any():
            part = np.ones(len(shares), bool)
        p = np.asarray(prios, np.float64)
        p = np.where(np.isnan(p), 0.0, p)
        windows = self.cw / np.maximum(p, 1e-9)
        backoff = self.rng.uniform(0.0, 1.0, size=len(windows)) * windows
        if self.device:
            winners = csma_device_stream(backoff * SLOT_S, windows * SLOT_S,
                                         self.k, part, self.entropy,
                                         self.calls)
            self.calls += 1
        else:
            winners = csma_numpy(backoff * SLOT_S, windows * SLOT_S, self.k,
                                 part, self.csma_rng)
        for u in winners:
            self.uploads[u] += 1
        self.total += len(winners)
        return winners

    def peek(self, prios) -> List[int]:
        """The winners ``select`` would give for ``prios``, leaving the
        streams and the counter where they are."""
        return copy.deepcopy(self).select(prios)


# ------------------------------------------------------ local training
def _cast(tree, dtype):
    """Floating leaves of ``tree`` in ``dtype``; integer leaves (labels,
    token ids) as they are."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def split(model, weights):
    """``(trained, frozen)`` by the configuration's ``split`` hook; all
    trained without one."""
    if hasattr(model, "split"):
        return model.split(weights)
    return weights, {}


def loss_of(model):
    """The configuration's ``loss`` hook, else cross-entropy of its
    ``apply`` over class logits."""
    if hasattr(model, "loss"):
        return model.loss

    def xent(trained, frozen, batch, cfg):
        logp = jax.nn.log_softmax(model.apply(trained, batch["x"]))
        return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None],
                                             axis=1))
    return xent


def evaluate(model, trained, frozen, test, cfg, dtype):
    """The configuration's ``evaluate`` hook, else arg-max accuracy."""
    if hasattr(model, "evaluate"):
        return float(model.evaluate(trained, frozen, test, cfg, dtype))
    return accuracy(model.apply, trained, test["x"], test["y"], dtype)


def make_epoch(loss, cfg, lr, dtype):
    """``epoch(trained, frozen, batches) -> (trained, mean batch loss)``:
    one SGD step of the trained part per batch of ``batches`` (leaves
    (nb, bs, ...)), in ``dtype``."""

    @jax.jit
    def epoch(params, frozen, batches):
        params = _cast(params, dtype)
        frozen = _cast(frozen, dtype)

        def step(p, batch):
            batch = _cast(batch, dtype)
            value, g = jax.value_and_grad(
                lambda q: loss(q, frozen, batch, cfg))(p)
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                                p, g), value.astype(jnp.float32)

        params, losses = jax.lax.scan(step, params, batches)
        return (jax.tree.map(lambda p: p.astype(jnp.float32), params),
                losses.mean())

    return epoch


@jax.jit
def priority(local, glob):
    """Eq. 2 with each layer's relative distance capped at 1."""
    prio = jnp.float32(1.0)
    for wl, wg in zip(jax.tree.leaves(local), jax.tree.leaves(glob)):
        d = jnp.sqrt(jnp.sum(jnp.square(wl - wg)))
        g = jnp.sqrt(jnp.sum(jnp.square(wg)))
        prio = prio * (1.0 + jnp.minimum(d / jnp.maximum(g, 1e-12), 1.0))
    return prio


def merge(models, sizes):
    """Eq. 1: the example-weighted mean of ``models`` (delivery order)."""
    s = np.asarray(sizes, np.float64)
    w = (s / s.sum()).astype(np.float32)
    out = jax.tree.map(lambda x: w[0] * x, models[0])
    for wj, m in zip(w[1:], models[1:]):
        out = jax.tree.map(lambda a, x: a + wj * x, out, m)
    return out


def accuracy(apply, params, x, y, dtype, batch=500):
    fwd = jax.jit(lambda p, xb: jnp.argmax(
        apply(jax.tree.map(lambda a: a.astype(dtype), p),
              xb.astype(dtype)), -1))
    hits = 0
    for i in range(0, len(y), batch):
        hits += int((np.asarray(fwd(params, x[i:i + batch]))
                     == y[i:i + batch]).sum())
    return hits / len(y)


# ------------------------------------------------------------- the job
@dataclass
class Trajectory:
    """What one job produced in its first rounds: per round the winners
    in delivery order, the mean training loss, the priorities selection
    saw, the eval value of the merged model (accuracy, or a held-out
    loss), and the trained part of the globals before round 0 and after
    each round (host arrays)."""
    winners: List[List[int]] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)
    priorities: List[np.ndarray] = field(default_factory=list)
    accuracy: List[float] = field(default_factory=list)
    globals: List[dict] = field(default_factory=list)
    #: winners the reference's selection gives for the priorities of
    #: another trajectory, round by round (see ``run_job``'s probe)
    probe_winners: List[List[int]] = field(default_factory=list)
    #: rounds in which the job selected on the probe's priorities
    followed: int = 0


def examples(data) -> int:
    """A user's example count: the first leaf's length (Eq. 1's
    weight)."""
    return len(jax.tree.leaves(data)[0])


def _batches(rng, data, nb, bs):
    perm = rng.permutation(examples(data))[:nb * bs]
    return jax.tree.map(
        lambda a: a[perm].reshape((nb, bs) + a.shape[1:]), data)


def run_job(model, cfg, weights, users, test, wl, seed, rounds, *,
            dtype=jnp.float32, probe: Optional[Trajectory] = None,
            fault: Optional[str] = None, shards: int = 1) -> Trajectory:
    """The first ``rounds`` rounds of one job from the initial
    ``weights`` of the configuration ``cfg``, whose reference module is
    ``model`` (its hooks: see the module's docstring).

    ``users``: per-user dicts of host arrays whose first axis counts the
    examples; ``test``: the test split.
    ``probe``: another trajectory whose round-t priorities are also put
    through this job's round-t selection state (without advancing it),
    so that a different winner list can be told apart from a selection
    that gives those priorities another outcome. Where that selection
    gives the probe's own winners and this job's priorities give others
    (a backoff on a slot boundary), the round selects on the probe's
    priorities: the job goes on with the probe's winners, merged from
    its own models, and the rounds after stay comparable (counted in
    ``followed``). Where it does not, the job keeps its own winners and
    the comparison counts a selection fault.

    ``fault`` plants one fault, for the readings the limits are set
    from: ``"unchanged"`` keeps the global model as it was (a merge that
    returns its state), ``"half"`` trains on the first half of every
    batch (the mean taken over the rest), ``"exchange"`` leaves out the
    exchange between the ``shards`` chips that split the users: Eq. 1
    over the winners of the first chip's share, users ``[0, U /
    shards)``, its weights renormalised (the global is kept where that
    share has no winner)."""
    if fault == "exchange" and shards < 2:
        raise ValueError("the exchange fault needs users split over "
                         "two or more chips")
    with jax.default_matmul_precision("highest"):
        return _run_job(model, cfg, weights, users, test, wl, seed, rounds,
                        dtype, probe, fault, shards)


def _run_job(model, cfg, weights, users, test, wl, seed, rounds, dtype,
             probe, fault, shards):
    U = len(users)
    bs = int(wl["batch_size"])
    nb = max(1, examples(users[0]) // bs)
    stale = wl["round_mode"] == "sparse"
    epoch = make_epoch(loss_of(model), cfg, float(wl["lr"]), dtype)
    sel = Selector(seed, U, wl)
    rngs = [client_rng(seed, u) for u in range(U)]
    part, frozen = split(model, weights)
    as32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    glob, frozen = as32(part), as32(frozen)
    cache = np.ones(U, np.float64)
    traj = Trajectory(globals=[jax.device_get(glob)])

    def train(u):
        batches = _batches(rngs[u], users[u], nb, bs)
        if fault == "half":
            batches = jax.tree.map(lambda a: a[:, :bs // 2], batches)
        local, loss = epoch(glob, frozen, batches)
        return local, float(loss), float(priority(local, glob))

    for t in range(rounds):
        if stale:
            prios = cache.copy()
        else:
            trained = [train(u) for u in range(U)]
            prios = np.array([p for _, _, p in trained], np.float64)
        chosen = prios
        if probe is not None and t < len(probe.priorities):
            theirs = sel.peek(probe.priorities[t])
            traj.probe_winners.append(theirs)
            if (theirs == list(probe.winners[t])
                    and sel.peek(prios) != theirs):
                # a backoff on a slot boundary: this selection, fed the
                # probe's priorities, gives the probe's winners; select
                # as the probe did, so that the rounds after merge the
                # same users' models
                chosen = probe.priorities[t]
                traj.followed += 1
        winners = sel.select(chosen)
        if stale:
            trained = [train(u) for u in winners]
            for u, (_, _, p) in zip(winners, trained):
                cache[u] = p
            locals_ = [m for m, _, _ in trained]
        else:
            locals_ = [trained[u][0] for u in winners]
        losses = [l for _, l, _ in trained]
        merged = [(u, m) for u, m in zip(winners, locals_)
                  if fault != "exchange" or u < U // shards]
        if merged and fault != "unchanged":
            glob = merge([m for _, m in merged],
                         [examples(users[u]) for u, _ in merged])
        traj.winners.append(list(winners))
        traj.losses.append(float(np.mean(np.asarray(losses, np.float64)))
                           if losses else float("nan"))
        traj.priorities.append(prios)
        traj.accuracy.append(evaluate(model, glob, frozen, test, cfg, dtype))
        traj.globals.append(jax.device_get(glob))
    return traj
