"""Run one benchmark cell once and print one JSON line.

    python3 bench/run.py --workload paper-mlp --seed 7 --seconds 44 --trace 0

A cell (``bench/workloads/<cell>.json``) names a model configuration
(``bench/configs/<config>.json`` and its plain reference beside it) and
a traffic mix (``bench/traffic/<traffic>.json``): the FL job a
researcher submits. The configuration's own files choose the task: its
``task`` key the data generator (``bench/data.make_task_data``), its
``program`` key (``"module:function"``) the builder of the program side,
and its reference module's hooks the reference's loss, eval and
trained part (``bench/reference.py``). The run

1. checks that JAX sees a TPU with as many chips as the cell asks for,
   and exits 3 without a result where it does not;
2. set-up: draws the data and the initial weights from ``--seed``,
   calls the configuration's builder, builds the engine on what it
   returns through ``repro.engine.build_host_engine`` and runs
   one warm-up job of ``CHECKED_ROUNDS`` rounds, which compiles every
   program the window uses;
3. the window: jobs (``FLEngine.run_sweep`` of ``rounds_per_job``
   rounds from the initial weights, each with a seed of its own) run
   back to back until ``--seconds`` have passed; the job in flight
   finishes. The first window job's first ``CHECKED_ROUNDS`` rounds are
   kept for the comparison: that job runs after another, as every
   window job does;
4. reads the peak device memory, frees the engine, runs the plain
   reference over those rounds and compares (``bench/compare.py``): a
   run is correct when each number is within its cell's limit, every
   checked round's global model was compared and no window round read
   a non-finite value.

``--trace 0`` reports the end-to-end metrics: ``rounds_per_s`` (all
rounds of the window over its length), ``round_ms_p95`` (the 95th
percentile of every round's latency: the time between consecutive eval
callbacks of a job, round 0 from the job's call) and ``setup_s``
(process start to window start). ``--trace 1`` profiles the window
(``TRACE_JOBS`` jobs) with the benchmark's spans around the
calls into each layer, and reports the per-layer metrics that
``BENCHMARK.json`` lists for the cell, each computed by its reader in
``bench/metrics/<metric>.py``.

The compiled programs are kept in ``JAX_COMPILATION_CACHE_DIR`` where it
is set, else in ``.jax_cache/`` at the root of the checkout.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the TPU runtime logs under /tmp unless told otherwise; a run writes
# only inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_DIR = ROOT / ".bench_trace"
EXIT_NO_CHIP = 3
#: rounds of a job that the comparison checks, and the warm-up job's length
CHECKED_ROUNDS = 3
#: jobs in the window of a ``--trace 1`` run
TRACE_JOBS = 1


# ------------------------------------------------------------- the cell
@dataclass
class Cell:
    name: str
    workload: dict
    traffic: dict
    config: dict
    model: object          # the config's plain reference module
    metrics: list          # BENCHMARK.json per-layer entries of the cell
    end_to_end: list       # names of the cell's end-to-end metrics

    @property
    def params(self) -> dict:
        """Traffic parameters, with the split sizes resolved."""
        tr = dict(self.traffic)
        epu = tr.get("examples_per_user")
        if epu is None:
            epu = self.config["n_train"] // tr["users"]
        tr["examples_per_user"] = int(epu)
        return tr


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(
        "-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = BENCH) -> Cell:
    """The cell ``name`` from the ``workloads``, ``traffic`` and
    ``configs`` directories under ``root``."""
    wl = json.loads((root / "workloads" / f"{name}.json").read_text())
    traffic = json.loads(
        (root / "traffic" / f"{wl['traffic']}.json").read_text())
    cfg = json.loads((root / "configs" / f"{wl['config']}.json").read_text())
    model = _load_module(root / "configs" / cfg["reference"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    def mine(entries):
        return [m for m in entries if name in m.get("workloads", [name])]
    return Cell(name, wl, traffic, cfg, model, mine(bench["per_layer"]),
                [m["name"] for m in mine(bench["end_to_end"])])


def program_builder(cfg: dict):
    """The configuration's ``program``, ``"module:function"``: called as
    ``builder(cfg, weights, test)``, it returns the trained part of the
    initial weights, the program's ``loss_fn(params, batch)`` and
    ``eval_fn(params)``, and further keyword arguments of
    ``repro.engine.build_host_engine``."""
    module, _, fn = cfg["program"].partition(":")
    return getattr(importlib.import_module(module), fn)


# --------------------------------------------------------- the devices
def require_devices(chips: int):
    """The devices of the cell; exits without a result unless JAX's
    default backend is a TPU with at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    return devs[:chips]


def peak_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def enable_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------ inputs
def job_seed(seed: int, job: int) -> int:
    """The FL seed of job ``job`` of a run seeded ``seed``."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), job])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


def make_inputs(cell: Cell, seed: int):
    """Users' data (dicts of arrays, one example a row), the test split
    and the initial weights (float32, made on the device in one call),
    all from ``seed``."""
    import jax
    from bench.data import make_task_data
    users, test = make_task_data(seed, cell.config, cell.params)
    key = jax.random.wrap_key_data(np.random.SeedSequence(
        [int(seed) & (2 ** 64 - 1), 1 << 20]).generate_state(2, np.uint32))
    init = jax.jit(lambda k: cell.model.init(k, cell.config))(key)
    return users, test, init


# ------------------------------------------------------------ engine
class EvalRecorder:
    """The program's eval, stamped with the host clock after
    each call; the next ``capture_left`` calls also keep the evaluated
    global model (host copy) in ``captured``."""

    def __init__(self, prog_eval):
        self.prog_eval = prog_eval
        self.stamps = []
        self.captured = []
        self.capture_left = 0

    def __call__(self, params):
        import jax
        if self.capture_left > 0:
            self.captured.append(jax.device_get(params))
            self.capture_left -= 1
        value = self.prog_eval(params)
        self.stamps.append(time.perf_counter())
        return value


def build_engine(cell: Cell, users, test, init, devices):
    """The engine on the configuration's program; returns ``(engine,
    spec, recorder, trained)``, ``trained`` the part of ``init`` that
    the engine trains."""
    from repro.engine import ExperimentSpec, build_host_engine
    tr = cell.params
    trained, loss_fn, eval_fn, extra = program_builder(cell.config)(
        cell.config, init, test)
    spec = ExperimentSpec(
        k_per_round=tr["k"], rounds=tr["rounds_per_job"], eval_every=1,
        strategy=tr["strategy"], cw_base=float(tr["cw_base"]),
        use_counter=tr["use_counter"],
        counter_threshold=tr["counter_threshold"],
        contention_backend=tr["contention_backend"],
        round_mode=tr["round_mode"], lr=tr["lr"], batch_size=tr["batch_size"],
        local_epochs=tr["local_epochs"], seed=0,
        **({"sparse_priority": tr["sparse_priority"]}
           if "sparse_priority" in tr else {}))
    mesh = None
    if len(devices) > 1:        # a cell on 4 chips shards the cohort
        from repro.sharding.cohort import cohort_mesh
        mesh = cohort_mesh(devices)
    recorder = EvalRecorder(eval_fn)
    engine = build_host_engine(spec, trained, loss_fn, users, recorder,
                               mesh=mesh, **extra)
    return engine, spec, recorder, trained


def run_job(engine, spec, recorder):
    """One job; returns ``(result, end time, round latencies in s)``."""
    import jax
    from repro.engine import SweepSpec
    recorder.stamps = []
    t0 = time.perf_counter()
    result = engine.run_sweep(SweepSpec([spec]))
    jax.block_until_ready(result.final_globals)
    t1 = time.perf_counter()
    marks = [t0] + recorder.stamps
    return result, t1, [b - a for a, b in zip(marks, marks[1:])]


class Spans:
    """Host spans ``(name, start, end)`` in ``time.time_ns`` ns, the
    profiler's clock, kept in memory for the trace reduction."""

    def __init__(self):
        self.events = []

    def wrap(self, name, fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            t0 = time.time_ns()
            try:
                return fn(*a, **k)
            finally:
                self.events.append((name, t0, time.time_ns()))
        return inner


def add_spans(engine, recorder, spans: Spans):
    """Wrap the calls into each layer with host spans (trace runs)."""
    span = spans.wrap
    be = engine.backend
    engine._select_lanes = span("fl.select", engine._select_lanes)
    be.sweep_batches = span("fl.batches", be.sweep_batches)
    be.sweep_train = span("fl.train_dispatch", be.sweep_train)
    be.sweep_sparse_train = span("fl.train_dispatch", be.sweep_sparse_train)
    be.sweep_merge = span("fl.merge_dispatch", be.sweep_merge)
    recorder.prog_eval = span("fl.eval", recorder.prog_eval)


class CompileCounter:
    """Programs compiled, or fetched from the persistent cache, while
    ``on``."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1


# ------------------------------------------------------------ metrics
def percentile(values, q):
    """The ``q``-th percentile, linear between closest ranks."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


@dataclass
class TraceView:
    """What a per-layer reader gets: the reduced trace of the traced
    window and the counts it needs."""
    trace: object
    rounds: int
    window_s: float
    chips: int
    peak: dict
    cell: Cell
    parameters: int
    flops_per_example: int
    compiles: int


def read_metric(name: str, view: TraceView):
    mod = _load_module(BENCH / "metrics" / f"{name}.py")
    return mod.read(view)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ------------------------------------------------------------ the run
def run(args, *, cell: Cell = None, chip: bool = True) -> dict:
    """One run of one cell; returns the result line's object.
    ``chip=False`` (tests) skips the look for a TPU, the peaks table and
    the persistent compilation cache, and runs on JAX's default
    devices."""
    cell = cell or load_cell(args.workload)
    chips = int(cell.workload["chips"])
    import jax
    if chip:
        enable_cache()
        devices = require_devices(chips)
    else:
        devices = jax.devices()[:chips]
    kind = devices[0].device_kind
    peak = peak_of(kind) if chip else {}
    tr = cell.params

    users, test, init = make_inputs(cell, args.seed)
    engine, spec, recorder, trained = build_engine(cell, users, test, init,
                                                   devices)
    init_host = jax.device_get(init)
    trained_host = jax.device_get(trained)
    del trained

    # set-up: the warm-up job compiles every program the window runs
    run_job(engine, replace(spec, seed=job_seed(args.seed, 0),
                            rounds=CHECKED_ROUNDS), recorder)

    counter = CompileCounter()
    spans = Spans()
    timed_job = run_job
    if args.trace:
        add_spans(engine, recorder, spans)
        timed_job = spans.wrap("fl.job", run_job)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # device events only: the spans are kept by ``Spans``
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    max_jobs = TRACE_JOBS if args.trace else None
    checked_seed = job_seed(args.seed, 1)
    recorder.capture_left = CHECKED_ROUNDS
    prog = None
    counter.on = True
    w0 = time.perf_counter()
    setup_s = w0 - T_PROCESS
    latencies, first_rounds, jobs, bad_rounds = [], [], 0, 0
    w1 = w0
    while True:
        job = replace(spec, seed=job_seed(args.seed, jobs + 1))
        res, w1, lat = timed_job(engine, job, recorder)
        h = res.histories[0]
        if prog is None:        # the first window job: the checked one
            from bench.reference import Trajectory
            n = CHECKED_ROUNDS
            prog = Trajectory(
                winners=[list(w) for w in h.winners[:n]],
                losses=list(h.train_loss[:n]),
                priorities=[np.asarray(p, np.float64)
                            for p in h.priorities[:n]],
                accuracy=list(h.accuracy[:n]),
                globals=[trained_host] + recorder.captured)
            recorder.captured = []
        bad_rounds += sum(1 for v in h.train_loss + h.accuracy
                          if not math.isfinite(v))
        latencies.extend(lat)
        first_rounds.append(lat[0])
        jobs += 1
        del res
        if w1 - w0 >= args.seconds or (max_jobs and jobs >= max_jobs):
            break
    counter.on = False
    window_s = w1 - w0
    if args.trace:
        jax.profiler.stop_trace()
    mem = memory_peak(devices)
    rounds = len(latencies)

    result = {"correct": False, "attempted": rounds, "failed": bad_rounds}
    if args.trace:
        from bench import tracing
        events, start = tracing.read_xspace(
            tracing.find_xspace(str(TRACE_DIR)))
        summary = tracing.summarize(
            events + tracing.host_spans(spans.events, start))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        view = TraceView(
            trace=summary, rounds=rounds, window_s=summary.window_ns * 1e-9,
            chips=chips, peak=peak, cell=cell,
            parameters=int(cell.model.parameter_count(cell.config)),
            flops_per_example=int(
                cell.model.train_flops_per_example(cell.config, tr)),
            compiles=counter.count)
        metrics = {}
        for m in cell.metrics:
            v = read_metric(m["name"], view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {"busy_s": summary.mean_busy_ns() * 1e-9,
                  "window_s": summary.window_ns * 1e-9}
        breakdown = {"device_ops": [[n, s] for n, s in summary.top_ops(10)],
                     "idle_gaps": [[n, sec] for n, sec
                                   in summary.idle_by_span(10)]}
    else:
        measured = {
            "rounds_per_s": {"value": rounds / window_s, "unit": "rounds/s"},
            "round_ms_p95": {"value": percentile(latencies, 95) * 1e3,
                             "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        print("window: " + ", ".join(f"{k} {v['value']!r}" for k, v
                                     in measured.items())
              + f", rounds {rounds}, jobs {jobs}", file=sys.stderr)
        # the tail's make-up: each job's round 0 (which pays the job's
        # start) against the median of the other rounds
        rest = latencies[:]
        for v in first_rounds:
            rest.remove(v)
        print("rounds: round 0 ms " + " ".join(
            f"{v * 1e3:.1f}" for v in first_rounds)
              + (f"; other rounds median ms {percentile(rest, 50) * 1e3:.1f}"
                 if rest else ""), file=sys.stderr)
        metrics = {k: measured[k] for k in cell.end_to_end}
        device, breakdown = {}, None
    result["metrics"] = metrics
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices), "memory_peak_bytes": mem,
                        **device}
    if breakdown is not None:
        result["breakdown"] = breakdown

    # the comparison, with the program's state freed
    del engine, recorder
    gc.collect()
    from bench import compare, reference
    ref = reference.run_job(cell.model, cell.config, init_host, users, test,
                            tr, checked_seed, CHECKED_ROUNDS, probe=prog)
    cmp = compare.compare(prog, ref)
    limits = cell.workload["limits"]
    # JSON has no inf or NaN: a number that could not be read, or read
    # NaN, goes out as null (and fails its limit)
    checks = {k: {"value": (v if math.isfinite(v) else None),
                  "limit": limits[k]}
              for k, v in cmp["checks"].items()}
    # every checked round's global model is compared, or the run fails
    missed = CHECKED_ROUNDS - cmp["rounds_compared"]
    checks["rounds_not_compared"] = {"value": missed, "limit": 0}
    result["correct"] = (compare.judge(cmp["checks"], limits)
                         and missed == 0 and bad_rounds == 0)
    result["rounds_followed"] = cmp["rounds_followed"]
    result["checks"] = checks
    print(f"rounds followed on the program's priorities: "
          f"{cmp['rounds_followed']} of {CHECKED_ROUNDS}; non-finite "
          f"rounds in window: {bad_rounds} (limit 0)", file=sys.stderr)
    for k, v in {**cmp["checks"], "rounds_not_compared": missed}.items():
        print(f"check {k}: {v!r} (limit {checks[k]['limit']!r})",
              file=sys.stderr)
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    result = run(args)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
