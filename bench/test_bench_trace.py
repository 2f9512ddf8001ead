"""The trace reduction: hand-made events, a short window recorded on a
TPU v5e (``trace_fixture.json``, one round of ``paper-mlp`` with the
benchmark's spans; the ops inside the training loop's ``while`` are
left out of the file, which the ``while`` op itself covers), and the
clock that the host spans share with the trace."""
from pathlib import Path

import pytest

from bench import run, tracing
from bench.tracing import Event

FIXTURE = Path(__file__).resolve().parent / "trace_fixture.json"
DEV = "/device:TPU:0"


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert tracing.union_ns(iv) == 30
    assert tracing.gaps(iv, -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    assert tracing.gaps([], 0, 5) == [(0, 5)]


def test_self_times_subtract_nested_ops():
    outer = Event(DEV, "XLA Ops", "%while.1 = f32[] while(x)", 0, 100)
    a = Event(DEV, "XLA Ops", "%fusion.2 = f32[] fusion(x)", 10, 20)
    b = Event(DEV, "XLA Ops", "%fusion.3 = f32[] fusion(x)", 40, 30)
    st = {e.name: s for e, s in tracing.self_times([b, outer, a])}
    assert st == {outer.name: 50, a.name: 20, b.name: 30}


def test_op_label():
    assert tracing.op_label(
        "%closed_call.31 = f32[10,1280,128]{2,1,0} custom-call(f32[1] %a)"
    ) == ("closed_call.31", "custom-call")
    assert tracing.op_label(
        "%copy-start = (f32[1,2]{1,0}, u32[]) copy-start(f32[1,2] %x)"
    ) == ("copy-start", "copy-start")
    assert tracing.op_label("%while.8 = (s32[1,512]{1,0}, pred[1,5") == \
        ("while.8", "while")


def _window():
    host = "/host:CPU"
    return [
        Event(host, "python", "fl.job", 0, 1000),
        Event(host, "python", "fl.batches", 0, 300),
        Event(host, "python", "fl.eval", 700, 250),
        Event(DEV, "XLA Modules", "jit_sweep_round(1)", 300, 300),
        Event(DEV, "XLA Ops", "%fusion.1 = f32[] fusion(x)", 300, 200),
        Event(DEV, "XLA Ops", "%closed_call.2 = (f32[1,1], f32[1,1]) "
              "custom-call(x), custom_call_target=\"tpu_custom_call\"",
              520, 60),
        Event(DEV, "XLA Modules", "jit_sweep_merge(2)", 650, 20),
        Event(DEV, "XLA Ops", "%closed_call.3 = f32[8] custom-call(s32[2] "
              "%idx), custom_call_target=\"tpu_custom_call\"", 650, 20),
    ]


def test_summary_of_a_hand_made_window():
    s = tracing.summarize(_window())
    assert s.window == (0, 1000)
    assert s.busy_ns[DEV] == 280
    assert s.module_ns(r"^jit_sweep_round$") == 300
    assert {o.module for o in s.ops} == {"jit_sweep_round",
                                         "jit_sweep_merge"}
    # the longest idle gap is the eval's, then the batch pipeline's
    assert s.idle_gaps[0] == ("fl.eval", 330)
    assert s.idle_gaps[1] == ("fl.batches", 300)
    assert s.span_ns("fl.eval") == 250
    by_span = s.idle_by_span()
    assert [n for n, _ in by_span] == ["fl.eval", "fl.batches", "fl.job"]
    assert [t for _, t in by_span] == pytest.approx([330e-9, 300e-9, 90e-9])


def _view(summary, rounds, cell="paper-mlp"):
    c = run.load_cell(cell)
    return run.TraceView(
        trace=summary, rounds=rounds, window_s=summary.window_ns * 1e-9,
        chips=1, peak=run.peak_of("TPU v5 lite"), cell=c,
        parameters=c.model.parameter_count(c.config),
        flops_per_example=c.model.train_flops_per_example(c.config, c.params),
        compiles=0)


def test_readers_on_a_hand_made_window():
    v = _view(tracing.summarize(_window()), rounds=1)
    assert run.read_metric("device.idle_share", v) == pytest.approx(72.0)
    assert run.read_metric("train.device_ms", v) == pytest.approx(3e-4)
    assert run.read_metric("merge.device_ms", v) == pytest.approx(2e-5)
    assert run.read_metric("batches.host_ms", v) == pytest.approx(3e-4)
    # (rows + 1) * P * 4 bytes at 819 GB/s over 60 ns
    want = 100 * 11 * 159010 * 4 / 819e9 / 60e-9
    assert run.read_metric("delta_norm_roofline", v) == pytest.approx(want)
    want = 100 * 4 * 159010 * 4 / 819e9 / 20e-9
    assert run.read_metric("gather_combine_roofline", v) == \
        pytest.approx(want)


def test_recorded_window():
    events = tracing.load_events(str(FIXTURE))
    s = tracing.summarize(events)
    assert s.devices == [DEV]
    busy = tracing.union_ns([
        (max(e.start_ns, s.window[0]), min(e.end_ns, s.window[1]))
        for e in events if e.plane == DEV and e.line == "XLA Ops"
        and e.end_ns > s.window[0] and e.start_ns < s.window[1]])
    assert 0 < s.busy_ns[DEV] == busy <= s.window_ns
    assert s.module_ns(r"^jit_sweep_round$") > 0
    v = _view(s, rounds=1)
    for name in ("device.idle_share", "train.device_ms", "merge.device_ms",
                 "batches.host_ms", "selection.host_ms",
                 "delta_norm_roofline", "gather_combine_roofline"):
        value = run.read_metric(name, v)
        assert value is not None and value > 0, name
    for name in ("delta_norm_roofline", "gather_combine_roofline",
                 "device.idle_share"):
        assert run.read_metric(name, v) <= 100, name
    assert s.top_ops(10) and s.idle_gaps


def test_host_spans_meet_the_trace_clock(tmp_path):
    """The profile's start, read from the trace, puts spans taken with
    ``time.time_ns`` on the clock of the trace's events."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # only so that the mark is recorded
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.time_ns()
    with jax.profiler.TraceAnnotation("fl.mark"):
        f(jnp.ones(4)).block_until_ready()
    t1 = time.time_ns()
    jax.profiler.stop_trace()
    path = tracing.find_xspace(str(tmp_path))
    _, start = tracing.read_xspace(path)
    mark = next(e for p in ProfileData.from_file(path).planes
                for line in p.lines for e in line.events
                if e.name == "fl.mark")
    assert t0 - 1e6 <= start + mark.start_ns <= t1
    [span] = tracing.host_spans([("fl.batches", t0, t1)], start)
    assert span.start_ns == t0 - start and span.dur_ns == t1 - t0
