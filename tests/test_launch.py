"""Entry-point plumbing: the persistent compile cache, the train CLI's
cell builder, the sparse bench's one-process-per-chip guard and the
chip smoke script's refusal to run without a TPU."""
import math
from pathlib import Path

import jax
import pytest

from repro.engine import build_host_engine
from repro.launch import compile_cache
from repro.launch.train import build_parser, paper_cell

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.enable_compile_cache()
    assert d == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.enable_compile_cache() == d


def test_paper_cell_is_what_the_cli_builds_and_it_trains():
    args = build_parser().parse_args(
        ["--users", "4", "--k", "1", "--rounds", "2",
         "--n-train", "512", "--n-test", "64"])
    cell = paper_cell(args)
    spec, params, _, user_data, _ = cell
    assert (spec.k_per_round, spec.rounds, len(user_data)) == (1, 2, 4)
    assert spec.strategy == "priority-distributed" and spec.use_counter
    assert params["fc1"]["w"].shape == (784, 200)
    hist = build_host_engine(*cell).run()
    assert len(hist.train_loss) == 2
    assert all(math.isfinite(x) for x in hist.train_loss + hist.accuracy)


def test_sparse_bench_refuses_children_while_holding_a_chip(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    from benchmarks import sparse_bench
    from jax._src import xla_bridge
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="child process"):
        sparse_bench.run()


def test_chip_smoke_refuses_to_run_without_a_tpu(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    with pytest.raises(RuntimeError, match="not a TPU"):
        chip_smoke.device_check(1)


def test_chip_smoke_contention_phase_runs_at_a_small_size(monkeypatch):
    """The phase's own checks (identical Pallas-path and oracle-path
    winners, K deliveries, collisions exercised) at a CPU size; on CPU
    both paths dispatch to the oracle."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    chip_smoke.contention_phase(lanes=2, contenders=2000, k=16)
