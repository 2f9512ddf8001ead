"""The benchmark's data files parse, name what exists, and the
configurations count what the shapes say."""
import json
import re
from pathlib import Path

import pytest

from bench import compare, run

BENCH = Path(__file__).resolve().parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = [c["name"] for c in SPEC["configs"]]


def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for entry in (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
                  + SPEC["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert set(e2e) == {"rounds_per_s", "round_ms_p95", "setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", CELLS))
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_parse(cell):
    c = run.load_cell(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c.workload["config"] == entry["config"]
    assert c.workload["traffic"] == entry["traffic"]
    assert c.workload["chips"] == entry["chips"]
    assert set(c.workload["limits"]) == set(compare.CHECKS)
    p = c.params
    assert p["examples_per_user"] >= p["batch_size"]
    assert p["round_mode"] in ("fused", "sparse")
    assert 0 < run.CHECKED_ROUNDS <= p["rounds_per_job"]
    assert c.metrics, "the cell reports no per-layer metric"


@pytest.mark.parametrize("config", CONFIGS)
def test_config_files_parse(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    cfg = json.loads((BENCH.parent / entry["file"]).read_text())
    assert cfg["name"] == config and cfg["reduced"] == entry["reduced"]
    model = run._load_module(BENCH / "configs" / cfg["reference"])
    assert model.parameter_count(cfg) == cfg["parameters"]


def _model(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, run._load_module(BENCH / "configs" / cfg["reference"])


def test_mlp_counts():
    cfg, m = _model("mlp-fashion")
    assert m.parameter_count(cfg) == 159_010
    # forward 784*200 + 200*10 MACs, backward the weight gradients of
    # both layers and the input gradient of the second
    assert m.train_flops_per_example(cfg) == 2 * (
        2 * 784 * 200 + 3 * 200 * 10)


def test_cnn_counts():
    cfg, m = _model("cnn-cifar")
    assert m.parameter_count(cfg) == 993_034
    conv1 = 32 * 32 * 128 * 25 * 3
    conv2 = 16 * 16 * 256 * 25 * 128
    fc = 16384 * 10
    flops = m.train_flops_per_example(cfg)
    assert flops == 2 * (2 * conv1 + 3 * conv2 + 3 * fc)
    assert abs(flops / 1.32e9 - 1) < 0.02


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_init_matches_program_shapes(config):
    import jax
    from repro.models.paper_models import get_paper_model
    cfg, m = _model(config)
    pm = cfg["program_model"]
    init_fn, _ = get_paper_model(pm["name"], pm["dataset"])
    ours = jax.eval_shape(lambda k: m.init(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(ours)] == \
        [a.shape for a in jax.tree.leaves(theirs)]


def test_peaks_table_has_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peak_of("TPU v0 imaginary")
