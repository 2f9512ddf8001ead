"""The benchmark's data files parse, name what exists, and the
configurations count what the shapes say."""
import json
import re
from pathlib import Path

import jax
import pytest

from bench import compare, reference, run

BENCH = Path(__file__).resolve().parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = [c["name"] for c in SPEC["configs"]]
#: the CPU fixture's token configuration, outside BENCHMARK.json
FIXTURES = BENCH / "fixtures"


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


def _model(name, root=BENCH):
    cfg = json.loads((root / "configs" / f"{name}.json").read_text())
    return cfg, run._load_module(root / "configs" / cfg["reference"])


def test_mlp_counts():
    cfg, m = _model("mlp-fashion")
    assert m.parameter_count(cfg) == 159_010
    # forward 784*200 + 200*10 MACs, backward the weight gradients of
    # both layers and the input gradient of the second
    assert m.train_flops_per_example(cfg, run.load_cell("paper-mlp").params) \
        == 2 * (2 * 784 * 200 + 3 * 200 * 10)


def test_cnn_counts():
    cfg, m = _model("cnn-cifar")
    assert m.parameter_count(cfg) == 993_034
    conv1 = 32 * 32 * 128 * 25 * 3
    conv2 = 16 * 16 * 256 * 25 * 128
    fc = 16384 * 10
    flops = m.train_flops_per_example(cfg, run.load_cell("paper-cnn").params)
    assert flops == 2 * (2 * conv1 + 3 * conv2 + 3 * fc)
    assert abs(flops / 1.32e9 - 1) < 0.02


def test_token_fixture_counts_the_trained_part():
    cfg, m = _model("tiny-lm", FIXTURES)
    trained, frozen = m.split(m.init(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(trained))
    assert m.parameter_count(cfg) == cfg["parameters"] == n
    assert frozen["embed"].shape == (cfg["vocab_size"], cfg["d_model"])


@pytest.mark.parametrize("config,cell,root", [
    pytest.param(c, next(w["name"] for w in SPEC["workloads"]
                         if w["config"] == c), BENCH, id=c)
    for c in CONFIGS] + [pytest.param("tiny-lm", "tiny-lm", FIXTURES,
                                      id="tiny-lm")])
def test_reference_init_matches_program_shapes(config, cell, root):
    """The configuration's program builder takes the reference's initial
    weights at the published widths: the trained part it returns has the
    leaves of the reference's ``split``, and its loss traces on a batch
    of the task's examples, as the reference's does."""
    cfg, m = _model(config, root)
    users, test = _examples(run.load_cell(cell, root))
    batch = users[0]
    weights = jax.eval_shape(lambda k: m.init(k, cfg), jax.random.PRNGKey(0))

    def program(w):
        trained, loss_fn, _, _ = run.program_builder(cfg)(cfg, w, test)
        return trained, loss_fn(trained, batch)

    trained, value = jax.eval_shape(program, weights)
    ours, frozen = reference.split(m, weights)
    assert jax.tree.structure(trained) == jax.tree.structure(ours)
    assert [a.shape for a in jax.tree.leaves(trained)] == \
        [a.shape for a in jax.tree.leaves(ours)]
    ref = jax.eval_shape(lambda t, f, b: reference.loss_of(m)(t, f, b, cfg),
                         ours, frozen, batch)
    assert value.shape == ref.shape == ()


def _examples(cell):
    """Two users' and a test split's worth of the cell's examples, at the
    configuration's published shapes."""
    from bench.data import make_task_data
    tr = {**cell.params, "users": 2, "examples_per_user": 2}
    return make_task_data(5, {**cell.config, "n_test": 2}, tr)


def test_peaks_table_has_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peak_of("TPU v0 imaginary")


def test_token_data_is_drawn_from_the_seed():
    from bench.data import make_token_data
    args = (64, 5, 7, 12, 9, "noniid", 4)
    users, test = make_token_data(2 ** 31 + 3, *args)
    again, test2 = make_token_data(2 ** 31 + 3, *args)
    other, _ = make_token_data(2 ** 31 + 4, *args)
    assert len(users) == 5 and test["tokens"].shape == (9, 13)
    for u, v in zip(users, again):
        assert u["tokens"].shape == (7, 13) and u["tokens"].dtype == "int32"
        assert 0 <= u["tokens"].min() and u["tokens"].max() < 64
        assert (u["tokens"] == v["tokens"]).all()
    assert (test["tokens"] == test2["tokens"]).all()
    assert any((u["tokens"] != v["tokens"]).any()
               for u, v in zip(users, other))


def test_noniid_tokens_skew_each_user_to_two_topics():
    """A non-IID user's token mix lies farther from the population's
    than an IID user's: two topics of eight against all of them."""
    import numpy as np
    from bench.data import make_token_data

    def spread(kind):
        users, _ = make_token_data(11, 64, 16, 64, 16, 4, kind, 8)
        hists = np.stack([np.bincount(u["tokens"].ravel(), minlength=64)
                          for u in users]).astype(float)
        probs = hists / hists.sum(1, keepdims=True)
        pop = hists.sum(0) / hists.sum()
        return 0.5 * np.abs(probs - pop).sum(1).mean()
    assert spread("noniid") > 1.5 * spread("iid")
    with pytest.raises(ValueError):
        make_token_data(11, 64, 2, 2, 4, 2, "dirichlet")
