"""Compile every Pallas kernel for a TPU v5e chip that is described, not
attached.

Interpret mode (the other kernel tests) runs a kernel's body but not
the chip's compiler, which refuses blocks that are not tiled to its
(8, 128) layout and scalar stores to vector memory. These tests lower
each ``*_pallas`` entry at the paper MLP's widths for one chip of a
``v5e:2x2`` topology and require the compiled program to hold the
kernel (``tpu_custom_call``) under the name its Pallas call gives it,
which a device trace shows. The HostBackend round programs compile
too: with their kernels on one chip, and over the four chips of the
topology with the cohort sharded, where GSPMD cannot partition a
kernel and every ``kernels.ops`` call must take its jnp oracle. The
dense sweep's round program compiles at the paper cells' shapes with
the data stack as its argument.
Nothing runs.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU's
library, and every test worker imports this file. The persistent
compilation cache is off around these compiles, since an entry
compiled for a described chip cannot be read back without one.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.aircomp import aircomp_pallas
from repro.kernels.contention import contention_event_pallas
from repro.kernels.delta_norm import delta_norm_pallas
from repro.kernels.fedavg import fedavg_pallas
from repro.kernels.fused_sgd import fused_sgd_pallas
from repro.kernels.gather import gather_combine_pallas
from repro.kernels.robust import robust_pallas
from repro.kernels.server_opt import server_opt_pallas

#: the paper MLP's widest layer (784 x 200) and its cohort: U=10 users,
#: K=2 winners a round, 4 sweep lanes
LAYER = (784, 200)
U, K, LANES = 10, 2, 4
#: the device contention pool: 4 lanes of the first 512 expiries, and
#: one lane over the full 1e5-contender population
POOLS = ((LANES, 512), (1, 100_000))
F32, I32 = jnp.float32, jnp.int32


def _contention(shape):
    def fn(c, live, dbl, win, rnd):
        return contention_event_pallas(c, live, dbl, win, rnd, 5)
    return fn, [(shape, I32), (shape, jnp.bool_), (shape, I32),
                (shape, F32), (shape, F32)]


#: name -> (function, [(shape, dtype), ...] of its arguments)
CASES = {
    "fedavg": (fedavg_pallas, [((U,) + LAYER, F32), ((U,), F32)]),
    "robust": (robust_pallas, [((K,) + LAYER, F32), ((K,), F32),
                               ((K,), F32), (LAYER, F32)]),
    "aircomp": (aircomp_pallas, [((K,) + LAYER, F32), ((K,), F32),
                                 (LAYER, F32), ((), F32)]),
    "server_opt": (server_opt_pallas, [(LAYER, F32)] * 4 + [((5,), F32)]),
    "fused_sgd": (fused_sgd_pallas, [(LAYER, F32), (LAYER, F32),
                                     ((), F32)]),
    "delta_norm": (delta_norm_pallas, [(LAYER, F32), (LAYER, F32)]),
    # Eq. 2 as the fused round calls it: over the stacked cohort
    "delta_norm_vmap_users": (
        jax.vmap(delta_norm_pallas, in_axes=(0, None)),
        [((U,) + LAYER, F32), (LAYER, F32)]),
    "gather_combine": (gather_combine_pallas,
                       [((U,) + LAYER, F32), ((K + 1,), I32),
                        ((K + 1,), F32), (LAYER, F32)]),
    # Eq. 1 as the sweep merge calls it: one gather per lane
    "gather_combine_vmap_lanes": (
        jax.vmap(gather_combine_pallas),
        [((LANES, U) + LAYER, F32), ((LANES, K + 1), I32),
         ((LANES, K + 1), F32), ((LANES,) + LAYER, F32)]),
    **{f"contention_event_{b}x{n}": _contention((b, n)) for b, n in POOLS},
}

#: the names the Pallas calls of each case give their custom calls
KERNEL_NAMES = {
    "fedavg": ("fedavg_combine",), "robust": ("robust_combine",),
    "aircomp": ("aircomp_combine",), "server_opt": ("server_opt_combine",),
    "fused_sgd": ("fused_sgd",), "delta_norm": ("delta_norm",),
    "gather_combine": ("gather_combine",),
    "contention_event": ("contention_min", "contention_expiry",
                         "contention_transition"),
}


def _kernel_calls(compiled):
    """The names each Mosaic custom call of the compiled program carries
    in its ``op_name`` (the path segments, ``vmap(...)`` unwrapped)."""
    out = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op = re.search(r'op_name="([^"]*)"', line).group(1)
            out.append({re.sub(r"^(vmap\()+|\)+$", "", seg)
                        for seg in op.split("/")})
    return out


def _names_ok(calls, names):
    """Every kernel in ``names`` is there, and every custom call carries
    one of the names."""
    return (all(any(n in c for c in calls) for n in names)
            and all(c & set(names) for c in calls))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:          # no TPU library or no v5e target
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    calls = _kernel_calls(compiled)
    assert calls
    base = next(k for k in KERNEL_NAMES if name.startswith(k))
    assert _names_ok(calls, KERNEL_NAMES[base])


#: the cohort-sharded round programs: (round mode, program)
ROUND_PROGRAMS = [("fused", "round"), ("fused", "merge"),
                  ("sparse", "round"), ("sparse", "merge")]
SHARD_U, SHARD_K = 8, 4


def _round_program(mode, which, mesh):
    """A HostBackend round program at the paper MLP's widths with its
    argument shapes; the cohort (fused: U users, one sweep lane) or the
    winner stack (sparse: K rows) splits over ``mesh`` when given."""
    from repro.engine import build_host_engine
    from repro.engine.backends import data_rows
    from repro.launch.train import build_parser, paper_cell

    args = build_parser().parse_args(
        ["--users", str(SHARD_U), "--k", str(SHARD_K),
         "--n-train", str(SHARD_U * 60), "--n-test", "64"])
    spec, params, loss_fn, user_data, _ = paper_cell(args)
    be = build_host_engine(spec, params, loss_fn, user_data,
                           round_mode=mode, mesh=mesh).backend
    be._ensure_xstack()
    rows = (1, SHARD_U) if mode == "fused" else (SHARD_K,)
    lead = rows[:-1]
    sds = jax.ShapeDtypeStruct
    stack = jax.tree.map(lambda p: sds(rows + p.shape, p.dtype), params)
    glob = jax.tree.map(lambda p: sds(lead + p.shape, p.dtype), params)
    batched = jax.tree.map(
        lambda x: sds(rows + (be._nb, be._batch_size) + x.shape[2:],
                      x.dtype), be._xstack)
    idx, w = sds(lead + (SHARD_K,), I32), sds(lead + (SHARD_K,), F32)
    if mode == "fused":
        # the round gathers its batches out of the resident data stack
        _, rnd, merge = be._build_sweep_fns(1)
        data = jax.tree.map(
            lambda x: sds(x.shape, x.dtype, sharding=be._data_sharding(1)),
            jax.tree.map(data_rows, be._xstack))
        rows_idx = sds(rows + (be._nb, be._batch_size), I32)
        return {"round": (rnd, (stack, data, rows_idx, True)),
                "merge": (merge, (stack, idx, w, glob))}[which]
    be._build_sparse()
    return {"round": (be._sparse_round, (stack, batched)),
            "merge": (be._fused_merge_fn, (stack, idx, w, glob))}[which]


@pytest.mark.parametrize("mode,which", ROUND_PROGRAMS)
def test_sharded_round_compiles_for_four_v5e_chips(mode, which, topo,
                                                   no_compile_cache,
                                                   monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, so once the cohort (or
    the winner stack) splits over chips every kernels.ops call in the
    program, the local SGD update included, must take its jnp oracle.
    kernels.ops is steered to the compiled kernels here, as on a TPU."""
    from repro.kernels import ops as kops
    from repro.sharding.cohort import cohort_mesh
    monkeypatch.setattr(kops, "_mode", lambda uk, interp: (bool(uk), False))
    fn, args = _round_program(mode, which, cohort_mesh(topo.devices))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_sharded_round_gathers_from_its_own_shard(topo, no_compile_cache,
                                                  monkeypatch):
    """With the cohort split over four chips, each chip's steps gather
    their batches out of its own quarter of the resident data stack:
    the program moves no part of the stack between chips."""
    from repro.kernels import ops as kops
    from repro.sharding.cohort import cohort_mesh
    monkeypatch.setattr(kops, "_mode", lambda uk, interp: (bool(uk), False))
    fn, (stack, data, idx, prio) = _round_program(
        "fused", "round", cohort_mesh(topo.devices))
    text = fn.lower(stack, data, idx, prio).compile().as_text()
    assert not re.search(r"\b(all-gather|all-to-all|collective-permute)"
                         r"(-start)?\(", text)
    x = data["x"].shape
    shard = ",".join(map(str, (x[0] // len(topo.devices),) + x[1:]))
    operands = re.findall(r"= \S+ gather\(%(\S+),", text)
    params = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    assert f"f32[{shard}]" in {params.get(p) for p in operands}


@pytest.mark.parametrize("mode,which", ROUND_PROGRAMS)
def test_unsharded_round_compiles_with_its_kernels(mode, which, one_chip,
                                                   monkeypatch):
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_mode", lambda uk, interp: (bool(uk), False))
    fn, args = _round_program(mode, which, None)
    args = jax.tree.map(
        lambda s: (jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                   if isinstance(s, jax.ShapeDtypeStruct) else s), args)
    compiled = fn.lower(*args).compile()
    names = (("fused_sgd", "delta_norm") if which == "round"
             else ("gather_combine",))
    assert _names_ok(_kernel_calls(compiled), names)


#: the benchmark's paper cells: (model, dataset, examples a user, shape
#: of an example), U=10 users, batch 32, one sweep lane
PAPER_CELLS = {"mlp-fashion": ("mlp", "fashion", 6000, (784,)),
               "cnn-cifar": ("cnn", "cifar", 5000, (32, 32, 3))}


@pytest.mark.parametrize("name", sorted(PAPER_CELLS))
def test_sweep_round_gathers_from_the_resident_stack(name, one_chip,
                                                     monkeypatch):
    """The dense sweep's round program at a paper cell's shapes takes
    the data stack, one row an example, as an argument, not a constant,
    and gathers the round's batches out of it."""
    import math

    import numpy as np
    from repro.engine import HostBackend
    from repro.engine.backends import TILE
    from repro.kernels import ops as kops
    from repro.models.paper_models import get_paper_model
    monkeypatch.setattr(kops, "_mode", lambda uk, interp: (bool(uk), False))
    model, dataset, n, shape = PAPER_CELLS[name]
    init, apply_fn = get_paper_model(model, dataset)

    def loss_fn(params, batch):
        logits = apply_fn(params, batch["x"])
        oh = jax.nn.one_hot(batch["y"], 10)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))
    bs = 32
    # views of one zero: the shapes of the cell's data, not its bytes
    user = {"x": np.broadcast_to(np.float32(0), (n,) + shape),
            "y": np.broadcast_to(np.int32(0), (n,))}
    be = HostBackend(loss_fn, [user] * U, batch_size=bs)
    sds = jax.ShapeDtypeStruct
    be._xstack = {"x": sds((U, n) + shape, F32), "y": sds((U, n), I32)}
    be._nb = n // bs
    _, rnd, _ = be._build_sweep_fns(1)
    params = jax.eval_shape(init, jax.random.key(0))
    stack = jax.tree.map(
        lambda p: sds((1, U) + p.shape, p.dtype, sharding=one_chip), params)
    tile = math.prod(TILE)
    rows = -(-math.prod(shape) // tile) * TILE[0]
    data = {"x": sds((U, n, rows, TILE[1]), F32, sharding=one_chip),
            "y": sds((U, n), I32, sharding=one_chip)}
    idx = sds((1, U, be._nb, bs), I32, sharding=one_chip)
    compiled = rnd.lower(stack, data, idx, True).compile()
    text = compiled.as_text()
    # the examples keep their index major on the chip
    dims = f"{U},{n},{rows},{TILE[1]}"
    assert re.search(rf"= f32\[{dims}\]{{3,2,1,0:\S* parameter\(", text)
    assert re.search(rf"= s32\[{U},{n}\]\S* parameter\(", text)
    assert not re.search(rf"f32\[{dims}\]\S* constant\(", text)
    assert re.search(r"\bgather\(", text)
    stack_bytes = U * n * (4 * rows * TILE[1] + 4)
    assert compiled.memory_analysis().argument_size_in_bytes >= stack_bytes
    assert _names_ok(_kernel_calls(compiled), ("fused_sgd", "delta_norm"))
