"""FL training driver — runs the paper's experiment (or the LLM variant)
end-to-end on whatever devices exist, through the engine API.

Examples:
  # the paper's setup: 10 users, 2/round, MLP on (synthetic) Fashion-MNIST
  PYTHONPATH=src python -m repro.launch.train --model mlp --dataset fashion \
      --strategy priority-distributed --rounds 100

  # the same cell swept over 4 seeds as ONE stacked device program
  PYTHONPATH=src python -m repro.launch.train --sweep-seeds 4 --rounds 100

  # federated finetune of a reduced assigned arch on synthetic tokens
  PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b --rounds 20
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.data import (make_classification_dataset, make_token_stream,
                        partition_iid, partition_noniid_shards)
from repro.engine import (ExperimentSpec, FLEngine, PAPER_STRATEGIES,
                          SweepSpec, available_strategies,
                          build_host_engine, make_accuracy_eval)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.paper_models import get_paper_model
from repro.models.model import init_params, compute_loss
from repro.checkpoint import save_checkpoint


def _spec_from_args(args) -> ExperimentSpec:
    return ExperimentSpec(
        k_per_round=args.k, rounds=args.rounds, strategy=args.strategy,
        cw_base=args.cw_base, use_counter=not args.no_counter,
        counter_threshold=args.threshold, lr=args.lr,
        batch_size=args.batch_size, seed=args.seed,
        contention_backend=args.contention_backend)


def paper_cell(args):
    """The paper cell from the CLI arguments: ``(spec, init_params,
    loss_fn, user_data, eval_fn)``, the arguments of
    ``build_host_engine``."""
    (xtr, ytr), (xte, yte) = make_classification_dataset(
        args.dataset, n_train=args.n_train, n_test=args.n_test,
        seed=args.seed)
    init_fn, apply_fn = get_paper_model(args.model, args.dataset)
    if args.model == "mlp":
        xtr = xtr.reshape(len(xtr), -1)
        xte = xte.reshape(len(xte), -1)
    part = partition_iid if args.iid else partition_noniid_shards
    users = part(xtr, ytr, args.users, seed=args.seed)
    user_data = [{"x": x, "y": y} for x, y in users]

    def loss_fn(params, batch):
        logits = apply_fn(params, batch["x"])
        oh = jax.nn.one_hot(batch["y"], 10)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    eval_fn = make_accuracy_eval(apply_fn, xte, yte)
    params = init_fn(jax.random.PRNGKey(args.seed))
    return _spec_from_args(args), params, loss_fn, user_data, eval_fn


def build_paper_engine(args) -> FLEngine:
    return build_host_engine(*paper_cell(args))


def build_llm_engine(args) -> FLEngine:
    cfg_model = get_config(args.arch).reduced()
    seq = args.llm_seq
    user_seqs = make_token_stream(
        args.users, seq, args.llm_seqs_per_user, cfg_model.vocab_size,
        noniid=not args.iid, seed=args.seed)
    user_data = [{"tokens": s} for s in user_seqs]
    test_tokens = np.concatenate(
        make_token_stream(2, seq, 8, cfg_model.vocab_size,
                          noniid=False, seed=args.seed + 99))

    loss_fn = functools.partial(compute_loss, cfg=cfg_model)

    @jax.jit
    def eval_loss(params):
        return compute_loss(params, {"tokens": jnp.asarray(test_tokens)},
                            cfg_model)

    def eval_fn(params):
        return -float(eval_loss(params))  # "metric up" convention

    params = init_params(jax.random.PRNGKey(args.seed), cfg_model)
    return build_host_engine(_spec_from_args(args), params, loss_fn,
                             user_data, eval_fn)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--dataset", default="fashion",
                    choices=["fashion", "cifar"])
    ap.add_argument("--arch", default=None, choices=ARCH_IDS,
                    help="federated-finetune a reduced assigned arch "
                         "instead of the paper model")
    ap.add_argument("--strategy", default="priority-distributed",
                    choices=available_strategies() or PAPER_STRATEGIES)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--users", type=int, default=10)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--no-counter", action="store_true")
    ap.add_argument("--threshold", type=float, default=0.16)
    ap.add_argument("--cw-base", type=float, default=2048.0)
    ap.add_argument("--contention-backend", default="numpy",
                    choices=["numpy", "device"],
                    help="CSMA engine: numpy reference or the "
                         "device-resident JAX/Pallas port (DESIGN.md §6)")
    ap.add_argument("--n-train", type=int, default=6000)
    ap.add_argument("--n-test", type=int, default=1000)
    ap.add_argument("--llm-seq", type=int, default=128)
    ap.add_argument("--llm-seqs-per-user", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep-seeds", type=int, default=1,
                    help="run this many seed-varied copies of the cell "
                         "as ONE run_sweep device program")
    ap.add_argument("--out", default=None, help="history JSON path")
    ap.add_argument("--ckpt", default=None, help="final checkpoint path")
    ap.add_argument("--verbose", action="store_true")
    return ap


def main():
    enable_compile_cache()
    args = build_parser().parse_args()

    t0 = time.perf_counter()
    engine = (build_llm_engine(args) if args.arch
              else build_paper_engine(args))
    if args.sweep_seeds > 1:
        sweep = SweepSpec.grid(
            engine.spec, seed=range(args.seed,
                                    args.seed + args.sweep_seeds))
        result = engine.run_sweep(sweep, verbose=args.verbose)
        hist = result.histories[0]       # lead cell drives the summary
        final_params = result.lane_params(0)
        extra = {
            "sweep_cells": len(result),
            "sweep_labels": result.labels,
            "sweep_best_metric": [max(h.accuracy) if h.accuracy else None
                                  for h in result],
        }
    else:
        hist = engine.run(verbose=args.verbose)
        final_params = engine.global_params
        extra = {}
    dt = time.perf_counter() - t0

    summary = {
        "strategy": args.strategy,
        "final_metric": hist.accuracy[-1] if hist.accuracy else None,
        "best_metric": max(hist.accuracy) if hist.accuracy else None,
        "selections": hist.selections.tolist(),
        "uploads_total": hist.uploads_total,
        "wall_s": round(dt, 1),
        **extra,
    }
    print(json.dumps(summary, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**summary,
                       "accuracy": hist.accuracy,
                       "eval_round": hist.eval_round,
                       "train_loss": hist.train_loss}, f, indent=1)
    if args.ckpt:
        save_checkpoint(args.ckpt, final_params)


if __name__ == "__main__":
    main()
