"""The benchmark's own synthetic data: a copy of the class-template
generator the program ships (`repro.data.synthetic`) and of its two
partitions (`repro.data.partition`), and a token generator with the
semantics of its ``make_token_stream``, so that a change to the
program's data code cannot move the yardstick.

A configuration's ``task`` picks the generator (``make_task_data``):

- ``classify``: images are class templates (smooth random cosines) plus
  noise and per-sample distortions, in [0, 1], NHWC float32, at the
  published shapes of the stand-in dataset; an example is an image and
  its label, ``{"x", "y"}``;
- ``next_token``: an example is one int32 sequence of ``seq_len + 1``
  token ids, ``{"tokens"}``, drawn from the Zipf(1.1) law of one topic
  over that topic's own permutation of the vocabulary.

Everything is drawn from the seed; no file is read.
"""
from __future__ import annotations

import numpy as np

#: confusable class pairs: each pair shares a base template
HARD_PAIRS = ((1, 3), (5, 7), (2, 9))


def _smooth_template(rng, shape):
    """Low-frequency random image in [0, 1] (four 2-D cosines per
    channel)."""
    h, w, c = shape
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
    img = np.zeros((h, w, c))
    for ch in range(c):
        for _ in range(4):
            fy, fx = rng.uniform(0.5, 3.0, 2)
            py, px = rng.uniform(0, 2 * np.pi, 2)
            amp = rng.uniform(0.3, 1.0)
            img[:, :, ch] += amp * np.cos(
                2 * np.pi * fy * yy / h + py) * np.cos(
                2 * np.pi * fx * xx / w + px)
    img -= img.min()
    img /= max(img.max(), 1e-9)
    return img


def _templates(rng, shape, classes, class_sep=1.0):
    shared = _smooth_template(rng, shape)
    in_pair = {c for p in HARD_PAIRS for c in p}
    templates = [None] * classes
    for a, b in HARD_PAIRS:
        base = (class_sep * _smooth_template(rng, shape)
                + (1.0 - class_sep) * shared)
        for c in (a, b):
            templates[c] = np.clip(
                base + 0.30 * class_sep * _smooth_template(rng, shape)
                - 0.15, 0.0, 1.0)
    for c in range(classes):
        if c not in in_pair:
            templates[c] = (class_sep * _smooth_template(rng, shape)
                            + (1.0 - class_sep) * shared)
    return np.stack(templates).astype(np.float32)


def _draw(rng, templates, n, noise):
    classes = len(templates)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = templates[y]                       # fancy index: a fresh copy
    x += noise * rng.standard_normal(x.shape, dtype=np.float32)
    x *= rng.uniform(0.7, 1.3, size=(n, 1, 1, 1)).astype(np.float32)
    x += rng.uniform(-0.15, 0.15, size=(n, 1, 1, 1)).astype(np.float32)
    np.clip(x, 0.0, 1.0, out=x)
    return x, y


def make_dataset(seed: int, shape, classes: int, n_train: int,
                 n_test: int, noise: float = 0.35):
    """((x_train, y_train), (x_test, y_test)) drawn from ``seed``."""
    ss = np.random.SeedSequence(int(seed))
    tmpl_ss, train_ss, test_ss = ss.spawn(3)
    templates = _templates(np.random.default_rng(tmpl_ss), tuple(shape),
                           classes)
    train = _draw(np.random.default_rng(train_ss), templates, n_train,
                  noise)
    test = _draw(np.random.default_rng(test_ss), templates, n_test, noise)
    return train, test


def partition(x, y, num_users: int, kind: str, seed: int,
              shards_per_user: int = 2):
    """Per-user ``(x, y)`` index arrays: ``iid`` is a random equal split;
    ``noniid`` the label-sorted shard split of McMahan et al. (each user
    gets ``shards_per_user`` shards of one or two labels)."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)).spawn(4)[3])
    if kind == "iid":
        idx = rng.permutation(len(y))
        return np.array_split(idx, num_users)
    if kind != "noniid":
        raise ValueError(f"unknown partition {kind!r}")
    n_shards = num_users * shards_per_user
    shard = len(y) // n_shards
    order = np.argsort(y, kind="stable")
    assign = rng.permutation(n_shards).reshape(num_users, shards_per_user)
    return [np.concatenate([order[s * shard:(s + 1) * shard] for s in row])
            for row in assign]


#: exponent of the Zipf law over a topic's token ranks
ZIPF_EXPONENT = 1.1


def make_token_data(seed: int, vocab_size: int, num_users: int,
                    per_user: int, seq_len: int, n_test: int,
                    kind: str, topics: int = 8):
    """Per-user ``{"tokens": (per_user, seq_len + 1)}`` int32 arrays and
    the test split ``{"tokens": (n_test, seq_len + 1)}``, drawn from
    ``seed``. Each topic is a Zipf(1.1) law over its own permutation of
    the ``vocab_size`` ids; each sequence is drawn from one topic.
    ``noniid`` gives a user 2 topics (the paper's 2-shard label skew),
    ``iid`` all of them; the test split draws from all topics."""
    if kind not in ("iid", "noniid"):
        raise ValueError(f"unknown partition {kind!r}")
    perm_ss, user_ss, test_ss = np.random.SeedSequence(int(seed)).spawn(3)
    prng = np.random.default_rng(perm_ss)
    perms = np.stack([prng.permutation(vocab_size) for _ in range(topics)])
    cdf = np.cumsum(np.arange(1, vocab_size + 1, dtype=np.float64)
                    ** -ZIPF_EXPONENT)
    cdf /= cdf[-1]

    def draw(rng, n, mine):
        t = mine[rng.integers(0, len(mine), size=n)]
        ranks = np.searchsorted(cdf, rng.random((n, seq_len + 1)),
                                side="right")
        ranks = np.minimum(ranks, vocab_size - 1)
        return {"tokens": perms[t[:, None], ranks].astype(np.int32)}

    every = np.arange(topics)
    users = []
    for ss in user_ss.spawn(num_users):
        rng = np.random.default_rng(ss)
        mine = (rng.choice(topics, size=2, replace=False)
                if kind == "noniid" else every)
        users.append(draw(rng, per_user, mine))
    return users, draw(np.random.default_rng(test_ss), n_test, every)


def make_task_data(seed: int, cfg: dict, tr: dict):
    """``(users, test)`` of a cell: per-user dicts of arrays whose first
    axis counts the user's examples, and the test split, drawn from
    ``seed`` by the generator of the configuration's ``task``."""
    task = cfg.get("task", "classify")
    U, epu = tr["users"], tr["examples_per_user"]
    if task == "next_token":
        return make_token_data(seed, cfg["vocab_size"], U, epu,
                               tr["seq_len"], cfg["n_test"],
                               tr["partition"], tr.get("topics", 8))
    if task != "classify":
        raise ValueError(f"unknown task {task!r}")
    (x, y), (xt, yt) = make_dataset(seed, cfg["input_shape"],
                                    cfg["n_classes"], U * epu, cfg["n_test"])
    if cfg["input_layout"] == "flat":
        x = x.reshape(len(x), -1)
        xt = xt.reshape(len(xt), -1)
    parts = partition(x, y, U, tr["partition"], seed,
                      tr.get("shards_per_user", 2))
    return [{"x": x[i], "y": y[i]} for i in parts], {"x": xt, "y": yt}
