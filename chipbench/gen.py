"""Traffic generators of the benchmark: everything a run feeds the program,
made from ``--seed``.

Copies of the program's own generators (``repro.data.synthetic``,
``repro.data.partition``, ``repro.dfl.lm_worker.worker_streams``), kept here
so that no later change to the program can move the yardstick.  The
reference implementations use these too, so the program and the reference
see the same inputs and nothing else in common.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple, Tuple

import numpy as np


class Classification(NamedTuple):
    x: np.ndarray        # (n, dim) float32
    y: np.ndarray        # (n,) int32
    n_classes: int


def make_classification(n_samples: int, dim: int, n_classes: int = 10,
                        sep: float = 2.0, seed: int = 0) -> Classification:
    """Gaussian blobs: class means on a sphere of radius ``sep``, unit
    covariance."""
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(n_classes, dim))
    means = sep * means / np.linalg.norm(means, axis=1, keepdims=True)
    y = rng.integers(0, n_classes, size=n_samples)
    x = means[y] + rng.normal(size=(n_samples, dim))
    return Classification(x.astype(np.float32), y.astype(np.int32), n_classes)


def train_test_split(data: Classification, test_frac: float, seed: int
                     ) -> Tuple[Classification, Classification]:
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(data.y))
    n_test = int(len(data.y) * test_frac)
    te, tr = perm[:n_test], perm[n_test:]
    return (Classification(data.x[tr], data.y[tr], data.n_classes),
            Classification(data.x[te], data.y[te], data.n_classes))


def dirichlet_partition(y: np.ndarray, n_classes: int, n_workers: int,
                        phi: float, seed: int, min_per_worker: int = 8
                        ) -> Tuple[List[np.ndarray], np.ndarray]:
    """Dirichlet(phi) class skew over ``n_workers``; phi >= 1 is exactly
    IID.  Returns per-worker sample ids and the (N, C) class histogram."""
    rng = np.random.default_rng(seed)
    idx_by_class = [np.flatnonzero(y == c) for c in range(n_classes)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    if phi >= 1.0:
        props = np.full((n_classes, n_workers), 1.0 / n_workers)
    else:
        props = rng.dirichlet([phi] * n_workers, size=n_classes)
    assign: List[List[int]] = [[] for _ in range(n_workers)]
    counts = np.zeros((n_workers, n_classes), np.int64)
    for c in range(n_classes):
        idx = idx_by_class[c]
        splits = (np.cumsum(props[c]) * len(idx)).astype(int)[:-1]
        for w, part in enumerate(np.split(idx, splits)):
            assign[w].extend(part.tolist())
            counts[w, c] = len(part)
    every = np.arange(len(y))
    for w in range(n_workers):
        if len(assign[w]) < min_per_worker:
            extra = rng.choice(every, size=min_per_worker - len(assign[w]),
                               replace=False)
            assign[w].extend(extra.tolist())
            for e in extra:
                counts[w, y[e]] += 1
    return [np.array(a, np.int64) for a in assign], counts


def make_token_stream(vocab_size: int, n_tokens: int, seed: int) -> np.ndarray:
    """A noisy order-2 Markov chain over the vocabulary."""
    rng = np.random.default_rng(seed)
    out = np.empty(n_tokens, np.int32)
    state = 1
    for i in range(n_tokens):
        if rng.random() < 0.15:
            tok = rng.integers(0, vocab_size)
        else:
            tok = (state * 1103515245 + 12345) % vocab_size
        out[i] = tok
        state = (state * 2 + int(tok)) % (1 << 31)
    return out


def worker_streams(vocab_size: int, n_workers: int, batch: int, seq: int,
                   seed: int, n_stream: int = 400_000
                   ) -> Iterator[Dict[str, np.ndarray]]:
    """Per-round (N, batch, seq) token batches: worker w draws its windows
    from its own slice of one long stream."""
    stream = make_token_stream(vocab_size, n_stream, seed)
    n = len(stream) - seq - 1
    rng = np.random.default_rng(seed)
    slice_len = n // n_workers
    windows = np.lib.stride_tricks.sliding_window_view(stream, seq + 1)
    while True:
        starts = np.empty((n_workers, batch), np.int64)
        for w in range(n_workers):
            lo = w * slice_len % max(n - slice_len, 1)
            starts[w] = rng.integers(lo, lo + max(slice_len - seq - 1, 1),
                                     size=batch)
        win = windows[starts]
        yield {"tokens": np.ascontiguousarray(win[..., :-1]),
               "labels": np.ascontiguousarray(win[..., 1:])}
