"""The benchmark's own copy of the cells' data recipe.

Synthetic class-prototype mixtures with the shapes and cardinalities of
CIFAR-10 and UCI HAR, and the Dirichlet non-IID partition (Hsu et al.
2019) with volume skew. Each draw has its own ``SeedSequence(seed,
spawn_key=(kind,))`` stream: kind 5 for the samples, kind 6 for the
partition. The run compares the program's dataset and client splits with
what this module makes from the same seed, so a change that makes the
workload easier shows as a run that is not correct.
"""
from __future__ import annotations

import hashlib

import numpy as np

KIND_DATASET = 5
KIND_PARTITION = 6

# name -> (n_train, n_test, sample shape, classes, separation, noise)
RECIPES = {
    "cifar10": (50000, 10000, (32, 32, 3), 10, 1.1, 3.0),
    "har": (7352, 2947, (128, 9), 6, 1.05, 3.5),
}


def stream(seed: int, kind: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed,
                                                        spawn_key=(kind,)))


def make_dataset(name: str, seed: int, scale: float):
    """(x_train, y_train, x_test, y_test) as float32 / int32 arrays."""
    n_train, n_test, shape, n_classes, sep, noise = RECIPES[name]
    n_tr, n_te = int(n_train * scale), int(n_test * scale)
    rng = stream(seed, KIND_DATASET)
    dim = int(np.prod(shape))
    protos = rng.normal(size=(n_classes, dim)) * sep / np.sqrt(dim)

    def make(n):
        y = rng.integers(0, n_classes, n)
        x = protos[y] + rng.normal(size=(n, dim)) * noise / np.sqrt(dim)
        return x.reshape((n,) + shape).astype(np.float32), y.astype(np.int32)

    xtr, ytr = make(n_tr)
    xte, yte = make(n_te)
    return xtr, ytr, xte, yte


def dirichlet_partition(labels: np.ndarray, n_clients: int, p: float,
                        seed: int, min_per_client: int = 8):
    """(splits list, label_dist [n, H], volumes [n]) for heterogeneity p."""
    rng = stream(seed, KIND_PARTITION)
    n_classes = int(labels.max()) + 1
    by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    for a in by_class:
        rng.shuffle(a)
    if p <= 0:
        splits = np.array_split(rng.permutation(len(labels)), n_clients)
    else:
        delta = 1.0 / p
        props = rng.dirichlet([delta] * n_classes, size=n_clients)
        vol = rng.dirichlet([max(delta, 0.2)] * n_clients)
        vol = np.maximum(vol, min_per_client / len(labels))
        vol = vol / vol.sum()
        counts = np.maximum(
            np.floor(props * (vol[:, None] * len(labels))).astype(int), 0)
        cursor = [0] * n_classes
        splits = []
        for i in range(n_clients):
            take = []
            for c in range(n_classes):
                k = min(counts[i, c], len(by_class[c]) - cursor[c])
                take.append(by_class[c][cursor[c]:cursor[c] + k])
                cursor[c] += k
            s = np.concatenate(take)
            if len(s) < min_per_client:
                extra = rng.integers(0, len(labels), min_per_client - len(s))
                s = np.concatenate([s, extra])
            rng.shuffle(s)
            splits.append(s)
    label_dist = np.zeros((n_clients, n_classes))
    volumes = np.zeros(n_clients, int)
    for i, s in enumerate(splits):
        volumes[i] = len(s)
        if len(s):
            label_dist[i] = np.bincount(labels[s], minlength=n_classes) / len(s)
    return splits, label_dist, volumes


def digest(*arrays) -> str:
    """sha256 over the arrays' dtypes, shapes and bytes."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def split_digest(splits) -> str:
    """Digest of per-client sample indices, order within a client kept."""
    off = np.cumsum([0] + [len(s) for s in splits]).astype(np.int64)
    flat = (np.concatenate(splits).astype(np.int64) if len(splits)
            else np.zeros(0, np.int64))
    return digest(off, flat)
