"""The training feed: a pool of distinct seeded batches.

A pure function of (parameters, seed, shapes). Rows are flat float32
CHW images in [0, 1) and uniform labels; every row of the pool differs.
The reader cycles the pool, so the feed path does the same work at
every step whatever the seed.
"""

import numpy as np


def pool(params: dict, seed: int, batch: int, row_dim: int, classes: int):
    """(rows [pool*batch, row_dim] float32, labels [pool*batch] int64)."""
    n = int(params["pool_batches"]) * batch
    rng = np.random.default_rng(int(seed))
    rows = rng.random((n, row_dim), dtype=np.float32)
    labels = rng.integers(0, classes, n)
    return rows, labels


def batch_of(rows, labels, batch: int, k: int):
    """The k-th batch the reader yields (it cycles the pool)."""
    nb = rows.shape[0] // batch
    i = (k % nb) * batch
    return rows[i:i + batch], labels[i:i + batch]
