"""The one general generator: rows for every cell, from `--seed` and
the `data` block of the cell's file.

Rows are shaped like the upstream deployment's input (dense hashed
features, labels 1..num_classes, most entries zero): per-class centres
pulled together so the classes overlap, Gaussian noise, a fixed share
of entries zeroed — the regime `kafka_ps_tpu/data/synth.py` calls
"hard", re-implemented here in bulk float32 so the yardstick does not
move when the program's generator does (PERF.md, Open questions).
Train and test rows come from ONE draw, so they share centres.

Every seed gives the same sizes: only the values change.
"""

from __future__ import annotations

import numpy as np


def make_rows(seed: int, train_rows: int, test_rows: int, num_features: int,
              num_classes: int, *, noise: float = 2.0,
              sparsity: float = 0.7, center_scale: float = 0.2):
    """(train_x, train_y, test_x, test_y): float32 features, int32
    labels in 1..num_classes."""
    rng = np.random.default_rng(int(seed))
    rows = train_rows + test_rows
    centers = (rng.standard_normal((num_classes, num_features),
                                   dtype=np.float32)
               * np.float32(center_scale))
    y = rng.integers(1, num_classes + 1, size=rows, dtype=np.int32)
    x = rng.standard_normal((rows, num_features), dtype=np.float32)
    x *= np.float32(noise)
    x += centers[y - 1]
    x[rng.random((rows, num_features), dtype=np.float32)
      < np.float32(sparsity)] = 0.0
    return x[:train_rows], y[:train_rows], x[train_rows:], y[train_rows:]


def feed(sink, x: np.ndarray, y: np.ndarray, num_workers: int) -> None:
    """Deliver rows to `sink(worker, features, label)` the way the
    program's CsvStreamProducer does: row i goes to worker
    i % num_workers.  The CSV text hop is skipped (rows are handed over
    dense), the sink is the program's own."""
    labels = y.tolist()
    for i in range(len(labels)):
        sink(i % num_workers, x[i], labels[i])
