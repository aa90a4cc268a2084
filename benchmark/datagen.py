"""The generator of rows with a class label: every cell of a
configuration without a `family` key, from `--seed` and the `data`
block of the configuration's file (benchmark/run.py's docstring has the
interface it calls: `make`, `feed`, `slabs`).

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


def make(seed: int, cfg, data: dict):
    """The cell's rows from the seed and the CLI's configuration:
    ((train_x, train_y), (test_x, test_y))."""
    train_x, train_y, test_x, test_y = make_rows(
        seed, cfg.num_workers * data["rows_per_worker"], data["test_rows"],
        cfg.model.num_features, cfg.model.num_classes, noise=data["noise"],
        sparsity=data["sparsity"], center_scale=data["center_scale"])
    return (train_x, train_y), (test_x, test_y)


def feed(sink, train, num_workers: int) -> None:
    """Deliver rows to `sink(worker, features, label)` the way the
    program's CsvStreamProducer does: row i goes to worker
    i % num_workers.  The CSV text hop is skipped (rows are handed over
    dense), the sink is the program's own."""
    x, labels = train[0], train[1].tolist()
    for i in range(len(labels)):
        sink(i % num_workers, x[i], labels[i])


def slabs(train, num_workers: int) -> list:
    """What `feed` leaves in the workers' buffers, as the program's
    `snapshot()` hands it out, (x, y, mask) a worker, where the rows
    fill each buffer and no more: control.py runs no program."""
    x, y = train
    return [(x[w::num_workers], y[w::num_workers],
             np.ones((len(range(w, len(y), num_workers)),), np.float32))
            for w in range(num_workers)]
