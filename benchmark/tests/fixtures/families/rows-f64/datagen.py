"""The fixture family's generator: dense Gaussian rows pulled towards
one centre a class (`data.pull`), labels 1..num_classes, from the seed.
Row i goes to worker i % num_workers."""

import numpy as np


def make(seed, cfg, data):
    rng = np.random.default_rng([int(seed), 64])
    classes, width = cfg.model.num_classes, cfg.model.num_features
    centres = rng.standard_normal((classes, width))
    rows = cfg.num_workers * data["rows_per_worker"] + data["test_rows"]
    y = rng.integers(1, classes + 1, size=rows).astype(np.int32)
    x = (rng.standard_normal((rows, width))
         + data["pull"] * centres[y - 1]).astype(np.float32)
    cut = rows - data["test_rows"]
    return (x[:cut], y[:cut]), (x[cut:], y[cut:])


def feed(sink, train, num_workers):
    x, y = train
    for i, label in enumerate(y.tolist()):
        sink(i % num_workers, x[i], label)


def slabs(train, num_workers):
    x, y = train
    return [(x[i::num_workers], y[i::num_workers],
             np.ones(len(y[i::num_workers]), np.float32))
            for i in range(num_workers)]
