"""The generator of token rows: every cell of the `granite-hybrid`
family, from `--seed` and the `data` block of the configuration's file
(benchmark/run.py's docstring has the interface it calls: `make`,
`feed`, `slabs`).

A row is `sequence_length + 2` token ids (`int32`), the width the
program's token rows have for every language-model family: the S input
positions and the token after them that the next-token head is trained
to predict; the last id is carried and read by nothing (this family
has no second head).  Ids are drawn from the slice of the vocabulary
held here (`vocab_held`), Zipf-distributed: id i with probability
proportional to 1 / (i + 1) ** zipf_exponent, the most frequent tokens
holding the lowest ids, as a tokenizer built by merges numbers them.  A
few ids fill most positions, as in text; WHICH ids are frequent does
not change with the seed (the other families' generators have the
reason: with an expert layer, which experts are busy would otherwise
change from seed to seed, and an update's time with it; this family has
none, and draws its rows the same way so that its cell reads beside
theirs).  A row carries no label of its own (the labels are the row,
shifted); the label column the buffers keep is zero.

Every seed gives the same sizes: only the values change.
"""

from __future__ import annotations

import json
import os

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def model_file(cfg) -> dict:
    """The model file the CLI's configuration names (a relative path
    from the repository's root)."""
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        return json.load(fh)


def make_rows(seed: int, rows: int, width: int, vocab: int,
              exponent: float) -> np.ndarray:
    rng = np.random.default_rng(int(seed))
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return rng.choice(vocab, size=(rows, width),
                      p=p / p.sum()).astype(np.int32)


def make(seed: int, cfg, data: dict):
    """((train rows, zero labels), (held-out rows, zero labels))."""
    model = model_file(cfg)
    train_rows = cfg.num_workers * data["rows_per_worker"]
    rows = make_rows(seed, train_rows + data["test_rows"],
                     model["sequence_length"] + 2, model["vocab_held"],
                     data["zipf_exponent"])
    zeros = np.zeros((len(rows),), np.int32)
    return ((rows[:train_rows], zeros[:train_rows]),
            (rows[train_rows:], zeros[train_rows:]))


def feed(sink, train, num_workers: int) -> None:
    """Row i goes to worker i % num_workers, through the program's own
    `sink(worker, row, label)`."""
    rows = train[0]
    for i in range(len(rows)):
        sink(i % num_workers, rows[i], 0)


def slabs(train, num_workers: int) -> list:
    """What `feed` leaves in the workers' buffers, (rows, labels, mask)
    a worker, where the rows fill each buffer and no more: control.py
    runs no program."""
    rows, labels = train
    return [(rows[w::num_workers], labels[w::num_workers],
             np.ones((len(range(w, len(rows), num_workers)),), np.float32))
            for w in range(num_workers)]
