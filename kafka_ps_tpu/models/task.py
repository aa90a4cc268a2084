"""The ML-task abstraction — the reference's implicit task API made
explicit.

The reference's whole learning surface is one class,
`LogisticRegressionTaskSpark` (ml/LogisticRegressionTaskSpark.java:30):
`initialize` / `setWeights` / `calculateGradients` / `calculateTestMetrics`
over a flat integer-keyed parameter vector.  The processors only ever
touch that surface, so the PS runtime is model-agnostic in spirit —
this module makes it so in fact.  A task owns:

  * the flat parameter layout (`num_params` — the KeyRange key space)
    and the way between it and the parameters' leaves (`unflatten`,
    `flatten`),
  * the k-step local solver on the leaves (`fit` → new leaves; its
    difference from the old ones is the delta, the "gradient" the
    reference exchanges, LogisticRegressionTaskSpark.java:179-220),
  * test evaluation from the leaves (`evaluate_leaves` → weighted F1 /
    accuracy / loss, Metrics.java:15-24),
  * what a row is (`row_width`, `row_dtype`: a classifier's float32
    features, a language model's int32 tokens) and how its label is
    encoded for `fit` (`encode_labels`: one-hot for the classifiers,
    nothing for token rows, whose labels are the row itself) — the
    buffers, the slab and the step builders ask the task,
  * whether its update batches over the worker axis
    (`batches_workers`): the fused step `vmap`s the classifiers over
    the workers and folds a family whose own matrix products fill the
    chip one worker at a time (parallel/bsp.py).

Inside a solver program the parameters are their leaves; the flat
vector is what a program takes and returns (the wire and server
contract).  The flat entry points (`local_update`, `evaluate`,
`evaluate_batch`, `predict_logits`) are that surface wrapped —
unflatten, fit, flatten the delta — for callers that hold one flat
theta: the range-sharded step, the server's eval, serving.  They are
written once, in `FlatFace`, from the members a family owns.

Every entry point (runtime worker, fused BSP step, range-sharded step,
server eval) dispatches through a task; `logreg` stays the default —
the reference's model — `mlp` is a second classifier, and
`glm4_moe_lite`, `nemotron_h`, `afmoe`, `ouro`, `mellum`, `lfm2_moe` and
`granitemoehybrid` are language models over token rows (models/lm_common.py
has what they share).

What a family says of itself, for whoever has to refuse a lever before
any program is built (cli/run.py): `model_file` (its widths are a file
of its own, `--model_json`), `row_dtype` (int32 rows are tokens, stored
as they are) and `batches_workers` (a family that folds its workers has
no program over a mesh).
"""

from __future__ import annotations

import functools
import importlib
from typing import Any, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from kafka_ps_tpu.compress.slab import decode_x
from kafka_ps_tpu.models import logreg
from kafka_ps_tpu.models import metrics as metrics_mod
from kafka_ps_tpu.utils.config import ModelConfig


class MLTask(Protocol):
    """What the PS runtime needs from a model family.  All functions are
    jit-safe and shard_map-safe (no data-dependent Python control flow;
    gradients must not rely on AD of replicated operands — see
    logreg.grad_loss_onehot's note on shard_map cotangent psums).
    `leaves` is the family's pytree of parameter arrays."""

    cfg: ModelConfig
    batches_workers: bool
    row_dtype: Any
    model_file: bool

    @property
    def num_params(self) -> int: ...

    @property
    def row_width(self) -> int: ...

    def encode_labels(self, y) -> Any: ...

    def init_params(self) -> jax.Array: ...

    def unflatten(self, theta) -> Any: ...

    def flatten(self, leaves) -> jax.Array: ...

    def fit(self, leaves, x, encoded, mask): ...

    def evaluate_leaves(self, leaves, x_test, y_test) \
            -> metrics_mod.Metrics: ...

    def logits(self, leaves, x) -> jax.Array: ...

    def local_update(self, theta, x, y, mask): ...

    def evaluate(self, theta, x_test, y_test) -> metrics_mod.Metrics: ...

    def evaluate_batch(self, thetas, x_test, y_test) \
            -> metrics_mod.Metrics: ...

    def predict_logits(self, theta, x) -> jax.Array: ...


def local_steps(step, loss, params, k: int):
    """A classifier family's k local steps, written once: `step` (leaves
    → leaves) k times from `params`, then `loss` at the result →
    (new leaves, loss).

    The FIRST step is taken from `params` as they were handed in; only
    the k − 1 steps after it are a `lax.scan`, whose carry is the first
    step's result (no scan at k = 1).  A scan's carry has one type, so
    under a `vmap` over workers whose leaves are shared (parallel/bsp.py
    `_make_round`, the gang's `update_bcast` / `update_eval_bcast`) a
    scan from `params` began with the leaves copied out to every worker
    — 1.08 GB of W1 at 64 workers x H=4096, written, read by a batched
    forward and read again by the parameter step, before any worker
    differed from another.  Peeled, the first forward is one product
    over all workers' rows that reads each weight once, and a worker's
    first own copy of the parameters is what its first parameter step
    writes: the shared leaf less its own gradient (PERF.md §6, PR 30).
    Leaves that carry the worker axis already (the gang's stacked
    members, a single worker's program) do in the first step what the
    scan's first iteration did."""
    if k < 1:
        raise ValueError(f"a local update takes at least one step, not {k}")
    new = step(params)
    if k > 1:
        new, _ = jax.lax.scan(lambda p, _: (step(p), None), new, None,
                              length=k - 1)
    return new, loss(new)


def fit_delta(task: MLTask, leaves, x, onehot, mask):
    """k local steps from `leaves` → (delta leaves, loss at the new
    parameters)."""
    new, loss = task.fit(leaves, x, onehot, mask)
    with jax.named_scope("kps.fit.delta"):
        return jax.tree.map(jnp.subtract, new, leaves), loss


def fit_slab(task: MLTask, leaves, x, y, mask):
    """The k-step solver on one slab as a worker stores it (labels, any
    slab storage form: the decode fuses into the program that traces
    this, and is the identity for f32) → (delta leaves, loss)."""
    return fit_delta(task, leaves, decode_x(x), task.encode_labels(y), mask)


class FlatFace:
    """A family's flat entry points, written once from its leaf-level
    members: one flat theta in, a flat delta, the metrics or the scores
    out.  `local_update` and `evaluate` are jitted, so that a caller
    holding one flat theta outside any program pays one cached XLA
    program per (family, cfg); inside a caller's own jit they inline.
    The task is those programs' static argument: two tasks are equal
    when their family and their cfg are."""

    model_file = False      # the widths are ModelConfig's own fields

    def __eq__(self, other):
        return type(self) is type(other) and self.cfg == other.cfg

    def __hash__(self):
        return hash((type(self), self.cfg))

    def local_update(self, theta, x, y, mask):
        """`fit_slab` from a flat theta → (flat delta, loss at the
        updated parameters)."""
        return _local_update(self, theta, x, y, mask)

    def evaluate(self, theta, x_test, y_test) -> metrics_mod.Metrics:
        """`evaluate_leaves` of a flat theta."""
        return _evaluate(self, theta, x_test, y_test)

    def evaluate_batch(self, thetas, x_test, y_test) -> metrics_mod.Metrics:
        """Stacked eval: (k, P) thetas against one test set -> Metrics
        with (k,)-leading fields.  The SAME per-element program as
        `evaluate` over the leading axis (a `vmap`; one theta at a time
        for a family whose update does not batch either), so row i is
        bitwise-identical to `evaluate(thetas[i], ...)` — the async eval
        engine's coalesced dispatch rides on this (evaluation/engine.py,
        the vmap-of-kernel construction the gang solvers proved,
        runtime/gang.py)."""
        def one(theta):
            return self.evaluate(theta, x_test, y_test)
        if self.batches_workers:
            return jax.vmap(one)(thetas)
        return jax.lax.map(one, thetas)

    def predict_logits(self, theta, x):
        """Rows → the scores `logits` gives them — the serving plane's
        forward pass (kafka_ps_tpu/serving/engine.py)."""
        return self.logits(self.unflatten(theta), x)


@functools.partial(jax.jit, static_argnames=("task",))
def _local_update(task, theta, x, y, mask):
    delta, loss = fit_slab(task, task.unflatten(theta), x, y, mask)
    return task.flatten(delta), loss


@functools.partial(jax.jit, static_argnames=("task",))
def _evaluate(task, theta, x_test, y_test):
    return task.evaluate_leaves(task.unflatten(theta), x_test, y_test)


class RowsWithClassLabel(FlatFace):
    """What the classifier families share: float32 feature rows of
    `cfg.num_features`, a class label one-hot over `cfg.num_rows`, an
    update that batches over the worker axis."""

    batches_workers = True
    row_dtype = np.float32

    @property
    def row_width(self) -> int:
        return self.cfg.num_features

    def encode_labels(self, y):
        return jax.nn.one_hot(y, self.cfg.num_rows, dtype=jnp.float32)


class LogRegTask(RowsWithClassLabel):
    """The reference's model: multinomial LR over the flat
    (C+1)·F + (C+1) layout (models/logreg.py)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    @property
    def num_params(self) -> int:
        return self.cfg.num_params

    def init_params(self):
        return logreg.init_params(self.cfg).flat

    def unflatten(self, theta) -> logreg.LogRegParams:
        return logreg.unflatten(theta, self.cfg)

    def flatten(self, leaves: logreg.LogRegParams) -> jax.Array:
        return leaves.flat

    def fit(self, leaves, x, onehot, mask):
        return logreg.fit(leaves, x, onehot, mask, cfg=self.cfg)

    def evaluate_leaves(self, leaves, x_test, y_test) -> metrics_mod.Metrics:
        return metrics_mod.evaluate_leaves(leaves, x_test, y_test,
                                           cfg=self.cfg)

    def logits(self, leaves, x):
        """(B, F) → (B, C+1) class scores."""
        return logreg.logits(leaves, x)


_REGISTRY = {"logreg": LogRegTask}
# optional families, bound late so that importing task.py stays cheap:
# name -> (module, class)
_LATE = {"mlp": ("kafka_ps_tpu.models.mlp", "MLPTask"),
         "glm4_moe_lite": ("kafka_ps_tpu.models.glm4_moe_lite",
                           "Glm4MoeLiteTask"),
         "nemotron_h": ("kafka_ps_tpu.models.nemotron_h", "NemotronHTask"),
         "afmoe": ("kafka_ps_tpu.models.afmoe", "AfmoeTask"),
         "ouro": ("kafka_ps_tpu.models.ouro", "OuroTask"),
         "mellum": ("kafka_ps_tpu.models.mellum", "MellumTask"),
         "lfm2_moe": ("kafka_ps_tpu.models.lfm2_moe", "Lfm2MoeTask"),
         "granitemoehybrid": ("kafka_ps_tpu.models.granite_hybrid",
                              "GraniteHybridTask")}


def default_task(cfg: ModelConfig) -> "MLTask":
    """The reference's model family — what every factory falls back to
    when no task is passed."""
    return get_task("logreg", cfg)


def register(name: str, factory) -> None:
    _REGISTRY[name] = factory


def task_names() -> list[str]:
    """Every family's name: what is registered from the start, then the
    late-bound families in `_LATE`'s order (the CLI's `--task`)."""
    return [n for n in _REGISTRY if n not in _LATE] + list(_LATE)


def task_class(name: str):
    """The family's factory, by name: what `get_task` calls, and what
    says of the family what a caller must know before it has a cfg
    (`model_file`, `row_dtype`, `batches_workers`)."""
    if name not in _REGISTRY:
        if name not in _LATE:
            raise ValueError(
                f"unknown task {name!r}; registered: "
                f"{sorted({*_REGISTRY, *_LATE})}")
        module, cls = _LATE[name]
        register(name, getattr(importlib.import_module(module), cls))
    return _REGISTRY[name]


def get_task(name: str, cfg: ModelConfig) -> MLTask:
    return task_class(name)(cfg)
