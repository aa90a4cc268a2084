"""Work and bytes of one worker update, from shapes alone.

Started from bench.py (`logreg_update_flops/bytes`, `mlp_update_flops/
bytes`) so the yardstick lives with the benchmark; bench.py is never
imported.  Two repairs against the original: `mlp_update_bytes` counts
the weights, and `mlp_update_flops` no longer charges a backward pass
twice the forward (the gradient is taken with respect to the parameters
only, so the first layer's dX is never computed).  Shapes: b = slab rows
the update trains on (the buffer capacity: masked rows still flow
through every matmul), f = features, h = hidden width, c1 = class rows
(num_classes + 1), k = local solver steps, n = test rows.  One update =
k full-batch gradient steps plus one forward-only loss at the updated
parameters.  Bytes are counted at the float32 the configuration states.
The per-node path also evaluates the post-fit model on the test set
inside the same dispatch: `eval_cost` is that forward pass, added by the
roofline reader for the programs that carry it.

`update(cfg)` and `evaluation(cfg, test)` are what a roofline reader
calls (`run.family.costs`): these shapes' costs at the CLI's
configuration.  The table of peaks is benchmark/peaks.py's.
"""

from __future__ import annotations


def logreg_update_flops(b: int, f: int, c1: int, k: int) -> float:
    """k gradient steps of two [b,f]x[f,c1] matmuls (logits, grad) at
    2*b*f*c1 each, plus the forward-only final loss."""
    return k * 4.0 * b * f * c1 + 2.0 * b * f * c1


def logreg_update_bytes(b: int, f: int, c1: int, k: int) -> float:
    """The [b,f] f32 slab is read once per matmul: two per gradient
    step, one for the final loss.  Parameters and [b,c1] activations
    are noise beside it."""
    return (2 * k + 1) * b * f * 4.0


def mlp_update_flops(b: int, f: int, h: int, c1: int, k: int) -> float:
    """Per gradient step: the forward pass ([b,f]x[f,h] and
    [b,h]x[h,c1], 2*b*h*(f+c1)) and the backward pass with respect to
    the parameters (dW2 and dH at 2*b*h*c1 each, dW1 at 2*b*f*h; no dX
    of the first layer).  Then the forward-only final loss."""
    fwd = 2.0 * b * h * (f + c1)
    bwd = 2.0 * b * h * (f + 2 * c1)
    return k * (fwd + bwd) + fwd


def mlp_update_bytes(b: int, f: int, h: int, c1: int, k: int) -> float:
    """Lower bound on HBM traffic: the [b,f] slab read per forward and
    per dW1 matmul, [b,h] activation round trips, and the [h,f] weights
    read per forward/backward and written per step."""
    slab = (2 * k + 1) * b * f * 4.0
    acts = (3 * k + 1) * b * h * 4.0
    weights = (3 * k + 1) * h * (f + c1) * 4.0
    return slab + acts + weights


def update_cost(task: str, b: int, f: int, h: int, c1: int,
                k: int) -> tuple[float, float]:
    """(flops, bytes) of one worker update of `task` at these shapes."""
    if task == "mlp":
        return (mlp_update_flops(b, f, h, c1, k),
                mlp_update_bytes(b, f, h, c1, k))
    if task == "logreg":
        return (logreg_update_flops(b, f, c1, k),
                logreg_update_bytes(b, f, c1, k))
    raise KeyError(f"no cost model for task {task!r}")


def eval_cost(task: str, n: int, f: int, h: int,
              c1: int) -> tuple[float, float]:
    """(flops, bytes) of one forward pass over n test rows: the rows
    read once, the weights read once, and for the mlp the [n,h]
    activations written and read."""
    if task == "mlp":
        return (2.0 * n * h * (f + c1),
                n * f * 4.0 + 2 * n * h * 4.0 + h * (f + c1) * 4.0)
    if task == "logreg":
        return 2.0 * n * f * c1, n * f * 4.0 + f * c1 * 4.0
    raise KeyError(f"no cost model for task {task!r}")


def update(cfg) -> tuple[float, float]:
    """(flops, bytes) of one worker update at the CLI's configuration."""
    m = cfg.model
    return update_cost(cfg.task, cfg.buffer.max_size, m.num_features,
                       m.hidden_dim, m.num_rows, m.num_max_iter)


def evaluation(cfg, test) -> tuple[float, float]:
    """(flops, bytes) of one evaluation of the test set (rows, labels)."""
    m = cfg.model
    return eval_cost(cfg.task, len(test[1]), m.num_features, m.hidden_dim,
                     m.num_rows)
