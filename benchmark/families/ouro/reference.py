"""The plain reference of the `ouro` family: what one clock of the
parameter server means for ByteDance Ouro-2.6B's published shape
(model_type ouro), in float32 `jax.numpy` at `highest` matmul precision,
one worker, one row and one head at a time, and the comparison that
decides `correct`.

Imports nothing from the program and takes nothing it has made except
what is being judged (its parameter vectors and log rows).  The model's
shape is read from the same file the program is pointed at
(`--model_json`), since that file IS the configuration.

Semantics (benchmark/reference.py has the parameter server's; the same
here): a worker runs k full-batch gradient-descent steps of size lr on
its slab from the shared parameters, its delta is new - old, its logged
loss the objective at the new parameters; the server adds (1/W) * the
sum of the deltas.  The objective of a slab of token rows `t[0..S+1]`
is the mean over unmasked rows and the S positions of the next-token
cross-entropy (position i predicts t[i+1]; t[S+1] is carried and read
by nothing).  Evaluation is the mean next-token cross-entropy, accuracy
and support-weighted F1 of argmax predictions over the vocabulary on
the held-out rows.

The model.  (c) is what config.json of ByteDance/Ouro-2.6B states; (m)
is given from the published modelling code (`modeling_ouro.py`) as
known, with no network to check it, and stands under `assumed` in the
configuration's file in these words.

  frame      h = E[tokens] ((c): no scaling key); for step t = 1 ..
      total_ut_steps ((c): 4): h <- L_n(.. L_1(h)) through the SAME
      layers with the SAME weights, then h <- RMSNorm(h) * w_final — the
      model's one final norm, applied at the end of EVERY step, its
      output being what the next step starts from (m); after the last
      step an untied head ((c) tie_word_embeddings false) over the whole
      vocabulary; loss = mean next-token cross-entropy of the LAST
      step's logits.
  layer      (m) four norms a layer, one before and one after each half:
      a = x + N2(Attn(N1(x))), y = a + N4(MLP(N3(a))), each N an RMSNorm
      (eps 1e-6 (c)) with its own weight.
  attention  u the normed input: q = u W_q, k = u W_k, v = u W_v, each
      [S, heads, head_dim] ((c): 16 heads, 16 key/value heads, head_dim
      128: query head h reads key/value head h // (heads / kv heads),
      its own); no bias ((m): the config has no bias key); no head-wise
      norm; rotate-half RoPE over all the channels, theta = rope_theta
      (c), rope_scaling null (c), on q and k in every layer, positions 0
      .. S-1 the same at every step; scores q.k / sqrt(head_dim); query
      i sees key j iff j <= i ((c): every layer_types entry
      full_attention, use_sliding_window false); softmax; out = (P v)
      W_o.  ATTENTION AS ITS DEFINITION (`_attention`): the whole [S, S]
      score matrix of a head with the mask written as that inequality,
      no blocks, no running maximum.
  MLP        (silu(u W_gate) * u W_up) W_down at intermediate_size (c).
  left out, and said  the exit gate ((m): a hidden_size -> 1 projection
      with bias read after each step's norm, whose sigmoid gives each
      step a probability of stopping there).  At early_exit_threshold 1
      (c) no step stops early and the output is the last step's, which
      is what this configuration computes and trains; the published
      training objective weighs every step's loss by the gate's
      distribution with an entropy term whose coefficient the config
      does not give.  The gate is in no leaf here, in the program and in
      the reference alike: a departure, not a saving inside a tolerance.

Further assumed: initialisation normal(0, init_std) from the model
file's init_seed, norms at one; float32 parameters and plain SGD.

How it is computed — THE LOOP AS ITS DEFINITION: the layer applications
(steps x layers) are two nested Python loops over steps and layers that
index ONE dictionary of weights; the gradient is `jax.grad` of that
function, so a leaf's gradient is whatever the chain rule gives a value
used `total_ut_steps` times, and nothing is accumulated by hand.  Rows
and heads are loops too; every application, every row and every head is
recomputed in the backward pass (`jax.checkpoint`): it changes no value,
it lets the reference fit the chip beside its own four copies of the
parameters.

The flat layout (the wire contract, in this order): embed [V,H]; the
layers l<i>.{in_norm, wq, wk, wv, wo, post_attn_norm, pre_mlp_norm,
w_gate, w_up, w_down, post_mlp_norm}, each ONCE; final_norm; head [H,V].
Weights multiply from the right (x @ W).

benchmark/run.py's docstring has the interface it calls.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
LOG_COLUMN = {"loss": "loss", "f1": "fMeasure", "accuracy": "accuracy"}
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
FULL = "full_attention"


@dataclasses.dataclass(frozen=True)
class Shapes:
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_hidden_layers: int
    total_ut_steps: int
    rms_norm_eps: float
    rope_theta: float
    vocab_held: int
    sequence_length: int
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    def layer(self) -> list[tuple[str, tuple[int, ...]]]:
        h, i, d = self.hidden_size, self.intermediate_size, self.head_dim
        q, kv = self.num_attention_heads * d, self.num_key_value_heads * d
        return [("in_norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
                ("wv", (h, kv)), ("wo", (q, h)), ("post_attn_norm", (h,)),
                ("pre_mlp_norm", (h,)), ("w_gate", (h, i)),
                ("w_up", (h, i)), ("w_down", (i, h)),
                ("post_mlp_norm", (h,))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        out = [("embed", (self.vocab_held, self.hidden_size))]
        for i in range(self.num_hidden_layers):
            out += [(f"l{i}.{n}", s) for n, s in self.layer()]
        return out + [("final_norm", (self.hidden_size,)),
                      ("head", (self.hidden_size, self.vocab_held))]

    @property
    def num_params(self) -> int:
        return sum(math.prod(s) for _, s in self.leaves())


def shapes(cfg) -> Shapes:
    """The reference's view of the CLI's configuration: the model file
    it names (a relative path from the repository's root) and the local
    solver's flags."""
    path = cfg.model.model_json
    with open(path if os.path.isabs(path) else os.path.join(_ROOT, path)) \
            as fh:
        body = json.load(fh)
    assert body["layer_types"] == [FULL] * body["num_hidden_layers"]
    assert not body["use_sliding_window"] and not body["tie_word_embeddings"]
    assert body["early_exit_threshold"] >= 1, "no step stops early"
    assert body["vocab_held"] == body["vocab_size"], "the whole vocabulary"
    keys = {f.name for f in dataclasses.fields(Shapes)}
    return Shapes(local_iterations=cfg.model.num_max_iter,
                  local_lr=cfg.model.local_learning_rate,
                  num_workers=cfg.num_workers,
                  **{k: v for k, v in body.items() if k in keys})


def split(theta, s: Shapes) -> dict:
    """{leaf name: its part of a flat vector, shaped} (views)."""
    out, at = {}, 0
    for name, shape in s.leaves():
        n = math.prod(shape)
        out[name] = theta[at:at + n].reshape(shape)
        at += n
    return out


def join(leaves: dict, s: Shapes) -> np.ndarray:
    """The flat host vector of device (or host) leaves."""
    return np.concatenate([np.asarray(leaves[name]).reshape(-1)
                           for name, _ in s.leaves()])


def init_params(s: Shapes) -> np.ndarray:
    """The deployment's stated start, as a host vector, every leaf from
    PRNGKey(init_seed) folded with its place in the layout: matrices
    normal(0, init_std), norms one."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        if name.endswith("norm"):
            leaf = np.ones(shape, np.float32)
        else:
            leaf = s.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
        parts.append(np.asarray(leaf).reshape(-1))
    return np.concatenate(parts)


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotate(x, theta: float):
    """Rotate-half RoPE on `[S, heads, d]`: channel c < d/2 and channel
    c + d/2 turn together by the angle position * theta^(-2c/d)."""
    n, _, d = x.shape
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freq
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def _pairs_averaged(x):
    """Key/value heads 2k and 2k + 1 each replaced by their mean: what
    grouped-query attention would hold for these heads."""
    n, heads, d = x.shape
    mean = x.reshape(n, heads // 2, 2, d).mean(axis=2, keepdims=True)
    return jnp.broadcast_to(mean, (n, heads // 2, 2, d)).reshape(x.shape)


def _attention(u, p, s: Shapes, k: dict):
    """One row `[S, H]`, already normed; a query head at a time, each
    against the whole [S, S] score matrix of its key/value head."""
    n, nh, d = u.shape[0], s.num_attention_heads, s.head_dim
    per_kv = nh // s.num_key_value_heads
    q = (u @ p["wq"]).reshape(n, nh, d)
    key = (u @ p["wk"]).reshape(n, s.num_key_value_heads, d)
    val = (u @ p["wv"]).reshape(n, s.num_key_value_heads, d)
    if k["rope"]:
        q, key = _rotate(q, s.rope_theta), _rotate(key, s.rope_theta)
    if k["heads_grouped"]:
        key, val = _pairs_averaged(key), _pairs_averaged(val)
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i

    def head(args):
        q_h, at = args
        k_h, v_h = key[:, at // per_kv], val[:, at // per_kv]
        scores = jnp.where(seen, (q_h @ k_h.T) / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(jax.checkpoint(head),
                      (q.transpose(1, 0, 2), jnp.arange(nh)))
    return out.transpose(1, 0, 2).reshape(n, nh * d) @ p["wo"]


def _layer(x, p, s: Shapes, k: dict):
    eps = s.rms_norm_eps

    def after(y, w):                  # the second norm of a half
        return _norm(y, w, eps) if k["post_norms"] else y
    a = x + after(_attention(_norm(x, p["in_norm"], eps), p, s, k),
                  p["post_attn_norm"])
    u = _norm(a, p["pre_mlp_norm"], eps)
    y = (_silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]
    return a + after(y, p["post_mlp_norm"])


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], logits [S, V]).
    The loop as its definition: steps and layers are two nested Python
    loops that index the one dictionary `p`; each application is
    recomputed in the backward pass."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    steps = k["steps"]
    for step in range(steps):
        if k["grad_last_use_only"] and step == steps - 1:
            x = jax.lax.stop_gradient(x)
        for i in range(s.num_hidden_layers):
            x = jax.checkpoint(lambda x, q: _layer(x, q, s, k))(
                x, _sub(p, f"l{i}."))
        if k["norm_between_steps"] or step == steps - 1:
            x = _norm(x, p["final_norm"], s.rms_norm_eps)
    logits = x @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return (-jnp.take_along_axis(logp, row[1:n + 1, None], axis=-1)[:, 0],
            logits)


def _objective(p: dict, rows, mask, s: Shapes, k: dict):
    """Mean over the unmasked rows' positions, a row at a time, in the
    backward pass too."""
    def one(row):
        return _row(p, row, s, k)[0].sum()
    losses = jax.lax.map(jax.checkpoint(one), rows)
    return (losses * mask).sum() / (jnp.maximum(mask.sum(), 1.0)
                                    * s.sequence_length)


def _held_in(dtype):
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


class Reference:
    """Jitted once per cell; every call under `highest` precision.  On
    the device at once: the shared parameters, the running sum of
    deltas, one worker's working copy and its gradient.  `run` returns
    host arrays and keeps nothing on the device.

    The keywords exist for the CONTROLS only (benchmark/control.py):
    the same reference with one thing a faster or a mistaken program
    would do."""

    def __init__(self, shapes: Shapes, theta_dtype=None, fewer_steps=0,
                 grad_last_use_only=False, norm_between_steps=True,
                 post_norms=True, rope=True, heads_grouped=False):
        s = self.shapes = shapes
        k = self.switches = {
            "steps": s.total_ut_steps - fewer_steps,
            "grad_last_use_only": grad_last_use_only,
            "norm_between_steps": norm_between_steps,
            "post_norms": post_norms, "rope": rope,
            "heads_grouped": heads_grouped}
        self._store = _held_in(theta_dtype)
        self._grad = jax.jit(jax.grad(
            lambda p, rows, mask: _objective(p, rows, mask, s, k)))
        self._loss = jax.jit(
            lambda p, rows, mask: _objective(p, rows, mask, s, k))
        self._row = jax.jit(lambda p, row: _row(p, row, s, k))
        self._step = jax.jit(
            lambda p, g: jax.tree.map(lambda a, b: a - s.local_lr * b, p, g),
            donate_argnums=1)
        self._add_delta = jax.jit(
            lambda total, new, old: jax.tree.map(
                lambda t, n, o: t + (n - o), total, new, old),
            donate_argnums=0)
        self._apply = jax.jit(
            lambda theta, total: jax.tree.map(
                lambda a, d: self._store(a + d / s.num_workers), theta,
                total),
            donate_argnums=0)

    def _device(self, theta) -> dict:
        return {n: self._store(jnp.asarray(v, jnp.float32))
                for n, v in split(np.asarray(theta), self.shapes).items()}

    def run(self, theta0, slabs, clocks: int, keep_every: int = 1):
        """`clocks` BSP clocks from theta0 over every worker's (rows, _,
        mask) slab: ([theta after every `keep_every`-th clock] as host
        vectors, [mean of the workers' losses, a clock])."""
        slabs = [(np.asarray(x), np.asarray(m)) for x, _, m in slabs]
        thetas, losses, t0 = [], [], time.time()
        with jax.default_matmul_precision(PRECISION):
            theta = self._device(theta0)
            for done in range(1, clocks + 1):
                total = jax.tree.map(jnp.zeros_like, theta)
                of_clock = []
                for rows, mask in slabs:
                    rows, mask = jnp.asarray(rows), jnp.asarray(mask)
                    new = theta
                    for _ in range(self.shapes.local_iterations):
                        new = self._step(new, self._grad(new, rows, mask))
                    of_clock.append(self._loss(new, rows, mask))
                    # wait for each worker: the dispatch queue would
                    # otherwise hold every worker's buffers at once
                    total = jax.block_until_ready(
                        self._add_delta(total, new, theta))
                    del new
                theta = self._apply(theta, total)
                losses.append(float(np.mean([float(v) for v in of_clock])))
                if done % keep_every == 0:
                    thetas.append(join(theta, self.shapes))
                if done in (1, clocks):
                    print(f"[bench] reference: clock {done} done "
                          f"{time.time() - t0:.1f}s after its start",
                          flush=True)
        return thetas, losses

    def forward_rows(self, theta, rows):
        """Per row: (nll [S], logits argmax [S]), host arrays."""
        out = []
        with jax.default_matmul_precision(PRECISION):
            p = self._device(theta)
            for row in np.asarray(rows):
                nll, logits = self._row(p, jnp.asarray(row))
                out.append((np.asarray(nll),
                            np.asarray(jnp.argmax(logits, -1))))
        return out

    def evaluate(self, theta, test) -> dict:
        """The held-out rows under `theta`, by LOG_COLUMN's names."""
        s = self.shapes
        rows = np.asarray(test[0])
        got = self.forward_rows(theta, rows)
        labels = rows[:, 1:s.sequence_length + 1].reshape(-1)
        preds = np.concatenate([g[1] for g in got])
        loss = float(np.concatenate([g[0] for g in got]).astype(
            np.float64).mean())
        v = s.vocab_held
        support = np.bincount(labels, minlength=v).astype(np.float64)
        predicted = np.bincount(preds, minlength=v).astype(np.float64)
        tp = np.bincount(labels[preds == labels], minlength=v).astype(
            np.float64)
        precision = tp / np.maximum(predicted, 1.0)
        recall = tp / np.maximum(support, 1.0)
        f1 = 2 * precision * recall / np.maximum(precision + recall, 1e-12)
        return {"loss": loss,
                "f1": float((f1 * support).sum() / support.sum()),
                "accuracy": float(tp.sum() / support.sum())}


# -- the comparison ------------------------------------------------------------

def param_gap(theta_prog, theta_ref, theta0, s: Shapes) -> float:
    """Worst leaf of | ||prog change|| - ||ref change|| | over the
    reference's norm of that leaf's change or of the median leaf's,
    whichever is larger (some leaves hardly move); nan where a leaf of
    the program's is not finite.  A leaf at a time: three float64
    copies of the whole vector would not fit the host."""
    prog, ref, start = (split(np.asarray(t), s)
                        for t in (theta_prog, theta_ref, theta0))
    norms = {}
    for name, _ in s.leaves():
        base = start[name].astype(np.float64)
        norms[name] = (float(np.linalg.norm(prog[name] - base)),
                       float(np.linalg.norm(ref[name] - base)))
    floor = statistics.median(r for _, r in norms.values())
    gaps = {name: abs(got - want) / max(want, floor, 1e-30)
            for name, (got, want) in norms.items()}
    # a leaf that is not finite is the worst there is: its gap is nan,
    # which no limit admits
    where = max(gaps, key=lambda name: (math.isnan(gaps[name]), gaps[name]))
    print(f"[bench] reference: worst leaf {where!r} gap {gaps[where]!r}",
          flush=True)
    return gaps[where]


# the controls of benchmark/control.py: Reference keywords by name, each
# what a faster or a mistaken program would compute, and each has to
# break at least one limit of the cell.
#   theta_bf16             the shared parameters held in bfloat16 between
#                          clocks (half the delta, half the plane)
#   three_steps            the stack run 3 times for the published 4
#   grad_last_use_only     the gradient stopped at the input of the last
#                          step: a leaf's gradient is of ONE use, not of
#                          its four — what a loop that forgets to sum
#                          would give
#   no_norm_between_steps  the final norm after the last step only
#   no_post_norms          the second norm of each half left out
#   no_rope                no positional encoding
#   heads_grouped          key/value heads 2k and 2k + 1 averaged:
#                          grouped-query attention for multi-head
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "three_steps": {"fewer_steps": 1},
            "grad_last_use_only": {"grad_last_use_only": True},
            "no_norm_between_steps": {"norm_between_steps": False},
            "no_post_norms": {"post_norms": False},
            "no_rope": {"rope": False},
            "heads_grouped": {"heads_grouped": True}}
