"""The plain reference of the `nemotron-h` family: what one clock of the
parameter server means for NVIDIA-Nemotron-3-Nano-30B-A3B's published
shape (model_type nemotron_h), in float32 `jax.numpy` at `highest`
matmul precision, one worker and one row at a time, and the comparison
that decides `correct`.

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
and support-weighted F1 of argmax predictions over the held vocabulary
on the held-out rows.

The model, as published (config.json of nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16; RMSNorm eps 1e-5, no biases but the convolution's): every
block is x <- x + mixer(norm(x)), of the kind its letter in
hybrid_override_pattern spells.

  M  Mamba-2       (z | xBC | dt) = u W_in; xBC <- silu(conv(xBC)), a
      causal depthwise convolution of conv_kernel taps with bias, zeros
      before the row's start; (x | B | C) = xBC, x as [S, heads, P], B
      and C as [S, n_groups, N], head h reads group h // (heads /
      n_groups); D_t = softplus(dt + dt_bias) (no clamp: time_step_min
      / _max / _floor shape dt_bias's initialisation only); A_h =
      -exp(A_log_h); the state H_t,h = exp(D_t,h A_h) H_t-1,h + D_t,h
      x_t,h (x) B_t,g in R^{P x N} from zero; y_t,h = H_t,h C_t,g + D_h
      x_t,h; the gated norm, gate first: RMSNorm over each of the
      n_groups groups of channels of y * silu(z), times w; out = y
      W_out.  THE RECURRENCE ITSELF, a step a token (`_recurrence`):
      the definition, not the program's chunked algorithm.
  *  attention     num_attention_heads query heads over
      num_key_value_heads key/value heads of head_dim (query head h
      reads key/value head h // (heads / kv heads)), scores /
      sqrt(head_dim), causal softmax, W_o; no positional encoding.
  E  expert layer  s = sigmoid(u W_r) over ALL n_routed_experts; a
      token's experts are the top num_experts_per_tok of s + b; weights
      = s over its sum on the chosen, times routed_scaling_factor; y =
      sum of w_e relu(u W_up,e)^2 W_down,e over the chosen experts THAT
      ARE HELD HERE (expert_offset .. + experts_held), plus the shared
      expert (the same form, its own width).  What the absent experts
      would add is left out, here as in the program: this chip's share
      of an expert-parallel group (the guide's cut).
  head             final norm, untied head over the vocab_held rows held.

Departures from the published description, and what it does not say
(`assumed` in the configuration's file):
  * no positional encoding in attention: the published modelling code
    applies none (rope_theta is unused);
  * the selection bias b is held fixed at its initial zeros: its update
    rule is not in the config, and no gradient reaches it; n_group 1 /
    topk_group 1: no group limit, so none is computed;
  * initialisation: matrices normal(0, init_std), norms and D one,
    A_log = log(uniform[1, 16]), dt_bias the inverse softplus of
    log-uniform[time_step_min, time_step_max] floored at
    time_step_floor, the convolution's weights and bias
    uniform[-1/sqrt(k), 1/sqrt(k)] (what the published code's framework
    gives a depthwise convolution left to itself);
    rescale_prenorm_residual not applied;
  * blocks, rows, attention heads and held experts are loops, the
    convolution four shifted products; every block, and every row, is
    recomputed in the backward pass (`jax.checkpoint`), and the
    recurrence is recomputed in runs of `_SEGMENT` steps (the state
    kept at each run's start): it changes no value, it lets the
    reference fit the chip beside its own four copies of the
    parameters;
  * the routed experts are computed an expert at a time over every
    token, under a weight that is zero where the expert was not chosen
    (`_experts`): the plain form of the same sum.

The flat layout (the wire contract, in this order): embed [V,H]; the
blocks in their published order, b<i>.{norm, ...}: M {norm, w_in
[H, inner + conv_dim + heads], conv_w [conv_dim, k], conv_b, dt_bias,
A_log, D, gate_norm [inner], w_out [inner, H]}, * {norm, wq, wk, wv,
wo}, E {norm, router [H,E], router_bias [E], e_up [held,H,I], e_down
[held,I,H], s_up [H,Is], s_down [Is,H]}; final_norm; head [H,V].
Weights multiply from the right (x @ W).

benchmark/run.py's docstring has the interface it calls.
"""

from __future__ import annotations

import dataclasses
import functools
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
_SEGMENT = 32      # steps of the recurrence recomputed together
# the slabs of the last `Reference.run`, host arrays: `param_gap` counts
# on them the routing choices that differ between two parameter vectors
_LAST_SLABS: list = []


@dataclasses.dataclass(frozen=True)
class Shapes:
    hidden_size: int
    hybrid_override_pattern: str
    num_hidden_layers: int
    layer_norm_epsilon: float
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    @property
    def inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.ssm_state_size

    def block(self, kind: str) -> list[tuple[str, tuple[int, ...]]]:
        h = self.hidden_size
        if kind == "M":
            return [("norm", (h,)),
                    ("w_in", (h, self.inner + self.conv_dim
                              + self.mamba_num_heads)),
                    ("conv_w", (self.conv_dim, self.conv_kernel)),
                    ("conv_b", (self.conv_dim,)),
                    ("dt_bias", (self.mamba_num_heads,)),
                    ("A_log", (self.mamba_num_heads,)),
                    ("D", (self.mamba_num_heads,)),
                    ("gate_norm", (self.inner,)),
                    ("w_out", (self.inner, h))]
        if kind == "*":
            q = self.num_attention_heads * self.head_dim
            kv = self.num_key_value_heads * self.head_dim
            return [("norm", (h,)), ("wq", (h, q)), ("wk", (h, kv)),
                    ("wv", (h, kv)), ("wo", (q, h))]
        assert kind == "E", kind
        i, e = self.moe_intermediate_size, self.experts_held
        s = self.n_shared_experts * self.moe_shared_expert_intermediate_size
        return [("norm", (h,)), ("router", (h, self.n_routed_experts)),
                ("router_bias", (self.n_routed_experts,)),
                ("e_up", (e, h, i)), ("e_down", (e, i, h)),
                ("s_up", (h, s)), ("s_down", (s, h))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        out = [("embed", (self.vocab_held, self.hidden_size))]
        for i, kind in enumerate(self.hybrid_override_pattern):
            out += [(f"b{i}.{n}", s) for n, s in self.block(kind)]
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
    assert len(body["hybrid_override_pattern"]) == body["num_hidden_layers"]
    assert body["n_shared_experts"] == 1, "one shared expert"
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
    PRNGKey(init_seed) folded with its place in the layout (the module's
    docstring has the distributions)."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        k = jax.random.fold_in(key, at)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm") or last == "D":
            leaf = np.ones(shape, np.float32)
        elif last == "router_bias":
            leaf = np.zeros(shape, np.float32)
        elif last == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                              16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(s.time_step_max)
                            - math.log(s.time_step_min))
                         + math.log(s.time_step_min))
            dt = jnp.maximum(dt, s.time_step_floor)
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif last in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(s.conv_kernel)
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            leaf = s.init_std * jax.random.normal(k, shape, jnp.float32)
        parts.append(np.asarray(leaf).reshape(-1))
    return np.concatenate(parts)


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _conv(x, w, bias):
    """x [S, C], w [C, k]: four shifted products, zeros before the
    row's start; w[:, k - 1] weighs the position itself."""
    n, k = x.shape[0], w.shape[1]
    out = jnp.zeros_like(x) + bias
    for back in range(k):
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:n - back]], axis=0)
        out = out + shifted * w[:, k - 1 - back]
    return out


def _recurrence(x, dt, a, bm, cm, reset_every: int):
    """The state-space layer as its definition, a step a token: x [S,
    heads, P], dt [S, heads], a [heads], bm, cm [S, heads, N] (each
    head's group's) -> y [S, heads, P].  `reset_every` > 0 drops the
    state at every multiple of it (a control, never the model)."""
    n = x.shape[0]

    def step(state, token):
        x_t, dt_t, b_t, c_t, at = token
        if reset_every:
            state = jnp.where(at % reset_every == 0, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    def run(state, tokens):
        return jax.lax.scan(step, state, tokens)

    seg = math.gcd(n, _SEGMENT)
    tokens = jax.tree.map(
        lambda v: v.reshape((n // seg, seg) + v.shape[1:]),
        (x, dt, bm, cm, jnp.arange(n)))
    state0 = jnp.zeros(x.shape[1:] + (bm.shape[-1],), x.dtype)
    _, y = jax.lax.scan(jax.checkpoint(run), state0, tokens)
    return y.reshape(x.shape)


def _mamba(u, p, s: Shapes, k: dict):
    """One row `[S, H]`, already normed."""
    n, nh, hd = u.shape[0], s.mamba_num_heads, s.mamba_head_dim
    g, ns, inner = s.n_groups, s.ssm_state_size, s.inner
    zxbcdt = u @ p["w_in"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + s.conv_dim]
    dt = zxbcdt[:, inner + s.conv_dim:]
    if k["conv"]:
        xbc = _conv(xbc, p["conv_w"], p["conv_b"])
    xbc = _silu(xbc)
    x = xbc[:, :inner].reshape(n, nh, hd)
    # every head reads its group's B and C
    per_head = lambda m: jnp.repeat(m.reshape(n, g, ns), nh // g, axis=1)
    bm = per_head(xbc[:, inner:inner + g * ns])
    cm = per_head(xbc[:, inner + g * ns:])
    delta = jnp.logaddexp(dt + p["dt_bias"], 0.0)          # softplus
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), bm, cm,
                    s.chunk_size if k["state_reset"] else 0)
    if k["skip_D"]:
        y = y + p["D"][:, None] * x
    y, gate = y.reshape(n, inner), _silu(z)
    grouped = lambda v: _norm(v.reshape(n, g, inner // g), 1.0,
                              s.layer_norm_epsilon).reshape(n, inner)
    if k["gate_first"]:
        y = grouped(y * gate) * p["gate_norm"]
    else:
        y = grouped(y) * p["gate_norm"] * gate
    return y @ p["w_out"]


def _attention(u, p, s: Shapes):
    """One row `[S, H]`, already normed; a query head at a time."""
    n, nh, d = u.shape[0], s.num_attention_heads, s.head_dim
    per_kv = nh // s.num_key_value_heads
    q = (u @ p["wq"]).reshape(n, nh, d).transpose(1, 0, 2)
    key = (u @ p["wk"]).reshape(n, s.num_key_value_heads, d)
    val = (u @ p["wv"]).reshape(n, s.num_key_value_heads, d)
    future = jnp.arange(n)[None, :] > jnp.arange(n)[:, None]

    def head(args):
        q_h, at = args
        k_h, v_h = key[:, at // per_kv], val[:, at // per_kv]
        scores = jnp.where(future, -jnp.inf, (q_h @ k_h.T) / math.sqrt(d))
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(jax.checkpoint(head), (q, jnp.arange(nh)))
    return out.transpose(1, 0, 2).reshape(n, nh * d) @ p["wo"]


def _relu2(h, up, down, squared: bool):
    act = jnp.maximum(h @ up, 0.0)
    return (act * act if squared else act) @ down


def _chosen(h, p, s: Shapes, top_k: int):
    """[T, E] weights of the chosen experts (0 elsewhere), and the 0/1
    choice itself."""
    score = jax.nn.sigmoid(h @ p["router"])
    picked = jnp.argsort(-(score + p["router_bias"]), axis=-1)[:, :top_k]
    choice = jax.nn.one_hot(picked, s.n_routed_experts,
                            dtype=jnp.float32).sum(axis=1)
    w = score * choice
    if s.norm_topk_prob:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * s.routed_scaling_factor, choice


def _experts(h, p, s: Shapes, k: dict):
    """The held experts' part of the layer for the tokens `h` [T, H]: a
    loop over the held experts, each run over EVERY token and weighted
    by the token's weight for it, which is zero where it was not chosen
    — the plain form of the sum, sixteen times the routed work."""
    w, choice = _chosen(h, p, s, k["top_k"])
    held = slice(s.expert_offset, s.expert_offset + s.experts_held)

    def expert(y, e):
        up, down, weight = e
        return y + weight[:, None] * _relu2(h, up, down, k["squared"]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["e_up"], p["e_down"], w[:, held].T))
    if k["shared_expert"]:
        y = y + _relu2(h, p["s_up"], p["s_down"], k["squared"])
    return y, choice


def _block(kind: str, x, p, s: Shapes, k: dict):
    u = _norm(x, p["norm"], s.layer_norm_epsilon)
    if kind == "M":
        return x + _mamba(u, p, s, k), None
    if kind == "*":
        return x + _attention(u, p, s), None
    y, choice = _experts(u, p, s, k)
    return x + y, choice


def _nll(x, norm, head, targets, s: Shapes):
    logits = _norm(x, norm, s.layer_norm_epsilon) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], logits [S, V],
    the expert layers' choices [expert layers, S, E]).  The blocks are a
    loop in their published order; each is recomputed in the backward
    pass."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    choices = []
    for i, kind in enumerate(s.hybrid_override_pattern):
        x, choice = jax.checkpoint(
            lambda x, q, kind=kind: _block(kind, x, q, s, k))(
                x, _sub(p, f"b{i}."))
        if choice is not None:
            choices.append(choice)
    nll, logits = _nll(x, p["final_norm"], p["head"], row[1:n + 1], s)
    return nll, logits, jnp.stack(choices)


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
    the same reference with one thing a faster program would be tempted
    to do."""

    def __init__(self, shapes: Shapes, theta_dtype=None, fewer_experts=0,
                 shared_expert=True, squared=True, conv=True,
                 state_reset=False, gate_first=True, skip_D=True):
        s = self.shapes = shapes
        k = self.switches = {
            "top_k": s.num_experts_per_tok - fewer_experts,
            "shared_expert": shared_expert, "squared": squared,
            "conv": conv, "state_reset": state_reset,
            "gate_first": gate_first, "skip_D": skip_D}
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
        _LAST_SLABS[:] = [(np.asarray(x), np.asarray(m))
                          for x, _, m in slabs]
        thetas, losses, t0 = [], [], time.time()
        with jax.default_matmul_precision(PRECISION):
            theta = self._device(theta0)
            for done in range(1, clocks + 1):
                total = jax.tree.map(jnp.zeros_like, theta)
                of_clock = []
                for rows, mask in _LAST_SLABS:
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
        """Per row: (nll [S], logits argmax [S], choices [expert layers,
        S, E]), host arrays."""
        out = []
        with jax.default_matmul_precision(PRECISION):
            p = self._device(theta)
            for row in np.asarray(rows):
                nll, logits, choices = self._row(p, jnp.asarray(row))
                out.append((np.asarray(nll), np.asarray(jnp.argmax(logits,
                                                                   -1)),
                            np.asarray(choices)))
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

@functools.lru_cache(maxsize=2)
def _plain(s: Shapes) -> Reference:
    return Reference(s)


def routing_differs(theta_a, theta_b, s: Shapes) -> float | None:
    """The share of (token, expert layer) choices of the last run's
    first slab on which two parameter vectors pick another set of experts:
    a top-k choice is discrete, so a small difference in the parameters
    can flip it, and the flipped token then trains another expert."""
    if not _LAST_SLABS:
        return None
    ref = _plain(s)
    differ = total = 0
    for rows, mask in _LAST_SLABS[:1]:      # the first worker's slab
        live = rows[np.asarray(mask) > 0]
        a = ref.forward_rows(theta_a, live)
        b = ref.forward_rows(theta_b, live)
        for (_, _, ca), (_, _, cb) in zip(a, b):
            differ += int((np.abs(ca - cb).sum(axis=-1) > 0).sum())
            total += ca.shape[0] * ca.shape[1]
    return differ / max(total, 1)


def param_gap(theta_prog, theta_ref, theta0, s: Shapes) -> float:
    """Worst leaf of | ||prog change|| - ||ref change|| | over the
    reference's norm of that leaf's change or of the median leaf's,
    whichever is larger (some leaves hardly move, the selection bias
    never).  A leaf at a time: three float64 copies of the whole vector
    would not fit the host.  Beside it, printed: the share of routing
    choices on which the two parameter vectors differ."""
    prog, ref, start = (split(np.asarray(t), s)
                        for t in (theta_prog, theta_ref, theta0))
    norms = {}
    for name, _ in s.leaves():
        base = start[name].astype(np.float64)
        norms[name] = (float(np.linalg.norm(prog[name] - base)),
                       float(np.linalg.norm(ref[name] - base)))
    floor = statistics.median(r for _, r in norms.values())
    worst, where = 0.0, ""
    for name, (got, want) in norms.items():
        gap = abs(got - want) / max(want, floor, 1e-30)
        if gap > worst:
            worst, where = gap, name
    share = routing_differs(theta_prog, theta_ref, s)
    print(f"[bench] reference: worst leaf {where!r} gap {worst!r}; routing "
          f"choices (token, expert layer) that differ between the two "
          f"parameter vectors: {share!r} of the first worker's slab",
          flush=True)
    return worst


# the controls of benchmark/control.py: Reference keywords by name, each
# what a faster program would be tempted by, and each has to break at
# least one limit of the cell.
#   theta_bf16        the shared parameters held in bfloat16 between
#                     clocks (half the delta, half the parameter plane)
#   top5              one expert a token fewer than published (5 for 6)
#   no_shared         the shared expert left out
#   relu_not_squared  the experts' activation relu, not relu squared
#   no_conv           the causal convolution left out (silu alone)
#   state_reset_each_chunk  the state dropped at every chunk's start:
#                     what a chunked scan whose hand-over from chunk to
#                     chunk is broken computes
#   norm_before_gate  the gated norm the other way round: the norm,
#                     then the gate
#   no_D              the skip term D x left out
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "top5": {"fewer_experts": 1},
            "no_shared": {"shared_expert": False},
            "relu_not_squared": {"squared": False},
            "no_conv": {"conv": False},
            "state_reset_each_chunk": {"state_reset": True},
            "norm_before_gate": {"gate_first": False},
            "no_D": {"skip_D": False}}
