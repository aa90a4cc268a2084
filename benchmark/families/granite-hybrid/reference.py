"""The plain reference of the `granite-hybrid` family: what one clock of
the parameter server means for IBM Granite-4.0-H-Micro's published
shape (model_type granitemoehybrid), in float32 `jax.numpy` at `highest`
matmul precision, one worker, one row and one query head at a time, and
the comparison that decides `correct`.

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

The model.  (c) is what config.json of ibm-granite/granite-4.0-h-micro
states; (m1)-(m4) are ASSUMED, the config having no key for them, and
stand under `assumed` in the configuration's file in these words.

  frame      x0 = embedding_multiplier (c: 12) * E[tokens]; the layers;
      a final RMSNorm (eps rms_norm_eps 1e-5 (c)); logits = (N_f(x_L)
      E^T) / logits_scaling (c: 8) over the held slice — the head is the
      embedding transposed (c tie_word_embeddings), ONE matrix used
      twice; loss = mean next-token cross-entropy over the slice.
  layer      two norms a layer, a = x + residual_multiplier (c: 0.22) *
      Mix(N_in(x)), y = a + residual_multiplier * MLP(N_post(a)), each N
      an RMSNorm with its own weight (input_norm, post_norm); the
      multiplier on BOTH branches.  layer_types (c) says which Mix:
      mamba or attention.  There is no expert layer (c num_local_experts
      0): the MLP is the public code's shared_mlp alone.
  mamba      u the normed input: (z | xBC | dt) = u W_in, IN THIS ORDER,
      no bias (c mamba_proj_bias false); xBC <- silu(conv(xBC) + b), a
      causal depthwise convolution of mamba_d_conv (c: 4) taps with
      bias (c), zeros before the row's start; (x | B | C) = xBC, x as
      [S, heads, P] (c: 64 heads of 64), B and C as [S, groups, N] (c:
      ONE group of 128: every head reads the same B_t and C_t); D_t =
      softplus(dt + dt_bias), (m2) not clamped (time_step_limit (0,
      inf)); A_h = -exp(A_log_h); the state H_t,h = exp(D_t,h A_h)
      H_t-1,h + D_t,h x_t,h (x) B_t in R^{P x N} from zero; y_t,h =
      H_t,h C_t + D_h x_t,h; the gated norm, gate FIRST: RMSNorm over
      each group's channels (one group: all 4096) of y * silu(z), times
      w; Mix = y W_out.  THE RECURRENCE ITSELF, a step a token
      (`_recurrence`): the definition, not the program's chunked
      algorithm (c mamba_chunk_size 256 is the program's).  THE
      CONVOLUTION AS ITS DEFINITION (`_conv`): an explicit sum over the
      taps on a row padded with mamba_d_conv - 1 zeros in front.
  attention  q = u W_q as [S, heads, 64], k = u W_k, v = u W_v as [S, kv
      heads, 64] (c: head size hidden_size / num_attention_heads), no
      bias (c), no head norm, (m3) NO positional encoding (c
      position_embedding_type nope; rope_theta is unused); scores q.k *
      attention_multiplier (c: 0.015625 = 1/64, NOT 1/sqrt(64)); query
      head h reads KV head h // (heads / kv heads); query i sees key j
      iff j <= i; softmax; Mix = (P v) W_o.  ATTENTION AS ITS
      DEFINITION (`_attention`): the whole [S, S] score matrix of a
      head with the mask written as that inequality, a head at a time.
  MLP        (g | u) = h W_1, (m4) gate first (the public code's
      chunk(2)); (silu(g) * u) W_2, at shared_intermediate_size (c), no
      bias.

(m1) initialisation of the Mamba-2 leaves, the nemotron-h family's
start and for its reason (at normal 0.02 the state's part of y is under
1% and the scan's controls break no limit): A_log = log(uniform[1,
16]), dt_bias the inverse softplus of log-uniform[time_step_min 0.001,
time_step_max 0.1] floored at time_step_floor 1e-4 (the config has no
time_step_* key; the three stand in the model file), D one, the
convolution's weights and bias uniform[-1/sqrt(k), 1/sqrt(k)].  Further
assumed: matrices normal(0, init_std) from the model file's init_seed,
norms at one; float32 parameters and plain SGD.

How it is computed: layers, rows and query heads are loops; every
layer, every row and every head is recomputed in the backward pass
(`jax.checkpoint`), and the recurrence is recomputed in runs of
`_SEGMENT` steps (the state kept at each run's start, so a layer's
gradient keeps the states of S / _SEGMENT + _SEGMENT positions and not
of S): it changes no value, it lets the reference fit the chip beside
its own four copies of the parameters.

The flat layout (the wire contract, in this order): embed [V,H]; the
layers l<i>.{input_norm, then a mamba layer's w_in [H, inner + conv_dim
+ heads], conv_w [conv_dim, k], conv_b, dt_bias, A_log, D, gate_norm
[inner], w_out [inner, H] or an attention layer's wq, wk, wv, wo, then
post_norm, w1 [H, 2I], w2 [I, H]}; final_norm.  There is no head leaf.
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
MAMBA, ATTENTION = "mamba", "attention"
_SEGMENT = 32      # steps of the recurrence recomputed together


@dataclasses.dataclass(frozen=True)
class Shapes:
    hidden_size: int
    shared_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple
    num_hidden_layers: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_n_groups: int
    mamba_chunk_size: int
    rms_norm_eps: float
    vocab_held: int
    sequence_length: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layer(self, kind: str) -> list[tuple[str, tuple[int, ...]]]:
        h, i = self.hidden_size, self.shared_intermediate_size
        out = [("input_norm", (h,))]
        if kind == MAMBA:
            out += [("w_in", (h, self.inner + self.conv_dim
                              + self.mamba_n_heads)),
                    ("conv_w", (self.conv_dim, self.mamba_d_conv)),
                    ("conv_b", (self.conv_dim,)),
                    ("dt_bias", (self.mamba_n_heads,)),
                    ("A_log", (self.mamba_n_heads,)),
                    ("D", (self.mamba_n_heads,)),
                    ("gate_norm", (self.inner,)),
                    ("w_out", (self.inner, h))]
        else:
            q = self.num_attention_heads * self.head_dim
            kv = self.num_key_value_heads * self.head_dim
            out += [("wq", (h, q)), ("wk", (h, kv)), ("wv", (h, kv)),
                    ("wo", (q, h))]
        return out + [("post_norm", (h,)), ("w1", (h, 2 * i)),
                      ("w2", (i, h))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        out = [("embed", (self.vocab_held, self.hidden_size))]
        for i, kind in enumerate(self.layer_types):
            out += [(f"l{i}.{n}", s) for n, s in self.layer(kind)]
        return out + [("final_norm", (self.hidden_size,))]

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
    assert len(body["layer_types"]) == body["num_hidden_layers"]
    assert set(body["layer_types"]) <= {MAMBA, ATTENTION}
    assert not body["num_local_experts"], "no expert layer"
    assert body["position_embedding_type"] == "nope"
    assert body["tie_word_embeddings"], "one matrix at both ends"
    assert body["mamba_conv_bias"] and not body["mamba_proj_bias"]
    keys = {f.name for f in dataclasses.fields(Shapes)}
    body = dict(body, layer_types=tuple(body["layer_types"]))
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
    docstring has the distributions, (m1))."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        k = jax.random.fold_in(key, at)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm") or last == "D":
            leaf = np.ones(shape, np.float32)
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
            bound = 1.0 / math.sqrt(s.mamba_d_conv)
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


def _conv(x, w, bias, reversed_taps: bool):
    """The causal depthwise filter on one row `[S, C]` with taps `w`
    `[C, k]`, as its definition: the row padded with k - 1 zeros in
    front, out_t = bias + sum over j of w[:, j] * padded_(t + j), so
    that w[:, k - 1] weighs the token itself.  `reversed_taps` is the
    control that reads the taps the other way round (w[:, 0] on the
    token itself)."""
    n, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    if reversed_taps:
        w = w[:, ::-1]
    return bias + sum(padded[j:j + n] * w[:, j] for j in range(taps))


def _recurrence(x, dt, a, bm, cm, reset_every: int):
    """The state-space layer as its definition, a step a token: x [S,
    heads, P], dt [S, heads], a [heads], bm, cm [S, N] (the ONE group's,
    which every head reads) -> y [S, heads, P].  `reset_every` > 0 drops
    the state at every multiple of it (a control, never the model)."""
    n = x.shape[0]

    def step(state, token):
        x_t, dt_t, b_t, c_t, at = token
        if reset_every:
            state = jnp.where(at % reset_every == 0, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t

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
    n, nh, hd = u.shape[0], s.mamba_n_heads, s.mamba_d_head
    ns, inner = s.mamba_d_state, s.inner
    assert s.mamba_n_groups == 1, "one group of B and C"
    zxbcdt = u @ p["w_in"]
    z = zxbcdt[:, :inner]
    xbc = _silu(_conv(zxbcdt[:, inner:inner + s.conv_dim], p["conv_w"],
                      p["conv_b"], k["reversed_taps"]))
    dt = zxbcdt[:, inner + s.conv_dim:]
    x = xbc[:, :inner].reshape(n, nh, hd)
    bm, cm = xbc[:, inner:inner + ns], xbc[:, inner + ns:]
    delta = jnp.logaddexp(dt + p["dt_bias"], 0.0)          # softplus
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), bm, cm,
                    s.mamba_chunk_size if k["state_reset"] else 0)
    if k["skip_D"]:
        y = y + p["D"][:, None] * x
    y, gate = y.reshape(n, inner), _silu(z)
    if k["gate_first"]:
        y = _norm(y * gate, p["gate_norm"], s.rms_norm_eps)
    else:
        y = _norm(y, p["gate_norm"], s.rms_norm_eps) * gate
    return y @ p["w_out"]


def _attention(u, p, s: Shapes, k: dict):
    """One row `[S, H]`, already normed; a query head at a time, each
    against the whole [S, S] score matrix of its key/value head."""
    n, nh, d = u.shape[0], s.num_attention_heads, s.head_dim
    per_kv = nh // s.num_key_value_heads
    q = (u @ p["wq"]).reshape(n, nh, d)
    key = (u @ p["wk"]).reshape(n, s.num_key_value_heads, d)
    val = (u @ p["wv"]).reshape(n, s.num_key_value_heads, d)
    scale = 1.0 / math.sqrt(d) if k["scores_sqrt"] else s.attention_multiplier
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def head(args):
        q_h, at = args
        k_h, v_h = key[:, at // per_kv], val[:, at // per_kv]
        scores = jnp.where(seen, (q_h @ k_h.T) * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(jax.checkpoint(head),
                      (q.transpose(1, 0, 2), jnp.arange(nh)))
    return out.transpose(1, 0, 2).reshape(n, nh * d) @ p["wo"]


def _mlp(h, p, s: Shapes):
    i = s.shared_intermediate_size
    gu = h @ p["w1"]
    return (_silu(gu[:, :i]) * gu[:, i:]) @ p["w2"]


def _layer(x, p, s: Shapes, k: dict, kind: str):
    r = 1.0 if k["residual_one"] else s.residual_multiplier
    u = _norm(x, p["input_norm"], s.rms_norm_eps)
    a = x + r * (_mamba(u, p, s, k) if kind == MAMBA
                 else _attention(u, p, s, k))
    return a + r * _mlp(_norm(a, p["post_norm"], s.rms_norm_eps), p, s)


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], logits [S, V]).
    The layers are a loop in their published order; each is recomputed
    in the backward pass.  The head is the embedding transposed — the
    same array, used a second time — unless the control's own `head`
    leaf is there."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    if k["embed_scale"]:
        x = x * s.embedding_multiplier
    for i, kind in enumerate(s.layer_types):
        x = jax.checkpoint(
            lambda x, q, kind=kind: _layer(x, q, s, k, kind))(
                x, _sub(p, f"l{i}."))
    head = p["head"] if "head" in p else p["embed"].T
    logits = _norm(x, p["final_norm"], s.rms_norm_eps) @ head
    if k["logits_scale"]:
        logits = logits / s.logits_scaling
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, row[1:n + 1, None], axis=-1)[:, 0], \
        logits


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

    def __init__(self, shapes: Shapes, theta_dtype=None, state_reset=False,
                 skip_D=True, gate_first=True, reversed_taps=False,
                 residual_one=False, scores_sqrt=False, embed_scale=True,
                 logits_scale=True, untied_head=False):
        s = self.shapes = shapes
        k = self.switches = {
            "state_reset": state_reset, "skip_D": skip_D,
            "gate_first": gate_first, "reversed_taps": reversed_taps,
            "residual_one": residual_one, "scores_sqrt": scores_sqrt,
            "embed_scale": embed_scale, "logits_scale": logits_scale}
        self.untied_head = untied_head
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
        s = self.shapes
        p = {n: self._store(jnp.asarray(v, jnp.float32))
             for n, v in split(np.asarray(theta), s).items()}
        if self.untied_head:
            # the control's second matrix: a head of its own, from the
            # key after the layout's last, trained beside the leaves and
            # no part of the flat vector that is compared
            p["head"] = self._store(s.init_std * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(s.init_seed),
                                   len(s.leaves())),
                (s.hidden_size, s.vocab_held), jnp.float32))
        return p

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
#   theta_bf16              the shared parameters held in bfloat16 between
#                           clocks (half the delta, half the plane)
#   state_reset_each_chunk  the state dropped at every chunk's start: what
#                           a chunked scan whose hand-over from chunk to
#                           chunk is broken computes
#   no_D                    the skip term D x left out
#   norm_before_gate        the gated norm the other way round: the norm,
#                           then the gate
#   taps_reversed           the convolution's taps read the other way
#                           round: w[:, 0] on the token itself
#   residual_one            residual_multiplier 1 for 0.22, on both branches
#   scores_sqrt             scores times 1/sqrt(64) for attention_multiplier
#                           1/64
#   no_embed_scale          embedding_multiplier left out
#   no_logits_scale         logits_scaling left out
#   untied_head             a head of its own (a second matrix, from
#                           another key) for the embedding transposed
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "state_reset_each_chunk": {"state_reset": True},
            "no_D": {"skip_D": False},
            "norm_before_gate": {"gate_first": False},
            "taps_reversed": {"reversed_taps": True},
            "residual_one": {"residual_one": True},
            "scores_sqrt": {"scores_sqrt": True},
            "no_embed_scale": {"embed_scale": False},
            "no_logits_scale": {"logits_scale": False},
            "untied_head": {"untied_head": True}}
