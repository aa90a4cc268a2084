"""The plain reference of the `glm4-moe-lite` family: what one clock of
the parameter server means for GLM-4.7-Flash's published shape
(model_type glm4_moe_lite), in float32 `jax.numpy` at `highest` matmul
precision, one worker and one row at a time, and the comparison that
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
cross-entropy (position i predicts t[i+1]) plus `mtp_loss_weight` times
the MTP module's (position i, with Emb(t[i+1]), predicts t[i+2]).
Evaluation is the mean next-token cross-entropy, accuracy and
support-weighted F1 of argmax predictions over the held vocabulary on
the held-out rows.

The model, as published (config.json of zai-org/GLM-4.7-Flash; RMSNorm
eps 1e-5, no biases, a residual around each half of a layer):

  attention (MLA)  h = norm(x); c_q = norm(h Wqa); q = c_q Wqb -> heads
      of (nope | rope); (c_kv | k_r) = h Wkva; c_kv = norm(c_kv);
      (k_nope | v) = c_kv Wkvb per head; RoPE on q's rope part and on
      k_r, which all heads share; scores = (q_nope.k_nope + q_rope.k_r)
      / sqrt(nope + rope), causal softmax, P v -> Wo.
  layer 0          dense SwiGLU, width intermediate_size.
  later layers     s = sigmoid(h Wg) over ALL n_routed_experts; a token's
      experts are the top num_experts_per_tok of s + b; weights = s over
      its sum on the chosen, times routed_scaling_factor; y = sum of
      w_e SwiGLU_e(h) over the chosen experts THAT ARE HELD HERE
      (expert_offset .. + experts_held), plus the shared expert.  What
      the absent experts would add is left out, here as in the program:
      this chip's share of an expert-parallel group (the guide's cut).
  head             final norm, untied head over the vocab_held rows held.
  MTP              h' = Weh [norm_e(Emb(t[i+1])) | norm_h(h_i)] -> one
      expert block -> its own final norm -> the shared head.

Departures from the published description, and what it does not say
(`assumed` in the configuration's file):
  * RoPE is rotate-half over all qk_rope_head_dim dims
    (partial_rotary_factor 1), theta from the config;
  * the selection bias b is held fixed at its initial zeros: its update
    rule (noaux_tc) is not in the config, and no gradient reaches it;
  * n_group 1 / topk_group 1: no group limit, so none is computed;
  * the MTP module follows DeepSeek-V3's form, h_i taken BEFORE the
    final norm, the embedding first in the concatenation, weight
    mtp_loss_weight;
  * layers, rows and held experts are loops (`lax.scan` / `lax.map`)
    over their stacked leaves, and every block, and every row, is
    recomputed in the backward pass (`jax.checkpoint`): written out and
    kept, the gradient program took 212 s to compile and 16 GB; it
    changes no value, it lets the reference fit the chip beside its
    own four copies of the parameters;
  * the routed experts are computed an expert at a time over every
    token, under a weight that is zero where the expert was not chosen
    (`_experts`): the plain form of the same sum.

The flat layout (the wire contract, in this order; L = expert layers):
embed [V,H]; dense.{in_norm, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b,
wo, post_norm, w_gate, w_up, w_down}; moe.{in_norm .. post_norm, router
[H,E], router_bias [E], e_gate [held,H,I], e_up, e_down [held,I,H],
s_gate, s_up, s_down} each with a leading L; final_norm; head [H,V];
then, with the MTP module, mtp.{enorm, hnorm, eh_proj [2H,H], the keys
of one expert layer, final_norm}.  Weights multiply from the right
(x @ W).

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
# the slabs of the last `Reference.run`, host arrays: `param_gap` counts
# on them the routing choices that differ between two parameter vectors
_LAST_SLABS: list = []


@dataclasses.dataclass(frozen=True)
class Shapes:
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    num_hidden_layers: int
    num_nextn_predict_layers: int
    rms_norm_eps: float
    rope_theta: float
    experts_held: int
    expert_offset: int
    vocab_held: int
    sequence_length: int
    mtp_loss_weight: float
    init_std: float
    init_seed: int
    local_iterations: int
    local_lr: float
    num_workers: int

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - 1      # first_k_dense_replace 1

    def attention(self) -> list[tuple[str, tuple[int, ...]]]:
        h, nh = self.hidden_size, self.num_attention_heads
        return [("in_norm", (h,)), ("wq_a", (h, self.q_lora_rank)),
                ("q_norm", (self.q_lora_rank,)),
                ("wq_b", (self.q_lora_rank, nh * (self.qk_nope_head_dim
                                                  + self.qk_rope_head_dim))),
                ("wkv_a", (h, self.kv_lora_rank + self.qk_rope_head_dim)),
                ("kv_norm", (self.kv_lora_rank,)),
                ("wkv_b", (self.kv_lora_rank,
                           nh * (self.qk_nope_head_dim + self.v_head_dim))),
                ("wo", (nh * self.v_head_dim, h)), ("post_norm", (h,))]

    def expert_layer(self) -> list[tuple[str, tuple[int, ...]]]:
        h, i, e = (self.hidden_size, self.moe_intermediate_size,
                   self.experts_held)
        s = self.n_shared_experts * i
        return self.attention() + [
            ("router", (h, self.n_routed_experts)),
            ("router_bias", (self.n_routed_experts,)),
            ("e_gate", (e, h, i)), ("e_up", (e, h, i)), ("e_down", (e, i, h)),
            ("s_gate", (h, s)), ("s_up", (h, s)), ("s_down", (s, h))]

    def leaves(self) -> list[tuple[str, tuple[int, ...]]]:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_held
        out = [("embed", (v, h))]
        out += [("dense." + n, s) for n, s in self.attention()]
        out += [("dense.w_gate", (h, i)), ("dense.w_up", (h, i)),
                ("dense.w_down", (i, h))]
        out += [("moe." + n, (self.moe_layers,) + s)
                for n, s in self.expert_layer()]
        out += [("final_norm", (h,)), ("head", (h, v))]
        if self.num_nextn_predict_layers:
            out += [("mtp.enorm", (h,)), ("mtp.hnorm", (h,)),
                    ("mtp.eh_proj", (2 * h, h))]
            out += [("mtp." + n, s) for n, s in self.expert_layer()]
            out += [("mtp.final_norm", (h,))]
        return out

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
    assert body["first_k_dense_replace"] == 1, "one leading dense layer"
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
    """The deployment's stated start, as a host vector: every matrix
    normal(0, init_std) from PRNGKey(init_seed) folded with the leaf's
    place in the layout, norm weights one, the selection bias zero."""
    key = jax.random.PRNGKey(s.init_seed)
    parts = []
    for at, (name, shape) in enumerate(s.leaves()):
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm"):
            leaf = np.ones(shape, np.float32)
        elif last == "router_bias":
            leaf = np.zeros(shape, np.float32)
        else:
            leaf = np.asarray(s.init_std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32))
        parts.append(leaf.reshape(-1))
    return np.concatenate(parts)


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def _rope(x, theta):
    """x [S, heads, d]: rotate-half RoPE, position = row index."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(x, p, s: Shapes, rope: bool):
    """One row `[S, H]`."""
    n, nh = x.shape[0], s.num_attention_heads
    dn, dr, dv = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    h = _norm(x, p["in_norm"], s.rms_norm_eps)
    q = (_norm(h @ p["wq_a"], p["q_norm"], s.rms_norm_eps)
         @ p["wq_b"]).reshape(n, nh, dn + dr)
    kva = h @ p["wkv_a"]
    c_kv = _norm(kva[:, :s.kv_lora_rank], p["kv_norm"], s.rms_norm_eps)
    k_r = kva[:, None, s.kv_lora_rank:]                 # one head, shared
    kv = (c_kv @ p["wkv_b"]).reshape(n, nh, dn + dv)
    q_nope, q_r = q[..., :dn], q[..., dn:]
    if rope:
        q_r, k_r = _rope(q_r, s.rope_theta), _rope(k_r, s.rope_theta)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_r, k_r[:, 0])) / math.sqrt(
                  dn + dr)
    future = jnp.arange(n)[None, :] > jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(future[None], -jnp.inf, scores), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
    return out.reshape(n, nh * dv) @ p["wo"]


def _swiglu(h, gate, up, down):
    g = h @ gate
    return ((g * jax.nn.sigmoid(g)) * (h @ up)) @ down


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
    — the plain form of the sum, sixteen times the routed work.  (A
    gather of each expert's own tokens, up to four times an even share,
    was tried: with Zipf-distributed ids some expert is chosen by more
    than a quarter of a row's tokens in nearly every pass, my chip run,
    PR 27, so it only added a second program.)"""
    w, choice = _chosen(h, p, s, k["top_k"])
    held = slice(s.expert_offset, s.expert_offset + s.experts_held)

    def expert(y, e):
        gate, up, down, weight = e
        return y + weight[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["e_gate"], p["e_up"], p["e_down"],
                         w[:, held].T))
    if k["shared_expert"]:
        y = y + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"])
    return y, choice


def _dense_layer(x, p, s: Shapes, k: dict):
    x = x + _attention(x, p, s, k["rope"])
    return x + _swiglu(_norm(x, p["post_norm"], s.rms_norm_eps),
                       p["w_gate"], p["w_up"], p["w_down"])


def _expert_layer(x, p, s: Shapes, k: dict):
    x = x + _attention(x, p, s, k["rope"])
    y, choice = _experts(_norm(x, p["post_norm"], s.rms_norm_eps), p, s, k)
    return x + y, choice


def _nll(x, norm, head, targets, s: Shapes):
    logits = _norm(x, norm, s.rms_norm_eps) @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def _sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def _row(p: dict, row, s: Shapes, k: dict):
    """One row of S + 2 tokens -> (next-token nll [S], MTP nll [S] or
    None, logits [S, V], the expert layers' choices [blocks, S, E]).
    The expert layers are a loop over their stacked leaves; each layer
    is recomputed in the backward pass."""
    n = s.sequence_length
    x = p["embed"][row[:n]]
    x = jax.checkpoint(lambda x, q: _dense_layer(x, q, s, k))(
        x, _sub(p, "dense."))
    x, choices = jax.lax.scan(
        jax.checkpoint(lambda x, q: _expert_layer(x, q, s, k)), x,
        _sub(p, "moe."))
    nll, logits = _nll(x, p["final_norm"], p["head"], row[1:n + 1], s)
    mtp_nll = None
    if s.num_nextn_predict_layers:
        m = _sub(p, "mtp.")
        joined = jnp.concatenate(
            [_norm(p["embed"][row[1:n + 1]], m["enorm"], s.rms_norm_eps),
             _norm(x, m["hnorm"], s.rms_norm_eps)], axis=-1)
        y, choice = jax.checkpoint(
            lambda x, q: _expert_layer(x, q, s, k))(joined @ m["eh_proj"], m)
        mtp_nll, _ = _nll(y, m["final_norm"], p["head"], row[2:n + 2], s)
        choices = jnp.concatenate([choices, choice[None]], axis=0)
    return nll, mtp_nll, logits, choices


def _objective(p: dict, rows, mask, s: Shapes, k: dict):
    """Mean over the unmasked rows' positions, a row at a time, in the
    backward pass too: two rows' activations would stand beside the
    four copies of the parameters."""
    def one(row):
        nll, mtp_nll, _, _ = _row(p, row, s, k)
        loss = nll.sum()
        if mtp_nll is not None and k["mtp_loss"]:
            loss = loss + s.mtp_loss_weight * mtp_nll.sum()
        return loss
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
                 shared_expert=True, rope=True, mtp_loss=True):
        s = self.shapes = shapes
        k = self.switches = {
            "top_k": s.num_experts_per_tok - fewer_experts,
            "shared_expert": shared_expert, "rope": rope,
            "mtp_loss": mtp_loss}
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
        """Per row: (nll [S], logits argmax [S], choices [blocks, S, E]),
        host arrays."""
        out = []
        with jax.default_matmul_precision(PRECISION):
            p = self._device(theta)
            for row in np.asarray(rows):
                nll, _, logits, choices = self._row(p, jnp.asarray(row))
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
#   theta_bf16   the shared parameters held in bfloat16 between clocks
#                (half the delta, half the parameter plane's bytes)
#   top3         one expert a token fewer than published (three for four)
#   no_shared    the shared expert left out
#   no_rope      no rotary position on q's and k's rope parts
#   no_mtp_loss  the MTP module's loss left out (its parameters then
#                never move)
CONTROLS = {"theta_bf16": {"theta_dtype": jnp.bfloat16},
            "top3": {"fewer_experts": 1},
            "no_shared": {"shared_expert": False},
            "no_rope": {"rope": False},
            "no_mtp_loss": {"mtp_loss": False}}
