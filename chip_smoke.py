#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process drives the parameter server's main path through the entry
point a user calls (`kafka_ps_tpu.cli.run.main`) at the full width of
the models the repo supports, on data made from a seed:

  * the per-node message path (producer → buffers → k-step solver →
    consistency gate → apply → async eval), logreg F=1024 C=5, 4
    workers, default flags, once per consistency model (-c 0, 2, -1);
  * fused BSP at the widest model, `--task mlp --hidden_dim 4096`
    (≈4.2 M parameters), at `--eval_every 1` (per-round program) and
    `--eval_every 8` (the 8-round scan chunk);
  * the language models' grouped products told their tiles
    (`models/lm_common.py` `grouped_tiles`) against the chip's kernel
    left to itself, at the widths of the second language-model cell;
  * the attention core as the kernel (`models/attention_kernel.py`)
    against its plain tiles at the third language-model cell's shapes,
    a sliding and a full layer, forward and `jax.grad`: the gaps and
    the ms a call of each;
  * a head's norm and RoPE as its kernel (`models/norm_rope_kernel.py`)
    against the plain lines at the third and fifth language-model
    cells' shapes — q's 32 heads with the core's scale, k's 4, and a
    full layer's norm alone — forward and `jax.grad`: the gaps and the
    ms a call of each;
  * the expert layer's placement as its two kernels
    (`models/placement_kernel.py`) against the products with the 0/1
    matrix written out, at the fifth language-model cell's shape: to
    the bit where a row has one term, NaN in the dead rows, the ms a
    call of each;
  * the chunked state-space scan as its kernels
    (`models/ssd_kernel.py`) against the einsums at the seventh and the
    second language-model cells' shapes, forward and `jax.grad` of all
    six inputs: the gaps and the ms a call of each;
  * with more than one chip: `--fused -r` and `--fused --param_shards`
    over all of them.

Each phase is checked by the repo's own means (the eval CSVs, the
staleness auditor, the eval engine's lag, where theta lives) and the
first failed check ends the run with a traceback and a non-zero exit.
Stdout ends with two JSON lines, printed only when every phase passed:
the report (per-phase times, solver programs, cache, `"claim": null`)
and then, last, the verdict — exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as JAX reports it.

Exit codes: 0 all phases passed on a TPU; 2 JAX found no TPU (says what
it found); anything else a failed phase.  `python chip_smoke.py`, no
arguments; under 1200 s cold.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

NUM_CLASSES = 5
CHANCE_F1 = 1.0 / NUM_CLASSES     # hard regime: offline ceiling ≈ 0.54


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  The defaults are the contract (full
    width); tests/test_chip_smoke.py shrinks them for the CPU."""

    num_features: int = 1024
    fused_hidden: int = 4096      # widest supported model (fused BSP)
    buffer_min: int = 128
    buffer_max: int = 1024        # slab rows per worker
    train_rows: int = 4096        # 4 workers x 1024 rows
    test_rows: int = 2000
    per_node_clocks: int = 32
    fused_rounds: int = 24        # 3 scan chunks at --eval_every 8
    multichip_rounds: int = 8
    center_scale: float = 0.2     # synth.HARD_CENTER_SCALE: class overlap
    grouped_rows: int = 768       # a pass's placed rows, 8 held experts
    grouped_widths: tuple = (2688, 1856)      # hidden x expert width
    # the attention core at the third language-model cell's shapes: q
    # [B, S, G, R, D], the sliding layers' window, the tile
    core_shape: tuple = (1, 4096, 4, 8, 128)
    # and the sixth's: heads of 64 channels, two to a lane vector
    core_shape_halves: tuple = (1, 4096, 8, 4, 64)
    core_window: int = 2048
    core_block: int = 512
    core_calls: int = 5           # timed calls a program, after a warm one
    # the expert layer's placement at the fifth language-model cell's
    # shape: (rows under the bound, tokens, hidden), and the rows of the
    # held groups as its traced run routed them (one hot expert, PERF.md)
    placement_shape: tuple = (16384, 4096, 2304)
    placement_groups: tuple = (3247,) + (253,) * 15
    # a head's norm and RoPE: a row's positions, (q's heads, k's), the
    # channels a head — the third and fifth language-model cells'
    norm_rope_positions: int = 4096
    norm_rope_heads: tuple = (32, 4)
    norm_rope_dim: int = 128
    # the state-space scan at the seventh and second language-model
    # cells' shapes: (x `[B, S, heads, P]`, groups, state, chunk)
    scan_shapes: tuple = (((1, 2048, 64, 64), 1, 128, 256),
                          ((1, 1024, 64, 64), 8, 128, 128))


class SmokeFailure(RuntimeError):
    """A phase check did not hold."""


def require(cond, phase: str, what: str) -> None:
    if not cond:
        raise SmokeFailure(f"[{phase}] {what}")


# -- shared plumbing ---------------------------------------------------------


def make_data(workdir: str, sizes: Sizes) -> tuple[str, str]:
    """Seeded train/test CSVs (kafka_ps_tpu.data.synth; the hard,
    non-separable regime at full width) — nothing outside the checkout,
    no network."""
    from kafka_ps_tpu.data import synth
    x, y = synth.generate(sizes.train_rows + sizes.test_rows,
                          sizes.num_features, NUM_CLASSES, seed=0,
                          center_scale=sizes.center_scale)
    train = os.path.join(workdir, "train.csv")
    test = os.path.join(workdir, "test.csv")
    synth.write_csv(train, x[:sizes.train_rows], y[:sizes.train_rows])
    synth.write_csv(test, x[sizes.train_rows:], y[sizes.train_rows:])
    return train, test


@contextlib.contextmanager
def _in_dir(path: str):
    os.makedirs(path)
    cwd = os.getcwd()
    os.chdir(path)
    try:
        yield
    finally:
        os.chdir(cwd)


def run_cli(phase: str, phase_dir: str, argv: list[str]):
    """One run through the normal entry point, in this process, from a
    directory of its own (the CLI writes ./logs-*.csv).  Returns the
    StreamingPSApp the run built (observed, not altered) and the wall
    times around it."""
    from kafka_ps_tpu.cli import run as run_mod

    seen = {}
    real = run_mod.make_app_from_args

    def observe(*args, **kwargs):
        app, logs = real(*args, **kwargs)
        seen["app"], seen["built_at"] = app, time.time()
        return app, logs

    run_mod.make_app_from_args = observe
    started = time.time()
    try:
        with _in_dir(phase_dir):
            rc = run_mod.main(argv)
    finally:
        run_mod.make_app_from_args = real
    require(rc == 0, phase, f"cli.run.main returned {rc}")
    return seen["app"], {"started": started, "built_at": seen["built_at"],
                         "ended": time.time()}


def _platform_of(array) -> str:
    return next(iter(array.devices())).platform


def check_run(phase: str, phase_dir: str, app, times: dict, *,
              consistency: int, owed_clocks: list[int],
              platform: str, theta_on_device: bool = True,
              learns: bool = True) -> dict:
    """The checks every CLI phase owes, by the repo's own means.
    `learns=False` (the short multi-chip runs) keeps every check but
    the two on learning quality."""
    import numpy as np

    from kafka_ps_tpu.evaluation import logs, validate

    server_csv = os.path.join(phase_dir, "logs-server.csv")
    worker_csv = os.path.join(phase_dir, "logs-worker.csv")
    for path in (server_csv, worker_csv):
        with open(path) as f:
            require("nan" not in f.read().lower(), phase,
                    f"{os.path.basename(path)} holds a nan")
    server = logs.load_server_log(server_csv)
    worker = logs.load_worker_log(worker_csv)

    got = [int(c) for c in server["vectorClock"]]
    require(got == owed_clocks, phase,
            f"logs-server.csv clocks {got} != the rows the eval cadence "
            f"owes {owed_clocks}")
    for col in ("loss", "fMeasure", "accuracy"):
        require(bool(np.isfinite(server[col]).all()), phase,
                f"server {col} not finite")
    require(bool(np.isfinite(worker["loss"]).all()), phase,
            "worker loss not finite")
    first, last = server.iloc[0], server.iloc[-1]
    if learns:
        require(last["loss"] < first["loss"], phase,
                f"test loss did not fall: clock "
                f"{int(first['vectorClock'])} {first['loss']:.4f} -> clock "
                f"{int(last['vectorClock'])} {last['loss']:.4f}")
        require(last["fMeasure"] > CHANCE_F1 + 0.05, phase,
                f"final F1 {last['fMeasure']:.3f} not above chance "
                f"({CHANCE_F1:.2f})")

    violations = validate.validate_run(worker, server, consistency)
    require(violations == [], phase, f"validate_run: {violations}")
    if app.eval_engine is not None:
        require(app.eval_engine.lag_clocks == 0, phase,
                f"eval_lag_clocks {app.eval_engine.lag_clocks} != 0")

    import jax
    theta = app.server.theta
    if theta_on_device:
        require(isinstance(theta, jax.Array), phase,
                f"final theta is a {type(theta).__name__}, not a device "
                "array")
        require(_platform_of(theta) == platform, phase,
                f"final theta lives on {_platform_of(theta)}, not "
                f"{platform}")
    require(bool(np.isfinite(np.asarray(theta)).all()), phase,
            "final theta not finite")

    first_row = float(worker["timestamp"].iloc[0]) / 1e3
    return {
        "solver": app.solver,
        "wall_s": round(times["ended"] - times["started"], 2),
        # test CSV load + app construction
        "setup_s": round(times["built_at"] - times["started"], 2),
        # first call: app built -> first worker row (stream prefill,
        # compile, first dispatch returned)
        "first_row_s": round(first_row - times["built_at"], 2),
        # steady: first worker row -> run end (the remaining clocks,
        # the device queue drained, logs flushed, threads joined) —
        # row stamps are enqueue times, so only the END is a sync point
        "rest_s": round(times["ended"] - first_row, 2),
        "clocks": int(worker["vectorClock"].nunique()),
        "eval_rows": len(got),
        "loss": [round(float(first["loss"]), 4),
                 round(float(last["loss"]), 4)],
        "f1": round(float(last["fMeasure"]), 3),
    }


def _common_flags(train: str, test: str, sizes: Sizes) -> list[str]:
    return ["-training", train, "-test", test, "-l", "-p", "0",
            "--num_features", str(sizes.num_features),
            "--num_classes", str(NUM_CLASSES),
            "-min", str(sizes.buffer_min), "-max", str(sizes.buffer_max)]


# -- phases ------------------------------------------------------------------


def phase_per_node(workdir: str, train: str, test: str, sizes: Sizes,
                   platform: str, consistency: int) -> dict:
    """The per-node message path — the one that carries every
    subsystem — with default flags: threaded, gang on, async eval on,
    k=2, eval every clock."""
    workers = 4
    clocks = sizes.per_node_clocks
    name = f"per_node_c{consistency}"
    argv = _common_flags(train, test, sizes) + [
        "-c", str(consistency), "--num_workers", str(workers),
        "--max_iterations", str(workers * clocks)]
    phase_dir = os.path.join(workdir, name)
    app, times = run_cli(name, phase_dir, argv)

    # one eval row per worker-0 gradient the server applied
    applied = app.server.tracker.tracker[0].vector_clock
    if consistency == 0:
        require(applied == clocks, name,
                f"BSP applied {applied} clocks of worker 0, not {clocks}")
    rec = check_run(name, phase_dir, app, times, consistency=consistency,
                    owed_clocks=list(range(applied)), platform=platform)
    for w in app.workers:
        require(_platform_of(w.theta) == platform, name,
                f"worker {w.worker_id} replica lives on "
                f"{_platform_of(w.theta)}")
    return rec


def phase_fused(workdir: str, train: str, test: str, sizes: Sizes,
                platform: str, eval_every: int) -> dict:
    """Fused BSP at the widest supported model: eval_every 1 is the
    per-round program, eval_every 8 the 8-round scan chunk
    (StreamingPSApp.FUSED_CHUNK_ROUNDS)."""
    workers = 4
    name = f"fused_mlp{sizes.fused_hidden}_eval{eval_every}"
    argv = _common_flags(train, test, sizes) + [
        "--fused", "--task", "mlp", "--hidden_dim", str(sizes.fused_hidden),
        "--num_workers", str(workers), "--eval_every", str(eval_every),
        "--max_iterations", str(workers * sizes.fused_rounds)]
    phase_dir = os.path.join(workdir, name)
    app, times = run_cli(name, phase_dir, argv)
    owed = [c for c in range(1, sizes.fused_rounds + 1)
            if c % eval_every == 0]
    rec = check_run(name, phase_dir, app, times, consistency=0,
                    owed_clocks=owed, platform=platform)
    chunked = "multi_step" in next(iter(app._fused_programs.values()))
    require(chunked == (eval_every >= app.FUSED_CHUNK_ROUNDS), name,
            f"scan-chunk program built={chunked} at eval_every={eval_every}")
    rec["program"] = (f"bsp-scan-{app.FUSED_CHUNK_ROUNDS}" if chunked
                      else "bsp-step")
    rec["params"] = int(app.server.task.num_params)
    return rec


def phase_grouped_products(sizes: Sizes, platform: str) -> dict:
    """The expert layer's grouped product with the kernel told its
    tiles (`lm_common.told_grouped` under `grouped_tiles`) against the
    kernel left to itself: the product, dx and dW, in both orientations
    (up `[m, k] x [g, k, n]`, down `[m, n] x [g, n, k]`), over groups
    that fill under half the rows, one of them empty, so that rows past
    the last group and, at 1856 = 2.9 x 640, a partial last tile are
    both there.  The live rows and dW equal the untold kernel's to
    float32 rounding; the rows past the last group, NaN in both
    operands, reach no live row and no dW, and dx is zero there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafka_ps_tpu.models import lm_common as lm

    name, started = "grouped_products", time.time()
    m, held = sizes.grouped_rows, 8
    rng = np.random.default_rng(0)
    group_sizes = jnp.asarray(
        np.array([7, 4, 0, 5, 3, 4, 6, 3]) * (m // 74), jnp.int32)
    n_live = int(group_sizes.sum())
    live = (jnp.arange(m) < n_live)[:, None]
    rec = {"rows": m, "live_rows": n_live}
    for way, (k, n) in (("up", sizes.grouped_widths),
                        ("down", sizes.grouped_widths[::-1])):
        tiles = lm.grouped_tiles(m, k, n)
        require(tiles is not None, name,
                f"grouped_tiles({m}, {k}, {n}) tells the kernel nothing")
        rows, mats, seen = (
            jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)
            for scale, shape in ((1.0, (m, k)), (0.02, (held, k, n)),
                                 (1.0, (m, n))))
        rows, seen = (jnp.where(live, a, jnp.nan) for a in (rows, seen))
        require(_platform_of(mats) == platform, name,
                f"the matrices live on {_platform_of(mats)}")

        def results(product):
            def of(rows, mats, seen):
                y, back = jax.vjp(product, rows, mats)
                return (y, *back(seen))
            return jax.jit(of)(rows, mats, seen)
        told = results(lambda r, w: lm.told_grouped(r, w, group_sizes,
                                                    tiles))
        plain = results(lambda r, w: jax.lax.ragged_dot(r, w, group_sizes))
        require(not np.asarray(told[1])[n_live:].any(), name,
                f"{way} dx under tiles {tiles} is not zero past the last "
                "group")
        for what, a, b in zip(("product", "dx", "dW"), told, plain):
            a, b = (np.asarray(x) if what == "dW" else np.asarray(x)[:n_live]
                    for x in (a, b))
            gap, largest = float(np.abs(a - b).max()), float(np.abs(b).max())
            require(np.isfinite(a).all() and gap <= 1e-6 * largest, name,
                    f"{way} {what} under tiles {tiles}: off the untold "
                    f"kernel's by {gap} of {largest}")
            rec[f"{way}_{what}_gap"] = gap
        rec[f"{way}_tiles"] = tiles
    rec["wall_s"] = round(time.time() - started, 2)
    return rec


def _timed(calls: int, fn, *args):
    """(`fn(*args)`, the ms a call over `calls` calls after a warm
    one)."""
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t) / calls * 1e3


def phase_attention_core(sizes: Sizes, platform: str, *,
                         interpret: bool = False) -> dict:
    """The attention core as the kernel (`attention_kernel.attend`)
    against today's plain tiles (`lm_common._attend_tiles`) at the
    third language-model cell's shapes (`run_phases` once more at the
    sixth's, heads of 64 channels), a sliding and a full layer:
    the output and all three gradients of both, the largest gap of each
    as a share of the tiles' largest value (the two round the same
    operands to bfloat16 and sum in another order), and the ms a call
    of each, forward and `jax.grad` — the sweep PERF.md records.
    `interpret` runs the kernel in Pallas's interpreter (the CPU
    test)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafka_ps_tpu.models import attention_kernel
    from kafka_ps_tpu.models import lm_common as lm

    name, started = "attention_core", time.time()
    shape, block = sizes.core_shape, sizes.core_block
    require(attention_kernel.takes(shape, block), name,
            f"the kernel does not take {shape} in tiles of {block}")
    rng = np.random.default_rng(0)
    b, s, g, r, d = shape
    q, seen = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(2))
    q = q / np.sqrt(d)
    k, v = (jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32)
            for _ in range(2))
    require(_platform_of(q) == platform, name,
            f"the queries live on {_platform_of(q)}")

    timed = functools.partial(_timed, sizes.core_calls)
    rec = {"shape": list(shape), "block": block}
    for kind, window in (("window", sizes.core_window), ("full", None)):
        cores = {
            "kernel": lambda q, k, v: attention_kernel.attend(
                q, k, v, window, block, interpret),
            "tiles": lambda q, k, v: lm._attend_tiles(
                q, k, v, window=window, block=block)}
        got = {}
        for way, core in cores.items():
            out, rec[f"{kind}_{way}_forward_ms"] = timed(jax.jit(core),
                                                         q, k, v)
            grads, rec[f"{kind}_{way}_grad_ms"] = timed(jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(core(q, k, v) * seen),
                argnums=(0, 1, 2))), q, k, v)
            got[way] = (out, *grads)
        for what, a, b in zip(("out", "dq", "dk", "dv"), got["kernel"],
                              got["tiles"]):
            a, b = np.asarray(a), np.asarray(b)
            gap = float(np.abs(a - b).max() / np.abs(b).max())
            require(np.isfinite(a).all() and gap <= 0.02, name,
                    f"{kind} {what}: the kernel is off the tiles' by "
                    f"{gap} of their largest")
            rec[f"{kind}_{what}_gap"] = gap
    rec["wall_s"] = round(time.time() - started, 2)
    return rec


def phase_norm_rope(sizes: Sizes, platform: str, *,
                    interpret: bool = False) -> dict:
    """A head's norm and RoPE as the kernel
    (`norm_rope_kernel.norm_rope`) against the plain lines
    (`lm_common._norm_rope_plain`) on one row at the third and fifth
    language-model cells' shapes: q's heads with tables and the core's
    scale, k's heads with tables, and q's heads with the norm alone
    (the third cell's full layer) — the result, dx and the norm
    weight's gradient of both, each gap as a share of the plain lines'
    largest value (float32 on both sides: the sums' order differs), and
    the ms a call of each, forward and `jax.grad`.  The rows come as
    the projection writes them, `[1, S, heads x d]`, and the result
    leaves as the attention core reads it — q `[1, S, heads, d]`, k
    `[1, S, heads x d]` — so that neither side is charged a relayout
    the program does not make; a call under half a millisecond is the
    host's dispatch as much as the device's pass (PERF.md section 6, PR
    43).  `interpret` runs the kernel in Pallas's interpreter (the CPU
    test)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafka_ps_tpu.models import lm_common as lm
    from kafka_ps_tpu.models import norm_rope_kernel

    name, started = "norm_rope", time.time()
    s, d = sizes.norm_rope_positions, sizes.norm_rope_dim
    q_heads, k_heads = sizes.norm_rope_heads
    require(norm_rope_kernel.takes((1, s, q_heads, d)), name,
            f"the kernel does not take rows of {s} x {d}")
    rng = np.random.default_rng(0)
    eps = 1e-6
    tables = lm.rope_angles(s, 1.0 / (10000.0 ** (np.arange(
        0, d, 2, dtype=np.float32) / d)))
    w = jnp.asarray(1.0 + 0.1 * rng.standard_normal(d), jnp.float32)
    timed = functools.partial(_timed, sizes.core_calls)
    rec = {"positions": s, "dim": d}
    for case, heads, (cos, sin), scale in (
            ("q", q_heads, tables, 1.0 / np.sqrt(d)),
            ("k", k_heads, tables, 1.0),
            ("q_norm_only", q_heads, (None, None), 1.0 / np.sqrt(d))):
        flat = (1, s, heads * d)
        read = (1, s, heads, d) if norm_rope_kernel.by_head(heads) else flat
        x = jnp.asarray(rng.standard_normal(flat), jnp.float32)
        seen = jnp.asarray(rng.standard_normal(read), jnp.float32)
        require(_platform_of(x) == platform, name,
                f"the rows live on {_platform_of(x)}")
        ways = {
            "kernel": lambda x, w: norm_rope_kernel.norm_rope(
                x, w, cos, sin, eps, scale, interpret),
            "plain": lambda x, w: lm._norm_rope_plain(
                x, w, cos, sin, eps=eps, scale=scale)}
        got = {}
        for way, fn in ways.items():
            def laid(x, w, fn=fn):
                return fn(x.reshape(1, s, heads, d), w).reshape(read)
            out, rec[f"{case}_{way}_forward_ms"] = timed(jax.jit(laid), x, w)
            grads, rec[f"{case}_{way}_grad_ms"] = timed(jax.jit(jax.grad(
                lambda x, w: jnp.sum(laid(x, w) * seen), argnums=(0, 1))),
                                                     x, w)
            got[way] = (out, *grads)
        for what, a, b in zip(("out", "dx", "dw"), got["kernel"],
                              got["plain"]):
            a, b = np.asarray(a), np.asarray(b)
            gap = float(np.abs(a - b).max() / np.abs(b).max())
            require(np.isfinite(a).all() and gap <= 1e-4, name,
                    f"{case} {what}: the kernel is off the plain lines' by "
                    f"{gap} of their largest")
            rec[f"{case}_{what}_gap"] = gap
    rec["wall_s"] = round(time.time() - started, 2)
    return rec


def phase_ssd_scan(sizes: Sizes, platform: str, *,
                   interpret: bool = False) -> dict:
    """The chunked state-space scan as the kernels (`ssd_kernel.scan`)
    against today's einsums (`nemotron_h.chunks_scanned`), both through
    `nemotron_h.ssd_chunked`, at the seventh and the second
    language-model cells' shapes — one group read by 64 heads in chunks
    of 256, eight groups of eight heads in chunks of 128: y and the
    gradients of all six inputs of both, the largest gap of each as a
    share of the einsums' largest value (the two round the same
    operands to bfloat16; the sums' order differs), and the ms a call
    of each, forward and `jax.grad`.  `interpret` runs the kernels in
    Pallas's interpreter (the CPU test), where the einsums are float32
    and the gap is the rounding's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafka_ps_tpu.models import nemotron_h, ssd_kernel

    name, started = "ssd_scan", time.time()
    timed = functools.partial(_timed, sizes.core_calls)
    rec = {}
    for shape, groups, state, chunk in sizes.scan_shapes:
        case = f"g{groups}_q{chunk}"
        require(ssd_kernel.takes(shape, groups, state, chunk), name,
                f"the kernel does not take {shape} under {groups} groups "
                f"of {state} in chunks of {chunk}")
        rng = np.random.default_rng(0)
        b, s, h, _ = shape
        x, seen = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                   for _ in range(2))
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
        bm, cm = (jnp.asarray(rng.standard_normal((b, s, groups, state)),
                              jnp.float32) for _ in range(2))
        d = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
        require(_platform_of(x) == platform, name,
                f"the rows live on {_platform_of(x)}")

        def kernels(x, dt, cum, bm, cm, d):
            bb, nc, q, g, r = cum.shape
            packed = jnp.concatenate(
                [m.reshape(bb, nc * q, -1) for m in (x, bm, cm)], axis=-1)
            return ssd_kernel.scan(packed, dt, cum.reshape(bb, nc * q, g * r),
                                   d, g, bm.shape[-1], q, interpret)

        def einsums(x, dt, cum, bm, cm, d):
            return nemotron_h.chunks_scanned(
                (x * dt[..., None]).reshape(*cum.shape, -1), cum, bm,
                cm).reshape(shape) + d[:, None] * x
        got = {}
        for way, inside in (("kernel", kernels), ("einsums", einsums)):
            def scan(x, dt, a, bm, cm, d, inside=inside):
                # `ssd_chunked` with the way to run the chunks stated
                g, n = bm.shape[2:]
                nc, r = s // chunk, h // g
                cum = jnp.cumsum((dt * a).reshape(b, nc, chunk, g, r), axis=2)
                return inside(x, dt, cum, bm.reshape(b, nc, chunk, g, n),
                              cm.reshape(b, nc, chunk, g, n), d)
            out, rec[f"{case}_{way}_forward_ms"] = timed(
                jax.jit(scan), x, dt, a, bm, cm, d)
            grads, rec[f"{case}_{way}_grad_ms"] = timed(jax.jit(jax.grad(
                lambda *args: jnp.sum(scan(*args) * seen),
                argnums=(0, 1, 2, 3, 4, 5))), x, dt, a, bm, cm, d)
            got[way] = (out, *grads)
        for what, u, v in zip(("y", "dx", "ddt", "da", "db", "dc", "dd"),
                              got["kernel"], got["einsums"]):
            u, v = np.asarray(u), np.asarray(v)
            gap = float(np.abs(u - v).max() / np.abs(v).max())
            require(np.isfinite(u).all() and gap <= 0.05, name,
                    f"{case} {what}: the kernels are off the einsums' by "
                    f"{gap} of their largest")
            rec[f"{case}_{what}_gap"] = gap
    rec["wall_s"] = round(time.time() - started, 2)
    return rec


def phase_placement_products(sizes: Sizes, platform: str, *,
                             interpret: bool = False) -> dict:
    """The expert layer's two products with its 0/1 placement matrix as
    the kernels (`placement_kernel.multiply`) against `jnp.dot` with
    the matrix written out, as `lm_common.routed_experts` runs each
    where the kernels do not, over sorted rows of which under half are
    live, the dead ones NaN for the kernels.  In ONE pass (the default
    precision): placing `P · x`, one term a row, TO THE BIT, and its
    transpose `Pᵀ · x`, a token's sum in another order, to float32
    rounding.  In TWO (`HIGH` beside a 0/1 operand): the add-back `Pᵀ ·
    x` and its transpose — the compiler cuts the float32 operand's two
    bfloat16 pieces another way than the kernels' round-to-nearest (on
    the chip the two stand 6e-5 apart on values up to 5, PERF.md PR
    42), so each is set against the sum in float64 and the kernel has
    to stand no further from it than the product does.  And the ms a
    call of each.  `interpret` runs the kernels in Pallas's interpreter
    (the CPU test), where `jnp.dot` rounds nothing and is given the
    operand as the chip's precision leaves it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafka_ps_tpu.models import placement_kernel

    name, started = "placement_products", time.time()
    rows, tokens, hidden = sizes.placement_shape
    require(placement_kernel.takes(rows, tokens, hidden), name,
            f"the kernels do not take {sizes.placement_shape}")
    rng = np.random.default_rng(0)
    n_here = sum(sizes.placement_groups)
    tok = jnp.asarray(np.concatenate(
        [np.sort(rng.choice(tokens, size=n, replace=False))
         for n in sizes.placement_groups]
        + [rng.integers(0, tokens, size=rows - n_here)]), jnp.int32)
    live = (jnp.arange(rows) < n_here)[:, None]
    matrix = jnp.where(live, jax.nn.one_hot(tok, tokens,
                                            dtype=jnp.bfloat16), 0)
    plan = jax.jit(lambda tok: placement_kernel.plan(tok, n_here, tokens))(
        tok)
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((rows, hidden)), jnp.float32)
    require(_platform_of(h) == platform, name,
            f"the tokens live on {_platform_of(h)}")

    def as_the_chip_rounds(x, passes):
        if not interpret:
            return x
        return sum(piece.astype(jnp.float32)
                   for piece in placement_kernel._pieces_of(x)[:passes])

    timed = functools.partial(_timed, sizes.core_calls)

    rec = {"shape": list(sizes.placement_shape), "live_rows": n_here,
           "visited_share": float(plan.visit.sum()) / plan.visit.size}
    for what, back, passes in (("place", False, 1), ("place_t", True, 1),
                               ("add_back", True, 2),
                               ("add_back_t", False, 2)):
        precision = jax.lax.Precision.HIGH if passes == 2 else None
        x = y if back else h
        got, rec[f"{what}_kernel_ms"] = timed(jax.jit(
            lambda x: placement_kernel.multiply(x, plan, back, passes, None,
                                                interpret)),
            jnp.where(live, x, jnp.nan) if back else x)
        want, rec[f"{what}_product_ms"] = timed(jax.jit(
            lambda x: jnp.dot(matrix.T if back else matrix, x,
                              precision=precision,
                              preferred_element_type=jnp.float32)),
            as_the_chip_rounds(jnp.where(live, x, 0.0) if back else x,
                               passes))
        got, want = np.asarray(got), np.asarray(want)
        gap, largest = float(np.abs(got - want).max()), float(
            np.abs(want).max())
        require(np.isfinite(got).all(), name, f"{what}: not finite")
        rec[f"{what}_gap"] = gap
        if passes == 1:
            require(gap <= (1e-6 * largest if back else 0.0), name,
                    f"{what}: the kernel is off the product by {gap} of "
                    f"{largest}")
            continue
        at = np.asarray(tok)[:n_here]
        exact = np.zeros(got.shape)
        if back:
            np.add.at(exact, at, np.asarray(x, np.float64)[:n_here])
        else:
            exact[:n_here] = np.asarray(x, np.float64)[at]
        off, product_off = (float(np.abs(a - exact).max())
                            for a in (got, want))
        require(off <= product_off + 1e-7 * largest, name,
                f"{what}: the kernel stands {off} from the float64 sum, "
                f"the product {product_off}")
        rec[f"{what}_off_float64"] = [off, product_off]
    rec["wall_s"] = round(time.time() - started, 2)
    return rec


def phase_multichip(workdir: str, train: str, test: str, sizes: Sizes,
                    platform: str, device_count: int) -> dict:
    """The fused path over every chip of the host: `--fused -r` (1-D
    worker mesh) with one and two workers per chip, logreg and the wide
    MLP, and `--fused --param_shards 2` (workers x params mesh).  The
    worker slabs must actually be sharded over all the devices and the
    loss stay finite; eight rounds are too few to ask the wide MLP for
    quality."""
    from kafka_ps_tpu.parallel import bsp, range_sharded

    out = {}
    runs = [(task, w, "-r") for task in ("logreg", "mlp")
            for w in (device_count, 2 * device_count)]
    runs += [(task, device_count, "--param_shards")
             for task in ("logreg", "mlp")]
    for task, workers, mode in runs:
        name = f"multichip_{task}_w{workers}_{mode.strip('-')}"
        argv = _common_flags(train, test, sizes) + [
            "--fused", "--task", task,
            "--hidden_dim", str(sizes.fused_hidden),
            "--num_workers", str(workers), "--eval_every", "4",
            "--max_iterations", str(workers * sizes.multichip_rounds)]
        argv += [mode, "2"] if mode == "--param_shards" else [mode]

        placed = []                   # device sets of the sharded slabs
        module = range_sharded if mode == "--param_shards" else bsp
        real = module.shard_worker_batches

        def observe(mesh, x, y, mask, real=real):
            arrays = real(mesh, x, y, mask)
            placed.append({len(a.sharding.device_set) for a in arrays})
            return arrays

        module.shard_worker_batches = observe
        phase_dir = os.path.join(workdir, name)
        try:
            app, times = run_cli(name, phase_dir, argv)
        finally:
            module.shard_worker_batches = real
        owed = [c for c in range(1, sizes.multichip_rounds + 1)
                if c % 4 == 0]
        # --param_shards hands the server a host copy of theta after
        # every step (range_sharded.unshard_theta) — recorded, not
        # changed here
        rec = check_run(name, phase_dir, app, times, consistency=0,
                        owed_clocks=owed, platform=platform,
                        theta_on_device=mode == "-r", learns=False)
        require(placed and all(s == {device_count} for s in placed), name,
                f"worker slabs span {placed}, not {device_count} devices")
        rec["slab_devices"] = device_count
        if mode == "-r":
            require(len(app.server.theta.devices()) == device_count, name,
                    "replicated theta does not span every device")
        out[name] = rec
    return out


# -- entry point -------------------------------------------------------------


def run_phases(sizes: Sizes, platform: str, device_count: int,
               workdir: str) -> dict:
    """Every phase in order; the first failed check raises."""
    train, test = make_data(workdir, sizes)
    phases = {}
    for c in (0, 2, -1):
        phases[f"per_node_c{c}"] = phase_per_node(
            workdir, train, test, sizes, platform, c)
    for eval_every in (1, 8):
        phases[f"fused_mlp{sizes.fused_hidden}_eval{eval_every}"] = \
            phase_fused(workdir, train, test, sizes, platform, eval_every)
    phases["grouped_products"] = phase_grouped_products(sizes, platform)
    phases["attention_core"] = phase_attention_core(sizes, platform)
    phases["attention_core_halves"] = phase_attention_core(
        dataclasses.replace(sizes, core_shape=sizes.core_shape_halves),
        platform)
    phases["norm_rope"] = phase_norm_rope(sizes, platform)
    phases["placement_products"] = phase_placement_products(sizes, platform)
    phases["ssd_scan"] = phase_ssd_scan(sizes, platform)
    if device_count > 1:
        phases.update(phase_multichip(workdir, train, test, sizes,
                                      platform, device_count))
    return phases


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def verdict_line(found: dict) -> str:
    """The last line of stdout: `ok` and the device as JAX reports it,
    and no other key — the report goes on the line before."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(found["platform"]),
                   "kind": str(found["kind"]),
                   "count": int(found["count"])}})


def main() -> int:
    from kafka_ps_tpu import native
    from kafka_ps_tpu.utils import device

    device.configure_compile_cache()
    found = device.device_summary()       # first backend use
    if found["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform={found['platform']!r} "
              f"({found['kind']} x{found['count']}), not a TPU — nothing "
              "was run", file=sys.stderr)
        return 2
    print(device.startup_line(), flush=True)
    cache_dir = device.compile_cache_dir()
    cache_before = _cache_entries(cache_dir)
    print(f"[ingest] csv parser = {native.status()}", flush=True)

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        phases = run_phases(Sizes(), found["platform"], found["count"],
                            workdir)
    print(json.dumps({
        "versions": {k: found[k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache": {"dir": cache_dir,
                          "entries_at_start": cache_before,
                          "entries_at_end": _cache_entries(cache_dir)},
        "csv_parser": native.status(),
        "wall_s": round(time.time() - t0, 1),
        "phases": phases,
        "claim": None,
    }), flush=True)
    print(verdict_line(found), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
