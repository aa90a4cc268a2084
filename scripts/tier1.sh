#!/bin/bash
# Tier-1 verify: the exact command the driver runs (ROADMAP.md).
# Passes iff the suite exits 0 within the timeout; DOTS_PASSED echoes
# the progress-dot count so regressions against the recorded floor are
# visible at a glance.
#
# `scripts/tier1.sh --gang` runs the gang-dispatch smoke leg instead: a
# tiny serial run with coalescing on vs off, asserting identical final
# theta (bitwise) and a strictly lower device-dispatch count
# (docs/GANG_DISPATCH.md).
#
# `scripts/tier1.sh --serve` runs the serving-plane smoke leg: train a
# tiny model with serving enabled, predict in-process AND over the
# socket (PredictClient), and assert the staleness rejection path fires
# (docs/SERVING.md).
#
# `scripts/tier1.sh --compress` runs the compressed-transport smoke leg:
# socket mode end-to-end under --compress int8 — HELLO codec
# negotiation, batched T_DATA_BATCH ingest, error-feedback training to
# completion, and strictly fewer bytes on the wire than the
# uncompressed arm (docs/COMPRESSION.md).
#
# `scripts/tier1.sh --perf` runs the incremental-slab smoke leg: tiny
# serial runs asserting the incremental device slab trains to a
# BITWISE-identical theta vs whole-slab re-upload (f32, all three
# consistency models) and that bf16 slab storage trains end-to-end
# (docs/PERFORMANCE.md).
#
# `scripts/tier1.sh --shard` runs the range-sharding smoke leg: a
# socket-bridged fleet of 2 shard-server processes + 1 worker process
# (2 logical workers), SIGKILL one shard mid-run, restart it, and prove
# bitwise recovery by replaying each shard's per-shard durable-log
# gradients partition through a fresh ServerNode and comparing against
# the shard's final checkpoint theta bytes (docs/SHARDING.md).
#
# `scripts/tier1.sh --agg` runs the aggregation-tier smoke leg
# (docs/AGGREGATION.md): a socket fleet of 1 server (--bsp-order) + 2
# aggregator relays x 2 worker processes (4 logical workers), SIGKILL
# one relay mid-run, restart it (workers resend their caches through
# it), and assert final theta AND the server eval CSV (timestamps
# stripped) bitwise-equal to a direct no-relay fleet with the same
# flags (AGG_SMOKE_OK).
#
# `scripts/tier1.sh --wire` runs the wire-engine smoke leg
# (docs/WIRE.md): a socket fleet of 1 server (--bsp-order) + 1
# aggregator relay + 2 member worker processes (4 logical workers) runs
# twice — frame coalescing on (default) vs --no-wire-coalesce.  In EACH
# arm one member worker process is SIGKILL'd mid-run and restarted
# (durable worker state + relay weights stash + the server's READY
# liveness reissue recover the stalled round), and final theta AND the
# server eval CSV (timestamps stripped) must be bitwise-equal across
# the coalescing lever (WIRE_SMOKE_OK).
#
# `scripts/tier1.sh --eval` runs the async-eval smoke leg
# (docs/EVALUATION.md "Async evaluation"): a socket fleet of 1 server
# (--bsp-order) + 1 aggregator relay + 2 member worker processes (4
# logical workers) at eval_every=1 runs twice — the async coalescing
# eval engine on (default) vs --no-eval-async (fused apply+eval).  In
# EACH arm one member worker process is SIGKILL'd mid-run and
# restarted (pending evals in the engine queue hold no durable state —
# recovery is entirely the existing worker-state + relay-stash + READY
# reissue machinery), and final theta AND the server eval CSV
# (timestamps stripped) must be bitwise-equal across the eval lever
# (EVAL_SMOKE_OK).
#
# `scripts/tier1.sh --load` runs the serving-load smoke leg: a child
# training process serving over a socket (--serve --serve_port
# --serve-queue) driven by THIS process's load generator — zero
# deadline violations at low rate, >=1 explicit typed shed under a
# flash crowd, an offered-rate Poisson arm (open loop, latency from
# scheduled arrival) answering within the smoke SLO with zero errors,
# and the trained theta bitwise-identical to a no-load run
# (docs/SERVING.md, "Operating at load").  A final in-process arm
# proves the adaptive dispatcher settles on the batching BYPASS at
# low concurrency with p99 no worse than a hand-tuned unbatched
# engine (docs/SERVING.md, "Dispatch economics").
#
# `scripts/tier1.sh --tier` runs the tiered-parameter-store smoke leg
# (docs/TIERING.md): train through the public CLI with the hot tier
# capped at ~1/13 of the parameter bytes (+ a warm cap, so most pages
# live as commit-log records), for all three consistency models,
# asserting final theta AND the eval CSV (timestamp column stripped)
# bitwise-equal to the uncapped run; then SIGKILL a capped durable run
# mid-training, restart it, and prove bitwise recovery by replaying the
# gradients partition through a fresh fully-resident ServerNode against
# the restarted run's final checkpoint — whose recorded residency must
# still hold cold pages (faulted in on demand, never pre-materialized).
#
# `scripts/tier1.sh --analyze` runs the static-analysis leg: pscheck
# (docs/ANALYSIS.md) over the package — fails on ANY unsuppressed
# finding — plus ruff (pyproject.toml, rule sets E/F/B/PLE) when the
# binary is installed.
#
# `scripts/tier1.sh --obs` runs the observability smoke leg in two
# phases (docs/OBSERVABILITY.md): (1) one short socket-bridged run PER
# consistency model with tracing and metrics on (tracer pid pairs
# standing in for the `--listen --trace` / `--connect --trace`
# processes), asserting the six-trace merge contains >= 1 cross-process
# flow, the Prometheus dump parses with the staleness histogram
# families populated, and `python -m kafka_ps_tpu.telemetry critpath`
# exits 0 over the merged trace naming a dominant segment per model —
# BSP's must be gate_wait (OBS_CRITPATH_OK); (2) a subprocess fleet
# (2 shard servers + 1 worker, all with --flight-dir) where shard 1 is
# SIGKILLed mid-run — the survivors' flight dumps must exist, the
# killed shard's must not, and `python -m kafka_ps_tpu.telemetry
# postmortem` must exit 0 naming the dead shard and its last
# acknowledged weights send (POSTMORTEM_OK).
#
# `scripts/tier1.sh --drift` runs the model-health smoke leg
# (docs/OBSERVABILITY.md, "Model health & drift"): a socket-bridged
# server + worker pair (2 logical workers) trains with --model-health
# on a stream whose second half is label-flipped and feature-shifted —
# the server's drift plane must latch DRIFT (observed live over
# /modelz), the armed drift watchdog must ship a flight dump carrying
# the drift.trip event, and the wall-clock-stamped drift CSV must
# record the trip; a clean serial control run with the same flags must
# finish with ZERO trip rows (DRIFT_SMOKE_OK).
#
# Every leg pins JAX_PLATFORMS=cpu: these are correctness checks on the
# CPU.  The chip check is `python chip_smoke.py`, run through the chip
# tool (README "Running on the chip").
set -o pipefail

if [[ "${1:-}" == "--analyze" ]]; then
    # 1) drive the real threaded subsystems under an isolated recorder
    #    and dump the runtime lock-order edges the static graph is
    #    diffed against (the test_migrated_production_locks driver)
    EDGES=$(mktemp /tmp/kps_lock_edges.XXXXXX.json)
    trap 'rm -f "$EDGES"' EXIT
    timeout -k 10 120 env JAX_PLATFORMS=cpu python - "$EDGES" <<'EOF' || exit 1
import json
import sys
import tempfile
import threading

from kafka_ps_tpu.analysis import lockgraph
from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.serving.snapshot import SnapshotRegistry
from kafka_ps_tpu.utils.asynclog import DeferredSink
from kafka_ps_tpu.utils.config import BufferConfig
from kafka_ps_tpu.utils.csvlog import CsvLogSink

with tempfile.TemporaryDirectory() as td:
    with lockgraph.isolated() as g:
        fab = fabric_mod.Fabric()
        buf = SlidingBuffer(4, BufferConfig(min_size=16, max_size=64))
        reg = SnapshotRegistry()
        csv = CsvLogSink(td + "/t.csv", header="a;b")
        sink = DeferredSink(csv, drain_interval=0.01)

        def producer():
            for i in range(50):
                fab.send(fabric_mod.WEIGHTS_TOPIC, 0, i)
                buf.add([float(i)] * 4, i % 2)
                reg.publish([float(i)], vector_clock=i)
                sink(f"{i};x")

        def consumer():
            for _ in range(50):
                fab.poll_blocking(fabric_mod.WEIGHTS_TOPIC, 0, timeout=2)
                buf.snapshot()
                _ = reg.latest

        ts = [threading.Thread(target=f) for f in (producer, consumer)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        sink.close()
        csv.close()
        cycles = g.cycles()
        edges = g.export_edges()
if cycles:
    print(f"runtime lock-order cycle: {cycles}", file=sys.stderr)
    sys.exit(1)
with open(sys.argv[1], "w", encoding="utf-8") as f:
    json.dump({"edges": edges}, f)
print(f"runtime lock edges recorded: {len(edges)}")
EOF
    # 2) psverify: pscheck + threadck + lockflow + wireck + PS107 over
    #    the package, diffed against the runtime edges; hard-fails on
    #    ANY unsuppressed finding
    REPORT=$(mktemp /tmp/kps_psverify.XXXXXX.json)
    trap 'rm -f "$EDGES" "$REPORT"' EXIT
    python -m kafka_ps_tpu.analysis kafka_ps_tpu/ --json \
        --lock-coverage "$EDGES" > "$REPORT"
    STATUS=$?
    python - "$REPORT" "$STATUS" <<'EOF' || exit 1
import json
import sys

from kafka_ps_tpu.analysis import psverify

data = json.load(open(sys.argv[1], encoding="utf-8"))
uns = data["counts"]["unsuppressed"]
sup = data["counts"]["suppressed"]
if uns or int(sys.argv[2]) != 0:
    for f in data["findings"]:
        if not f["suppressed"]:
            print(f"{f['path']}:{f['line']}: {f['rule']} {f['message']}")
    print(f"psverify: {uns} unsuppressed findings", file=sys.stderr)
    sys.exit(1)
cov = data.get("lock_coverage") or {}
print(f"lock coverage: {cov.get('common', 0)} edges exercised at "
      f"runtime, {len(cov.get('static_only', []))} static-only, "
      f"{len(cov.get('runtime_only', []))} runtime-only")
for e in cov.get("runtime_only", []):
    print(f"  runtime-only {e['src']} -> {e['dst']} @ {e.get('site', '?')}")
print(f"ANALYZE_OK rules={len(psverify.RULES)} findings={uns} "
      f"suppressed={sup}")
EOF
    if command -v ruff >/dev/null 2>&1; then
        ruff check . || exit 1
    else
        echo "ruff not installed; skipped (psverify gate ran)"
    fi
    exit 0
fi

if [[ "${1:-}" == "--load" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

# two processes: a child training run serving over a socket, and THIS
# process driving it with the load generator.  The quiet arm repeats
# the identical (serial, deterministic) training run with serving off:
# read load must never perturb training — theta bitwise-identical.
root = tempfile.mkdtemp(prefix="kps-load-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(256, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:200], y[:200])),
                       (test, (x[200:], y[200:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# sized so training ALWAYS outlasts the ~10 s load window (warmup +
# low + flash crowd + poisson): ~1500 unloaded iters/s on a fast box
# -> ~11 s floor even before the load slows the trainer down (~450
# iters/s on the reference 1-core box -> ~36 s); liveness asserts
# below turn a too-fast trainer into a clear failure instead of an
# error storm
MAX_IT = 16000
common = ["-training", train, "-test", test, "--num_workers", "2",
          "--num_features", "8", "--num_classes", "2", "-min", "8",
          "-max", "32", "-p", "2", "-c", "0", "--mode", "serial",
          "--eval_every", "1000000", "--max_iterations", str(MAX_IT),
          "--checkpoint_every", "50"]

def arm(serve):
    ckpt = os.path.join(root, ("serve" if serve else "quiet") + ".npz")
    cmd = [sys.executable, "-m", "kafka_ps_tpu.cli.run", *common,
           "--checkpoint", ckpt]
    if serve:
        cmd += ["--serve", "--serve_port", "0", "--serve-queue", "4"]
    proc = subprocess.Popen(cmd, env=env, cwd=root, text=True,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE)
    port = None
    if serve:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stderr.readline()
            if not line:
                break
            m = re.search(r"serving on port (\d+)", line)
            if m:
                port = int(m.group(1))
                break
        if not port:
            proc.kill()
            raise SystemExit("child never announced its serving port")
    return proc, port, ckpt

from kafka_ps_tpu.serving import loadgen

proc, port, serve_ckpt = arm(serve=True)
target = loadgen.SocketTarget("127.0.0.1", port)
try:
    # one connection to pay the jit warmup before anything is measured
    loadgen.run_closed_loop(target, 8, concurrency=1, duration_s=1.0)
    # low rate: every request answered within the smoke SLO (500 ms is
    # generous on purpose — one core shared with training; observed
    # p99 is 30-100 ms), nothing shed, nothing errored
    low = loadgen.run_closed_loop(target, 8, concurrency=2,
                                  duration_s=3.0)
    # flash crowd: 32 in-flight against a 4-deep admission queue must
    # shed EXPLICITLY (typed PREDICT_OVERLOADED), never time out
    over = loadgen.run_closed_loop(target, 8, concurrency=32,
                                   duration_s=3.0)
    # offered-rate arm: memoryless Poisson arrivals at a modest rate —
    # the steady-state traffic model (docs/SERVING.md quotes its SLO
    # against this shape).  Latency counts from the SCHEDULED
    # arrival (no coordinated omission), so the smoke SLO here also
    # covers queueing behind the shared training core.  Sheds are
    # legal (bursts can momentarily fill the 4-deep queue); errors are
    # not — every rejection must be typed.
    pois = loadgen.run_open_loop(target, 8, rate_qps=40.0,
                                 duration_s=2.5, concurrency=8,
                                 arrivals="poisson")
    # the whole point is load DURING training: if the trainer already
    # exited, the run above measured a dead socket, not admission
    assert proc.poll() is None, \
        "trainer finished before the load window (raise MAX_IT)"
finally:
    target.close()
rc = proc.wait(timeout=240)
err = proc.stderr.read()
assert rc == 0, f"serving arm rc={rc}\n{err[-4000:]}"
assert low.meets(500.0), f"low-rate SLO violated: {low.as_dict()}"
assert over.shed >= 1, f"flash crowd never shed: {over.as_dict()}"
assert over.errors == 0, f"sheds must be typed: {over.as_dict()}"
assert pois.errors == 0, f"poisson arm errored: {pois.as_dict()}"
assert pois.ok > 0, f"poisson arm answered nothing: {pois.as_dict()}"
assert pois.p99_ms <= 500.0, f"poisson SLO violated: {pois.as_dict()}"

quiet, _, quiet_ckpt = arm(serve=False)
rc = quiet.wait(timeout=240)
assert rc == 0, f"quiet arm rc={rc}\n{quiet.stderr.read()[-4000:]}"
zs, zq = np.load(serve_ckpt), np.load(quiet_ckpt)
assert int(zs["iterations"]) >= MAX_IT <= int(zq["iterations"])
ts = np.asarray(zs["theta"], np.float32)
tq = np.asarray(zq["theta"], np.float32)
assert ts.tobytes() == tq.tobytes(), \
    "read load perturbed training theta"

# -- adaptive-dispatch arm (ROADMAP item 4): at low concurrency the
# auto engine must SETTLE ON THE BYPASS PATH — no queue, no window
# wait — and its accepted p99 must be no worse than a hand-tuned
# unbatched engine (max_batch=1, deadline 0), modulo scheduler noise.
# Runs after both training children exit so the box is quiet.
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.serving.engine import PredictionEngine
from kafka_ps_tpu.serving.snapshot import SnapshotRegistry
from kafka_ps_tpu.utils.config import ModelConfig

def _engine(**kw):
    cfg = ModelConfig(num_features=8, num_classes=2)
    task = get_task("logreg", cfg)
    reg = SnapshotRegistry()
    reg.publish(np.full(task.num_params, 0.5, np.float32), vector_clock=1)
    eng = PredictionEngine(task, reg, **kw)
    eng.warmup()
    return eng

auto_eng = _engine()                               # adaptive (default)
plain_eng = _engine(max_batch=1, deadline_s=0.0)   # hand-tuned unbatched
try:
    auto_res = loadgen.run_closed_loop(loadgen.EngineTarget(auto_eng), 8,
                                       concurrency=1, duration_s=2.0)
    auto_stats = auto_eng.stats()
    plain_res = loadgen.run_closed_loop(loadgen.EngineTarget(plain_eng), 8,
                                        concurrency=1, duration_s=2.0)
finally:
    auto_eng.close()
    plain_eng.close()
assert auto_stats["mode"] == "bypass", \
    f"auto engine never settled on bypass at conc 1: {auto_stats}"
assert auto_stats["bypasses"] > 0, auto_stats
assert auto_res.errors == auto_res.shed == 0, auto_res.as_dict()
# the whole point of adaptive dispatch: an idle-occupancy caller must
# not pay the micro-batching tax.  Same box, same inline path length —
# 1.5x multiplicative + 0.3 ms additive slack absorbs scheduler noise.
assert auto_res.p99_ms <= 1.5 * plain_res.p99_ms + 0.3, (
    f"bypass p99 {auto_res.p99_ms:.3f} ms worse than unbatched "
    f"{plain_res.p99_ms:.3f} ms")

print(f"LOAD_SMOKE_OK low_p99_ms={low.p99_ms} low_ok={low.ok} "
      f"sheds={over.shed} shed_rate={over.shed_rate:.3f} "
      f"poisson_p99_ms={pois.p99_ms} poisson_ok={pois.ok} "
      f"poisson_shed={pois.shed} "
      f"bypass_p99_ms={auto_res.p99_ms:.3f} "
      f"unbatched_p99_ms={plain_res.p99_ms:.3f} "
      f"dispatch_mode={auto_stats['mode']} "
      f"theta=bitwise-identical iters={MAX_IT}")
EOF
    exit $?
fi

if [[ "${1:-}" == "--shard" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

# a real split-deployment fleet: 2 shard-server subprocesses + 1 worker
# subprocess hosting 2 logical workers, driven through the public CLI
root = tempfile.mkdtemp(prefix="kps-shard-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(256, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:200], y[:200])),
                       (test, (x[200:], y[200:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

p0, p1 = free_port(), free_port()
env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
MAX_IT = 400
common = ["--num_workers", "2", "--num_features", "8",
          "--num_classes", "2", "--max_iterations", str(MAX_IT)]
logdir, ckpt = os.path.join(root, "log"), os.path.join(root, "ckpt.npz")

def shard(i, port):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
         "--listen", str(port), "--shards", "2", "--shard-id", str(i),
         "-training", train, "-test", test, "-p", "5", "-c", "0",
         "--durable-log", logdir, "--checkpoint", ckpt,
         "--checkpoint_every", "50", *common],
        env=env, cwd=root, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

s0, s1 = shard(0, p0), shard(1, p1)
w = subprocess.Popen(
    [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
     "--connect", f"127.0.0.1:{p0},127.0.0.1:{p1}",
     "--worker_ids", "0,1", "-test", test,
     "-min", "8", "-max", "32", *common],
    env=env, cwd=root, stderr=subprocess.PIPE,
    stdout=subprocess.DEVNULL, text=True)

# wait until shard 1 has logged a prefix of gradient slices, then
# SIGKILL it mid-run
grad_glob = os.path.join(logdir, "shard1of2", "gradients", "*", "*.log")
deadline = time.monotonic() + 120
while time.monotonic() < deadline:
    segs = glob.glob(grad_glob)
    if segs and sum(os.path.getsize(s) for s in segs) > 8000:
        break
    if s1.poll() is not None:
        print(s1.stderr.read(), file=sys.stderr)
        raise SystemExit("shard1 exited before the kill point")
    time.sleep(0.1)
else:
    raise SystemExit("shard1 gradient log never grew")
os.kill(s1.pid, signal.SIGKILL)
s1.wait()
time.sleep(0.5)
s1b = shard(1, p1)       # workers + shard0 kept running throughout

procs = {"shard0": s0, "shard1-restarted": s1b, "worker": w}
deadline = time.monotonic() + 300
while time.monotonic() < deadline:
    if all(p.poll() is not None for p in procs.values()):
        break
    time.sleep(0.5)
else:
    for p in procs.values():
        if p.poll() is None:
            p.kill()
    for name, p in procs.items():
        print(f"== {name} rc={p.poll()}\n{p.stderr.read()[-4000:]}",
              file=sys.stderr)
    raise SystemExit("fleet did not finish in time")
bad = []
for name, p in procs.items():
    err = p.stderr.read()
    if p.returncode != 0:
        print(f"== {name} rc={p.returncode}\n{err[-4000:]}",
              file=sys.stderr)
        bad.append(name)
assert not bad, f"{bad} failed"

# bitwise proof: replay each shard's FULL gradients partition (offset 0
# up to the final checkpoint's committed offset) through a fresh
# ServerNode — log order is processing order across both incarnations,
# and the tracker dedups redelivered slices identically — then compare
# against the shard's final checkpoint theta bytes.
from kafka_ps_tpu.log import LogConfig
from kafka_ps_tpu.log.manager import LogManager
from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime import serde
from kafka_ps_tpu.runtime.server import ServerNode
from kafka_ps_tpu.runtime.sharding import ShardPlan
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig,
                                       StreamConfig)

cfg = PSConfig(num_workers=2, consistency_model=0, task="logreg",
               model=ModelConfig(num_features=8, num_classes=2),
               buffer=BufferConfig(min_size=8, max_size=32),
               stream=StreamConfig(time_per_event_ms=5),
               use_gang=False)
plan = ShardPlan(get_task(cfg.task, cfg.model).num_params, 2)
replayed = []
for i in range(2):
    z = np.load(os.path.join(root, f"ckpt.npz.shard{i}of2.npz"))
    end = json.loads(str(z["log_offsets"]))["gradients/0"]
    srv = ServerNode(cfg, fabric_mod.Fabric(), None, None, None,
                     key_range=plan.ranges[i], shard_id=i, num_shards=2)
    srv.start_training_loop()
    mgr = LogManager(os.path.join(logdir, f"shard{i}of2"), LogConfig())
    n = 0
    for off, payload in mgr.get("gradients", 0).read_from(0):
        if off >= end:
            break
        srv.process(serde.from_bytes(payload))
        n += 1
    mgr.close()
    replay = np.asarray(srv.theta, dtype=np.float32)
    want = np.asarray(z["theta"], dtype=np.float32)
    assert srv.iterations >= MAX_IT, (i, srv.iterations)
    assert replay.tobytes() == want.tobytes(), \
        f"shard {i}: replayed theta diverged from final checkpoint"
    replayed.append(n)
print(f"SHARD_SMOKE_OK shards=2 replayed={replayed} "
      f"iters={MAX_IT} bitwise=recovered")
EOF
    exit $?
fi

if [[ "${1:-}" == "--agg" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

# the aggregation-tier A/B (docs/AGGREGATION.md): the SAME training
# run through two topologies —
#   direct:      server <-- 2 worker processes (4 logical workers)
#   aggregated:  server <-- 2 relay processes <-- 2 worker processes
# with deterministic knobs (--bsp-order on the server so BSP rounds
# apply in worker-id order; --ready-rows so training starts only after
# each worker ingested its FULL stream partition), final theta and the
# server eval CSV must match bitwise.  One relay is SIGKILL'd mid-run
# and restarted: the workers' redelivery caches resend through it and
# the server gate deduplicates, so the kill must not show up in either
# artifact.
root = tempfile.mkdtemp(prefix="kps-agg-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(192, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:128], y[:128])),
                       (test, (x[128:], y[128:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# 2000 rounds keep the training window open for seconds (the eval CSV
# is drained asynchronously, so a 200-round run can be over before any
# on-disk row count triggers the mid-run kill — the restarted relay
# then dials a server that already exited)
MAX_IT = 2000
# 128 rows / 4 workers = 32 per partition = the buffer cap, so
# --ready-rows 32 means "my whole partition arrived" — ingestion fully
# precedes training in both arms, which removes stream timing from the
# comparison
READY = 32
common = ["--num_workers", "4", "--num_features", "8",
          "--num_classes", "2", "--max_iterations", str(MAX_IT)]

def server_proc(tag, port):
    cwd = os.path.join(root, tag)
    os.makedirs(cwd, exist_ok=True)
    p = subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
         "--listen", str(port), "--bsp-order", "-c", "0",
         "-training", train, "-test", test, "-p", "1", "--logging",
         "--checkpoint", os.path.join(cwd, "ckpt.npz"), *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)
    return p, cwd

def worker_proc(cwd, wids, flag, addr):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
         flag, addr, "--worker_ids", wids, "-test", test,
         "-min", "8", "-max", "32", "--ready-rows", str(READY),
         *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def agg_proc(cwd, agg_id, wids, sport, aport):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.agg_runner",
         "--connect", f"127.0.0.1:{sport}", "--listen", str(aport),
         "--agg-id", str(agg_id), "--worker_ids", wids, *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def finish(procs, deadline_s=240):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in procs.values()):
            break
        time.sleep(0.25)
    else:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for name, p in procs.items():
            print(f"== {name} rc={p.poll()}\n{p.stderr.read()[-4000:]}",
                  file=sys.stderr)
        raise SystemExit("fleet did not finish in time")
    bad = []
    for name, p in procs.items():
        err = p.stderr.read()
        if p.returncode != 0:
            print(f"== {name} rc={p.returncode}\n{err[-4000:]}",
                  file=sys.stderr)
            bad.append(name)
    assert not bad, f"{bad} failed"

def csv_rows(cwd):
    # column 0 is the wall-clock timestamp — the only legal difference
    with open(os.path.join(cwd, "logs-server.csv")) as fh:
        return [";".join(ln.split(";")[1:]) for ln in fh.read().splitlines()]

# -- arm 1: direct (no relays) --------------------------------------------
pd = free_port()
sd, dcwd = server_proc("direct", pd)
finish({"server": sd,
        "worker01": worker_proc(dcwd, "0,1", "--connect",
                                f"127.0.0.1:{pd}"),
        "worker23": worker_proc(dcwd, "2,3", "--connect",
                                f"127.0.0.1:{pd}")})

# -- arm 2: aggregated, with a relay SIGKILL + restart mid-run ------------
pa, a0, a1 = free_port(), free_port(), free_port()
sa, acwd = server_proc("agg", pa)
r0 = agg_proc(acwd, 0, "0,1", pa, a0)
r1 = agg_proc(acwd, 1, "2,3", pa, a1)
w01 = worker_proc(acwd, "0,1", "--aggregate", f"127.0.0.1:{a0}")
w23 = worker_proc(acwd, "2,3", "--aggregate", f"127.0.0.1:{a1}")

# kill relay 0 once the server's eval CSV shows real training progress
csv_path = os.path.join(acwd, "logs-server.csv")
deadline = time.monotonic() + 120
while time.monotonic() < deadline:
    try:
        with open(csv_path) as fh:
            n = sum(1 for _ in fh) - 1
    except OSError:
        n = 0
    if n >= 16:
        break
    for name, p in (("server", sa), ("relay0", r0)):
        if p.poll() is not None:
            print(p.stderr.read(), file=sys.stderr)
            raise SystemExit(f"{name} exited before the kill point")
    time.sleep(0.05)
else:
    raise SystemExit("aggregated server never made progress")
os.kill(r0.pid, signal.SIGKILL)
r0.wait()
time.sleep(0.5)
# same listen port: the members' supervisor reconnects there and
# resends the whole redelivery cache (the relay itself held no state)
r0b = agg_proc(acwd, 0, "0,1", pa, a0)
finish({"server": sa, "relay0-restarted": r0b, "relay1": r1,
        "worker01": w01, "worker23": w23})

# -- the bitwise pin -------------------------------------------------------
zd = np.load(os.path.join(dcwd, "ckpt.npz"))
za = np.load(os.path.join(acwd, "ckpt.npz"))
assert int(zd["iterations"]) >= MAX_IT <= int(za["iterations"])
assert za["theta"].tobytes() == zd["theta"].tobytes(), \
    "aggregated theta diverged from the direct run"
assert csv_rows(acwd) == csv_rows(dcwd) != [], \
    "aggregated eval CSV diverged from the direct run"
print(f"AGG_SMOKE_OK relays=2 workers=4 iters={MAX_IT} "
      f"kill=relay0+restart theta=bitwise csv=bitwise")
EOF
    exit $?
fi

if [[ "${1:-}" == "--wire" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

# the wire-engine A/B (docs/WIRE.md): the SAME training run — server
# <-- 1 relay <-- 2 member worker processes (4 logical workers) — once
# with frame coalescing on (the default) and once with
# --no-wire-coalesce, deterministic knobs as in the --agg leg
# (--bsp-order, --ready-rows = full partition).  In EACH arm one member
# worker process is SIGKILL'd mid-run and restarted: its durable state
# (--checkpoint/--state_every, cli/socket_mode._run_worker_sharded)
# restores the frozen ingestion window, the relay redelivers its
# stashed weights on the re-HELLO, and the server's READY liveness
# reissue re-sends the in-flight round assignment — so the stalled BSP
# gate completes with a bit-identical applied-gradient sequence no
# matter when the kill landed.  Final theta and the server eval CSV
# must match bitwise across the coalescing lever.
root = tempfile.mkdtemp(prefix="kps-wire-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(192, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:128], y[:128])),
                       (test, (x[128:], y[128:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# 2000 rounds keep the training window open for seconds (the eval CSV
# is drained asynchronously, so a 200-round run is over before any
# on-disk row count can trigger a mid-run kill)
MAX_IT = 2000
READY = 32          # 128 rows / 4 workers: full-partition gating
common = ["--num_workers", "4", "--num_features", "8",
          "--num_classes", "2", "--max_iterations", str(MAX_IT)]

def server_proc(cwd, port, wire):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
         "--listen", str(port), "--bsp-order", "-c", "0",
         "-training", train, "-test", test, "-p", "1", "--logging",
         "--checkpoint", os.path.join(cwd, "ckpt.npz"), wire, *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def worker_proc(cwd, wids, aport, wire):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
         "--aggregate", f"127.0.0.1:{aport}", "--worker_ids", wids,
         "-test", test, "-min", "8", "-max", "32",
         "--ready-rows", str(READY),
         "--checkpoint", os.path.join(cwd, "job.npz"),
         "--state_every", "0.2", wire, *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def agg_proc(cwd, sport, aport, wire):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.agg_runner",
         "--connect", f"127.0.0.1:{sport}", "--listen", str(aport),
         "--agg-id", "0", "--worker_ids", "0,1,2,3", wire, *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def finish(procs, deadline_s=240):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in procs.values()):
            break
        time.sleep(0.25)
    else:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for name, p in procs.items():
            print(f"== {name} rc={p.poll()}\n{p.stderr.read()[-4000:]}",
                  file=sys.stderr)
        raise SystemExit("fleet did not finish in time")
    bad = []
    for name, p in procs.items():
        err = p.stderr.read()
        if p.returncode != 0:
            print(f"== {name} rc={p.returncode}\n{err[-4000:]}",
                  file=sys.stderr)
            bad.append(name)
    assert not bad, f"{bad} failed"

def csv_rows(cwd):
    # column 0 is the wall-clock timestamp — the only legal difference
    with open(os.path.join(cwd, "logs-server.csv")) as fh:
        return [";".join(ln.split(";")[1:]) for ln in fh.read().splitlines()]

def run_arm(tag, wire):
    cwd = os.path.join(root, tag)
    os.makedirs(cwd, exist_ok=True)
    sport, aport = free_port(), free_port()
    sp = server_proc(cwd, sport, wire)
    rp = agg_proc(cwd, sport, aport, wire)
    w01 = worker_proc(cwd, "0,1", aport, wire)
    w23 = worker_proc(cwd, "2,3", aport, wire)
    # SIGKILL member process 2,3 once the server shows real progress
    csv_path = os.path.join(cwd, "logs-server.csv")
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            with open(csv_path) as fh:
                n = sum(1 for _ in fh) - 1
        except OSError:
            n = 0
        if n >= 16:
            break
        for name, p in (("server", sp), ("relay", rp), ("w23", w23)):
            if p.poll() is not None:
                print(p.stderr.read(), file=sys.stderr)
                raise SystemExit(f"{tag}: {name} exited before the kill")
        time.sleep(0.05)
    else:
        raise SystemExit(f"{tag}: server never made progress")
    os.kill(w23.pid, signal.SIGKILL)
    w23.wait()
    time.sleep(0.5)
    # restart: durable state restores the 32-row windows, READY fires
    # immediately, the stalled round completes
    w23b = worker_proc(cwd, "2,3", aport, wire)
    finish({"server": sp, "relay": rp, "worker01": w01,
            "worker23-restarted": w23b})
    return cwd

cwd_on = run_arm("coalesce-on", "--wire-coalesce")
cwd_off = run_arm("coalesce-off", "--no-wire-coalesce")

zon = np.load(os.path.join(cwd_on, "ckpt.npz"))
zoff = np.load(os.path.join(cwd_off, "ckpt.npz"))
assert int(zon["iterations"]) >= MAX_IT <= int(zoff["iterations"])
assert zon["theta"].tobytes() == zoff["theta"].tobytes(), \
    "coalesced theta diverged from the --no-wire-coalesce arm"
assert csv_rows(cwd_on) == csv_rows(cwd_off) != [], \
    "coalesced eval CSV diverged from the --no-wire-coalesce arm"
print(f"WIRE_SMOKE_OK workers=4 relay=1 iters={MAX_IT} "
      f"kill=worker23+restart theta=bitwise csv=bitwise")
EOF
    exit $?
fi

if [[ "${1:-}" == "--eval" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

# the async-eval A/B (docs/EVALUATION.md "Async evaluation"): the SAME
# training run — server <-- 1 relay <-- 2 member worker processes (4
# logical workers), eval_every=1, deterministic knobs as in the --wire
# leg (--bsp-order, --ready-rows = full partition) — once with the
# async coalescing eval engine (the default) and once with
# --no-eval-async (the fused _apply_full_eval programs).  In EACH arm
# one member worker process is SIGKILL'd mid-run and restarted: the
# engine holds no durable state (pending (theta, clock) snapshots die
# with the process and the worker-state + relay-stash + READY-reissue
# machinery re-derives the applied sequence), so final theta AND the
# server eval CSV must match bitwise across the eval lever no matter
# when the kill landed.
root = tempfile.mkdtemp(prefix="kps-eval-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(192, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:128], y[:128])),
                       (test, (x[128:], y[128:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# 2000 rounds keep the training window open for seconds so the mid-run
# kill lands while the gate is still cycling (the eval CSV is drained
# asynchronously in BOTH arms — the on-disk row count lags the clock)
MAX_IT = 2000
READY = 32          # 128 rows / 4 workers: full-partition gating
common = ["--num_workers", "4", "--num_features", "8",
          "--num_classes", "2", "--max_iterations", str(MAX_IT),
          "--eval_every", "1"]

def server_proc(cwd, port, evalflag):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
         "--listen", str(port), "--bsp-order", "-c", "0",
         "-training", train, "-test", test, "-p", "1", "--logging",
         "--checkpoint", os.path.join(cwd, "ckpt.npz"),
         *evalflag, *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def worker_proc(cwd, wids, aport):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
         "--aggregate", f"127.0.0.1:{aport}", "--worker_ids", wids,
         "-test", test, "-min", "8", "-max", "32",
         "--ready-rows", str(READY),
         "--checkpoint", os.path.join(cwd, "job.npz"),
         "--state_every", "0.2", *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def agg_proc(cwd, sport, aport):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.agg_runner",
         "--connect", f"127.0.0.1:{sport}", "--listen", str(aport),
         "--agg-id", "0", "--worker_ids", "0,1,2,3", *common],
        env=env, cwd=cwd, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

def finish(procs, deadline_s=240):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in procs.values()):
            break
        time.sleep(0.25)
    else:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for name, p in procs.items():
            print(f"== {name} rc={p.poll()}\n{p.stderr.read()[-4000:]}",
                  file=sys.stderr)
        raise SystemExit("fleet did not finish in time")
    bad = []
    for name, p in procs.items():
        err = p.stderr.read()
        if p.returncode != 0:
            print(f"== {name} rc={p.returncode}\n{err[-4000:]}",
                  file=sys.stderr)
            bad.append(name)
    assert not bad, f"{bad} failed"

def csv_rows(cwd):
    # column 0 is the wall-clock timestamp — the only legal difference
    with open(os.path.join(cwd, "logs-server.csv")) as fh:
        return [";".join(ln.split(";")[1:]) for ln in fh.read().splitlines()]

def run_arm(tag, evalflag):
    cwd = os.path.join(root, tag)
    os.makedirs(cwd, exist_ok=True)
    sport, aport = free_port(), free_port()
    sp = server_proc(cwd, sport, evalflag)
    rp = agg_proc(cwd, sport, aport)
    w01 = worker_proc(cwd, "0,1", aport)
    w23 = worker_proc(cwd, "2,3", aport)
    # SIGKILL member process 2,3 once the server shows real progress
    csv_path = os.path.join(cwd, "logs-server.csv")
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            with open(csv_path) as fh:
                n = sum(1 for _ in fh) - 1
        except OSError:
            n = 0
        if n >= 16:
            break
        for name, p in (("server", sp), ("relay", rp), ("w23", w23)):
            if p.poll() is not None:
                print(p.stderr.read(), file=sys.stderr)
                raise SystemExit(f"{tag}: {name} exited before the kill")
        time.sleep(0.05)
    else:
        raise SystemExit(f"{tag}: server never made progress")
    os.kill(w23.pid, signal.SIGKILL)
    w23.wait()
    time.sleep(0.5)
    # restart: durable state restores the 32-row windows, READY fires
    # immediately, the stalled round completes; any evals the async
    # engine still held at kill time were never durable — the engine
    # re-derives them from the re-applied clock sequence
    w23b = worker_proc(cwd, "2,3", aport)
    finish({"server": sp, "relay": rp, "worker01": w01,
            "worker23-restarted": w23b})
    return cwd

cwd_async = run_arm("eval-async", [])
cwd_fused = run_arm("eval-fused", ["--no-eval-async"])

za = np.load(os.path.join(cwd_async, "ckpt.npz"))
zf = np.load(os.path.join(cwd_fused, "ckpt.npz"))
assert int(za["iterations"]) >= MAX_IT <= int(zf["iterations"])
assert za["theta"].tobytes() == zf["theta"].tobytes(), \
    "async-eval theta diverged from the --no-eval-async arm"
assert csv_rows(cwd_async) == csv_rows(cwd_fused) != [], \
    "async-eval CSV diverged from the --no-eval-async arm"
print(f"EVAL_SMOKE_OK workers=4 relay=1 iters={MAX_IT} eval_every=1 "
      f"kill=worker23+restart theta=bitwise csv=bitwise")
EOF
    exit $?
fi

if [[ "${1:-}" == "--obs" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import re
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.data.synth import generate_hard
from kafka_ps_tpu.runtime import fabric as fabric_mod, net
from kafka_ps_tpu.runtime.server import ServerNode
from kafka_ps_tpu.runtime.worker import WorkerNode
from kafka_ps_tpu.telemetry import Telemetry
from kafka_ps_tpu.telemetry.merge import merge_traces
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig
from kafka_ps_tpu.utils.csvlog import NullLogSink
from kafka_ps_tpu.utils.trace import Tracer

model = ModelConfig(num_features=64, num_classes=2)
x, y = generate_hard(512 + 500, num_features=64, num_classes=2, seed=9)
test_x, test_y = x[-500:], y[-500:]
# three workers, one straggler: worker 2 lags STRAGGLER_LAG_S before
# each local step.  Under BSP the gate then withholds the round's
# weights from BOTH fast workers until the straggler reports, so
# gate_wait accrues 2x the lag per round while buffer_wait (charged to
# the straggler's own flows) accrues 1x — the decomposition must
# convict the gate, not the wire, and with a 2x margin it does so
# robustly.  This is the scenario critical-path analysis exists for.
ids = [0, 1, 2]
STRAGGLER, STRAGGLER_LAG_S = 2, 0.012
out = Path(tempfile.mkdtemp(prefix="kps-obs-"))


def run_traced(c, pid_s, pid_w):
    """One short socket-bridged run under consistency model `c`; two
    tracers with distinct pids stand in for the two PROCESSES the
    socket deployment runs (`--listen --trace` / `--connect --trace`).
    Returns the worker/server trace paths and the server telemetry."""
    cfg = PSConfig(num_workers=3, consistency_model=c, model=model,
                   buffer=BufferConfig(min_size=32, max_size=256),
                   eval_every=10**9, use_gang=False)
    tr_s, tr_w = Tracer(pid=pid_s), Tracer(pid=pid_w)
    tel_s, tel_w = Telemetry(tracer=tr_s), Telemetry(tracer=tr_w)
    sbridge = net.ServerBridge(port=0, run_id=1, tracer=tr_s,
                               telemetry=tel_s)
    sfabric = sbridge.wrap(fabric_mod.Fabric())
    server = ServerNode(cfg, sfabric, test_x, test_y, NullLogSink(),
                        tracer=tr_s, telemetry=tel_s)
    wbridge = net.WorkerBridge("127.0.0.1", sbridge.port, ids,
                               tracer=tr_w, telemetry=tel_w)
    assert wbridge.trace_negotiated, "trace context did not negotiate on"
    wfabric = wbridge.make_fabric()
    buffers = {w: SlidingBuffer(64, cfg.buffer, telemetry=tel_w, worker=w)
               for w in ids}
    nodes = {w: WorkerNode(w, cfg, wfabric, buffers[w], test_x, test_y,
                           NullLogSink(), tracer=tr_w, telemetry=tel_w)
             for w in ids}
    for w in ids:
        for i in range(w, 512, len(ids)):
            buffers[w].add(dict(enumerate(x[i])), int(y[i]))
    reader = threading.Thread(target=wbridge.run_reader, args=(buffers,),
                              daemon=True)
    reader.start()
    for w in ids:
        wbridge.mark_ready(w)
    sbridge.wait_for_connected(ids, timeout=30)
    sbridge.wait_for_workers(ids, timeout=30)
    stop = threading.Event()

    def worker_loop(node, lag_s):
        try:
            while not stop.is_set():
                m = wfabric.poll_blocking(fabric_mod.WEIGHTS_TOPIC,
                                          node.worker_id, timeout=0.05)
                if m is not None:
                    if lag_s:
                        time.sleep(lag_s)   # the straggler's lag
                    node.on_weights(m)
        except (ConnectionError, OSError):
            pass
    ts = [threading.Thread(
              target=worker_loop,
              args=(nodes[w],
                    STRAGGLER_LAG_S if w == STRAGGLER else 0.0),
              daemon=True) for w in ids]
    for t in ts:
        t.start()
    server.start_training_loop()
    # warmup: run until the jit compiles (worker local_update, server
    # apply) have all fired, then clear both tracers — the critical
    # path must reflect steady state, not one-time compilation stalls
    while server.iterations < 8:
        g = sfabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                  timeout=0.2)
        if g is not None:
            server.process(g)
    tr_s.clear()
    tr_w.clear()
    while server.iterations < 32:
        g = sfabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                  timeout=0.2)
        if g is not None:
            server.process(g)
    stop.set()
    sbridge.close()
    for t in ts:
        t.join(timeout=120)
    wbridge.close()
    reader.join(timeout=10)
    server.log.close()
    pw = str(out / f"worker.{pid_w}.trace.json")
    ps = str(out / f"server.{pid_s}.trace.json")
    tr_w.dump(pw)
    tr_s.dump(ps)
    return pw, ps, tel_s


# one run per consistency model, distinct pid pairs, so all six traces
# merge onto ONE timeline and the critical-path CLI sees every model
runs = {0: run_traced(0, 1001, 2002),
        2: run_traced(2, 1003, 2004),
        -1: run_traced(-1, 1005, 2006)}
traces = [p for pw, ps, _ in runs.values() for p in (pw, ps)]
stats = merge_traces(traces, str(out / "merged.json"))
assert stats["cross_process_flows"] >= 1, stats
assert sorted(stats["pids"]) == [1001, 1003, 1005,
                                 2002, 2004, 2006], stats

tel_s = runs[2][2]
metrics = str(out / "metrics.prom")
tel_s.write_prometheus(metrics)
text = Path(metrics).read_text()
for line in text.splitlines():          # every sample line must parse
    if line and not line.startswith("#"):
        float(line.rsplit(" ", 1)[1])
for family in ("gate_wait_ms_bucket", "clock_lag_bucket",
               "gradients_applied_total", "frames_received"):
    assert family in text, f"{family} missing from metrics dump"
assert 'model="bounded"' in text, "staleness histograms unlabeled"
snap = tel_s.snapshot()
assert snap["gate_wait_ms"]["model=bounded"]["count"] > 0, snap
print(f"OBS_SMOKE_OK flows={stats['cross_process_flows']} "
      f"events={stats['events']} pids={sorted(stats['pids'])} "
      f"metric_families={len(snap)}")

# ---- critical-path decomposition over the merged trace ---------------
# the CLI must exit 0, decompose flows for EVERY consistency model, and
# convict gate_wait as BSP's dominant segment (the sequential gate
# holds weights until the whole round arrives — that wait IS the
# model's defining cost, docs/OBSERVABILITY.md "Critical-path analysis")
cp = subprocess.run(
    [sys.executable, "-m", "kafka_ps_tpu.telemetry", "critpath",
     str(out / "merged.json")], capture_output=True, text=True,
    timeout=120)
assert cp.returncode == 0, (
    f"critpath rc={cp.returncode}\n{cp.stdout}{cp.stderr}")
doms = dict(re.findall(r"^model=(\S+) flows=\d+ dominant=(\S+)",
                       cp.stdout, re.M))
for m in ("sequential", "bounded", "eventual"):
    assert m in doms, (doms, cp.stdout)
assert doms["sequential"] == "gate_wait", (doms, cp.stdout)
print(f"OBS_CRITPATH_OK dominants=" + ",".join(
    f"{m}:{d}" for m, d in sorted(doms.items())))

# ---- phase 2: black-box postmortem of a SIGKILLed shard --------------
# A real split-deployment fleet (2 shard servers + 1 worker process, the
# --shard leg's topology) runs with --flight-dir; shard 1 is SIGKILLed
# mid-run — it writes NO dump, and that absence is the finding.  The
# survivors' death-hook/shutdown dumps are merged by the postmortem CLI,
# which must name the dead shard and its last acknowledged weights send.
import glob
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np

proot = tempfile.mkdtemp(prefix="kps-postmortem-")
flight = os.path.join(proot, "flight")
repo = os.getcwd()
prng = np.random.default_rng(0)
px = prng.normal(size=(256, 8)).astype(np.float32)
py = (px[:, 0] > 0).astype(np.int32) + 1
ptrain = os.path.join(proot, "train.csv")
ptest = os.path.join(proot, "test.csv")
for path, (xx, yy) in ((ptrain, (px[:200], py[:200])),
                       (ptest, (px[200:], py[200:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

p0, p1 = free_port(), free_port()
penv = dict(os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# the fleet is killed mid-run; MAX_IT only has to outlast the kill point
MAX_IT = 5000
pcommon = ["--num_workers", "2", "--num_features", "8",
           "--num_classes", "2", "--max_iterations", str(MAX_IT),
           "--flight-dir", flight]
logdir = os.path.join(proot, "log")

def pshard(i, port):
    return subprocess.Popen(
        [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
         "--listen", str(port), "--shards", "2", "--shard-id", str(i),
         "-training", ptrain, "-test", ptest, "-p", "5", "-c", "0",
         "--durable-log", logdir,
         "--checkpoint", os.path.join(proot, "ckpt.npz"),
         "--checkpoint_every", "50", *pcommon],
        env=penv, cwd=proot, stderr=subprocess.PIPE,
        stdout=subprocess.DEVNULL, text=True)

s0, s1 = pshard(0, p0), pshard(1, p1)
w = subprocess.Popen(
    [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
     "--connect", f"127.0.0.1:{p0},127.0.0.1:{p1}",
     "--worker_ids", "0,1", "-test", ptest,
     "-min", "8", "-max", "32", *pcommon],
    env=penv, cwd=proot, stderr=subprocess.PIPE,
    stdout=subprocess.DEVNULL, text=True)

# wait until shard 1 has served real traffic (its gradient log has a
# prefix of slices — so every surviving ring holds shard-1 evidence),
# then SIGKILL it: no handler runs, no dump is written
grad_glob = os.path.join(logdir, "shard1of2", "gradients", "*", "*.log")
deadline = time.monotonic() + 120
while time.monotonic() < deadline:
    segs = glob.glob(grad_glob)
    if segs and sum(os.path.getsize(s) for s in segs) > 8000:
        break
    if s1.poll() is not None:
        print(s1.stderr.read(), file=sys.stderr)
        raise SystemExit("shard1 exited before the kill point")
    time.sleep(0.1)
else:
    raise SystemExit("shard1 gradient log never grew")
os.kill(s1.pid, signal.SIGKILL)
s1.wait()
time.sleep(1.0)

# SIGTERM the survivors: the flight recorder's death hook dumps the
# rings then re-raises, so each leaves flightdump-<pid>.json behind
# (a survivor that already noticed the dead peer and exited through
# its normal path dumped on OpsPlane.close instead — either way the
# evidence is on disk; exit codes are NOT asserted here)
for p in (w, s0):
    if p.poll() is None:
        p.send_signal(signal.SIGTERM)
for p in (w, s0):
    try:
        p.wait(timeout=60)
    except subprocess.TimeoutExpired:
        p.kill()
        raise SystemExit("survivor ignored SIGTERM")

dumps = sorted(glob.glob(os.path.join(flight, "flightdump-*.json")))
pids = {int(os.path.basename(d).split("-")[1].split(".")[0])
        for d in dumps}
assert s0.pid in pids, f"shard0 left no dump: {dumps}"
assert w.pid in pids, f"worker left no dump: {dumps}"
assert s1.pid not in pids, "SIGKILLed shard must not have dumped"

pm = subprocess.run(
    [sys.executable, "-m", "kafka_ps_tpu.telemetry", "postmortem",
     flight], env=penv, cwd=proot, capture_output=True, text=True,
    timeout=120)
assert pm.returncode == 0, f"postmortem rc={pm.returncode}\n{pm.stderr}"
assert "dead shard 1" in pm.stdout, pm.stdout
assert "last ack from shard 1" in pm.stdout, pm.stdout
print(f"POSTMORTEM_OK dumps={len(dumps)} dead_shard=1 "
      f"survivors={sorted(pids)}")
EOF
    exit $?
fi

if [[ "${1:-}" == "--drift" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

# a socket-bridged pair (server process + worker process hosting 2
# logical workers) trains on a stream that TURNS: the first half of
# train.csv is clean and learnable, the second half label-flipped AND
# feature-shifted.  The held-out test set stays clean, so streaming
# eval loss rises once the poisoned rows displace the clean ones in
# the worker buffers — exactly the regime the drift plane exists for.
root = tempfile.mkdtemp(prefix="kps-drift-")
flight = os.path.join(root, "flight")
repo = os.getcwd()
rng = np.random.default_rng(0)
N_CLEAN, N_DRIFT, N_TEST = 600, 600, 56
xc = rng.normal(size=(N_CLEAN + N_TEST, 8)).astype(np.float32)
yc = (xc[:, 0] > 0).astype(np.int32) + 1
xd = (rng.normal(size=(N_DRIFT, 8)) + 2.0).astype(np.float32)
yd = (3 - ((xd[:, 0] - 2.0 > 0).astype(np.int32) + 1)).astype(np.int32)

def write_csv(path, parts):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for xx, yy in parts:
            for r, lab in zip(xx, yy):
                fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

train = os.path.join(root, "train.csv")            # clean, then poisoned
clean_train = os.path.join(root, "train-clean.csv")
test = os.path.join(root, "test.csv")
write_csv(train, [(xc[:N_CLEAN], yc[:N_CLEAN]), (xd, yd)])
write_csv(clean_train, [(xc[:N_CLEAN], yc[:N_CLEAN])])
write_csv(test, [(xc[N_CLEAN:], yc[N_CLEAN:])])

def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

p0, hp = free_port(), free_port()
env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# the fleet is torn down once the verdict lands; MAX_IT only has to
# outlast the ~2.5 s stream plus the detector's baseline
MAX_IT = 100000
common = ["--num_workers", "2", "--num_features", "8",
          "--num_classes", "2", "--max_iterations", str(MAX_IT),
          "--eval_every", "2", "--model-health", "--drift-detector",
          "ph", "--flight-dir", flight]

server = subprocess.Popen(
    [sys.executable, "-m", "kafka_ps_tpu.cli.server_runner",
     "--listen", str(p0), "-training", train, "-test", test,
     "-p", "2", "-c", "0", "-l", "--health-port", str(hp), *common],
    env=env, cwd=root, stderr=subprocess.PIPE,
    stdout=subprocess.DEVNULL, text=True)
worker = subprocess.Popen(
    [sys.executable, "-m", "kafka_ps_tpu.cli.worker_runner",
     "--connect", f"127.0.0.1:{p0}", "--worker_ids", "0,1",
     "-test", test, "-min", "8", "-max", "64", *common],
    env=env, cwd=root, stderr=subprocess.PIPE,
    stdout=subprocess.DEVNULL, text=True)

def die(msg):
    for name, p in (("server", server), ("worker", worker)):
        if p.poll() is None:
            p.kill()
        print(f"== {name} rc={p.poll()}\n{p.stderr.read()[-4000:]}",
              file=sys.stderr)
    raise SystemExit(msg)

# watch the verdict live over /modelz until the server's plane latches
state, doc = None, {}
deadline = time.monotonic() + 240
while time.monotonic() < deadline:
    if server.poll() is not None or worker.poll() is not None:
        die("fleet died before the drift verdict")
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{hp}/modelz", timeout=2) as r:
            doc = json.loads(r.read())
        state = doc["drift"]["state"]
        if state == "DRIFT":
            break
    except (OSError, ValueError, KeyError):
        pass
    time.sleep(0.25)
else:
    die(f"drift never latched; last /modelz state={state}")
assert doc["drift"]["trips"] >= 1, doc
assert doc["updates"] > 0 and doc["workers"], doc

# the armed drift watchdog (latched DRIFT = continuous demand) must
# ship a flight dump carrying the drift.trip event within seconds
trip_dump = None
deadline = time.monotonic() + 60
while time.monotonic() < deadline and trip_dump is None:
    for path in sorted(glob.glob(
            os.path.join(flight, "flightdump-*.json"))):
        try:
            with open(path) as fh:
                d = json.load(fh)
        except (OSError, ValueError):
            continue
        if any(e.get("kind") == "drift.trip"
               for e in d.get("events") or []):
            trip_dump = path
    time.sleep(0.5)
if trip_dump is None:
    die("no flight dump carried the drift.trip event")

for p in (worker, server):
    if p.poll() is None:
        p.send_signal(signal.SIGTERM)
for p in (worker, server):
    try:
        p.wait(timeout=60)
    except subprocess.TimeoutExpired:
        p.kill()
        raise SystemExit("fleet ignored SIGTERM")

# the wall-clock-stamped drift CSV recorded the trip edge
with open(os.path.join(root, "logs-drift.csv")) as fh:
    rows = [ln.split(";") for ln in fh.read().splitlines()[1:] if ln]
trip_rows = [r for r in rows if r[1] == "trip"]
assert trip_rows, f"logs-drift.csv recorded no trip: {rows}"

# control: the same flags over a clean stream must end with ZERO trips
ctl = os.path.join(root, "control")
os.makedirs(ctl, exist_ok=True)
proc = subprocess.run(
    [sys.executable, "-m", "kafka_ps_tpu.cli.run",
     "-training", clean_train, "-test", test, "-min", "8", "-max", "64",
     "-p", "1", "-c", "0", "--mode", "serial", "-l",
     "--num_workers", "2", "--num_features", "8", "--num_classes", "2",
     "--eval_every", "2", "--max_iterations", "400",
     "--model-health", "--drift-detector", "ph"],
    env=env, cwd=ctl, capture_output=True, text=True, timeout=240)
assert proc.returncode == 0, \
    f"control rc={proc.returncode}\n{proc.stderr[-4000:]}"
with open(os.path.join(ctl, "logs-drift.csv")) as fh:
    crows = [ln.split(";") for ln in fh.read().splitlines()[1:] if ln]
ctrips = [r for r in crows if r[1] == "trip"]
assert not ctrips, f"control arm false-tripped: {ctrips}"

print(f"DRIFT_SMOKE_OK state=DRIFT trips={doc['drift']['trips']} "
      f"detector={doc['drift']['detector']} dump={os.path.basename(trip_dump)} "
      f"csv_trips={len(trip_rows)} control_trips=0 "
      f"control_events={len(crows)}")
EOF
    exit $?
fi

if [[ "${1:-}" == "--compress" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import threading
import numpy as np
from kafka_ps_tpu.compress import wire as cwire
from kafka_ps_tpu.data.buffer import SlidingBuffer
from kafka_ps_tpu.data.synth import generate_hard
from kafka_ps_tpu.runtime import fabric as fabric_mod, net
from kafka_ps_tpu.runtime.server import ServerNode
from kafka_ps_tpu.runtime.worker import WorkerNode
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig)
from kafka_ps_tpu.utils.csvlog import NullLogSink

model = ModelConfig(num_features=64, num_classes=2)
x, y = generate_hard(512 + 500, num_features=64, num_classes=2, seed=9)
test_x, test_y = x[-500:], y[-500:]

def run(compress, iters=24):
    ids = [0, 1]
    cfg = PSConfig(num_workers=2, consistency_model=0, model=model,
                   buffer=BufferConfig(min_size=32, max_size=256),
                   eval_every=10**9, use_gang=False, compress=compress)
    spec = cwire.parse_codec(compress)
    sbridge = net.ServerBridge(port=0, run_id=1, codec=spec)
    sfabric = sbridge.wrap(fabric_mod.Fabric())
    server = ServerNode(cfg, sfabric, test_x, test_y, NullLogSink())
    wbridge = net.WorkerBridge("127.0.0.1", sbridge.port, ids, codec=spec)
    wfabric = wbridge.make_fabric()
    buffers = {w: SlidingBuffer(64, cfg.buffer) for w in ids}
    nodes = {w: WorkerNode(w, cfg, wfabric, buffers[w], test_x, test_y,
                           NullLogSink()) for w in ids}
    if wbridge.negotiated.codec_id != net.CODEC_NONE:
        from kafka_ps_tpu import compress as comp
        codec = comp.get_codec(wbridge.negotiated, server.task.num_params)
        server.compressor = comp.WeightsCompressor(codec)
        for w in ids:
            nodes[w].compressor = comp.ErrorFeedback(codec)
    reader = threading.Thread(target=wbridge.run_reader, args=(buffers,),
                              daemon=True)
    reader.start()
    sbridge.wait_for_connected(ids, timeout=30)
    # batched ingest end-to-end: rows cross as ONE T_DATA_BATCH frame
    # and land via SlidingBuffer.add_many
    for w in ids:
        rows = [(dict(enumerate(x[i])), int(y[i]))
                for i in range(w, 512, 2)]
        assert sbridge.send_data_batch(w, rows), "batch send failed"
    deadline = 30.0
    import time
    t0 = time.monotonic()
    while any(buffers[w].count == 0 for w in ids):
        if time.monotonic() - t0 > deadline:
            raise AssertionError("batched rows never arrived")
        time.sleep(0.01)
    for w in ids:
        wbridge.mark_ready(w)
    sbridge.wait_for_workers(ids, timeout=30)
    stop = threading.Event()
    def worker_loop(node):
        try:
            while not stop.is_set():
                m = wfabric.poll_blocking(fabric_mod.WEIGHTS_TOPIC,
                                          node.worker_id, timeout=0.05)
                if m is not None:
                    node.on_weights(m)
        except (ConnectionError, OSError):
            pass
    ts = [threading.Thread(target=worker_loop, args=(nodes[w],),
                           daemon=True) for w in ids]
    for t in ts:
        t.start()
    server.start_training_loop()
    while server.iterations < iters:
        g = sfabric.poll_blocking(fabric_mod.GRADIENTS_TOPIC, 0,
                                  timeout=0.2)
        if g is not None:
            server.process(g)
    stop.set()
    sbridge.close()
    for t in ts:
        t.join(timeout=120)
    wbridge.close()
    reader.join(timeout=10)
    server.log.close()
    wire = (sbridge.wire_bytes.get(net.T_WEIGHTS, 0)
            + sbridge.wire_bytes.get(net.T_GRADIENTS, 0))
    return wbridge.negotiated.name, server.iterations, wire

neg8, it8, wire8 = run("int8")
assert neg8 == "int8", f"negotiation failed: {neg8}"
assert it8 >= 24, it8
neg0, it0, wire0 = run("none")
assert neg0 == "none", neg0
assert wire8 < wire0 / 2, (wire8, wire0)
print(f"COMPRESS_SMOKE_OK int8_wire={wire8} none_wire={wire0} "
      f"ratio={wire0 / wire8:.2f} iters={it8}")
EOF
    exit $?
fi

if [[ "${1:-}" == "--serve" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
from kafka_ps_tpu.runtime import net
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.serving import StalenessError
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig,
                                       ServingConfig, StreamConfig)

cfg = PSConfig(num_workers=4, consistency_model=0,
               model=ModelConfig(num_features=8, num_classes=2,
                                 local_learning_rate=0.5),
               buffer=BufferConfig(min_size=8, max_size=32),
               stream=StreamConfig(time_per_event_ms=1.0),
               serving=ServingConfig(enabled=True))
rng = np.random.default_rng(0)
x = rng.normal(size=(128, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
app = StreamingPSApp(cfg, test_x=x, test_y=y)
engine = app.enable_serving()
for i in range(128):
    app.buffers[i % 4].add({j: float(x[i, j]) for j in range(8)},
                           int(y[i]))
app.run_serial(24)

# in-process prediction against the trained snapshot
pred = engine.predict(x[0])
assert pred.vector_clock > 0, pred
ref = app.server.task.predict_logits(app.server.theta, x[:1])
assert pred.label == int(np.argmax(np.asarray(ref)[0])), pred

# the staleness rejection path must fire for an unsatisfiable bound
try:
    engine.predict(x[0], min_clock=10**9)
except StalenessError:
    pass
else:
    raise AssertionError("unsatisfiable min_clock was served")
assert engine.rejections >= 1, engine.stats()

# the same predictions over the wire (cli/run.py --serve --serve_port)
bridge = net.ServerBridge(port=0, run_id=app.server.run_id)
bridge.attach_serving(engine)
client = net.PredictClient("127.0.0.1", bridge.port)
try:
    remote = client.predict(x[0])
    assert remote.label == pred.label, (remote, pred)
    try:
        client.predict(x[0], min_clock=10**9)
    except StalenessError:
        pass
    else:
        raise AssertionError("remote staleness bound was served")
finally:
    client.close()
    bridge.close()
    s = engine.stats()
    app.close_serving()
print(f"SERVE_SMOKE_OK requests={s['requests']} batches={s['batches']} "
      f"rejections={s['rejections']}")
EOF
    exit $?
fi

if [[ "${1:-}" == "--gang" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig,
                                       StreamConfig)
from kafka_ps_tpu.utils.trace import Tracer

def run(use_gang):
    cfg = PSConfig(num_workers=4, consistency_model=0,
                   model=ModelConfig(num_features=8, num_classes=2,
                                     local_learning_rate=0.5),
                   buffer=BufferConfig(min_size=8, max_size=32),
                   stream=StreamConfig(time_per_event_ms=1.0),
                   use_gang=use_gang)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32) + 1
    tracer = Tracer()
    app = StreamingPSApp(cfg, test_x=x, test_y=y, tracer=tracer)
    for i in range(128):
        app.buffers[i % 4].add({j: float(x[i, j]) for j in range(8)},
                               int(y[i]))
    app.run_serial(24)
    return (np.asarray(app.server.theta),
            tracer.counters().get("dispatch.device", 0))

theta_on, disp_on = run(True)
theta_off, disp_off = run(False)
assert theta_on.tobytes() == theta_off.tobytes(), \
    "gang smoke: final theta diverged from the per-message path"
assert disp_on < disp_off, \
    f"gang smoke: dispatch count did not drop ({disp_on} vs {disp_off})"
print(f"GANG_SMOKE_OK dispatches {disp_on} vs {disp_off} per-message")
EOF
    exit $?
fi

if [[ "${1:-}" == "--perf" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig,
                                       StreamConfig)

def run(consistency, slab_dtype, incremental):
    cfg = PSConfig(num_workers=4, consistency_model=consistency,
                   model=ModelConfig(num_features=8, num_classes=2,
                                     local_learning_rate=0.5),
                   buffer=BufferConfig(min_size=8, max_size=32),
                   stream=StreamConfig(time_per_event_ms=1.0),
                   slab_dtype=slab_dtype, slab_incremental=incremental)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32) + 1
    app = StreamingPSApp(cfg, test_x=x, test_y=y)
    for i in range(128):
        app.buffers[i % 4].add({j: float(x[i, j]) for j in range(8)},
                               int(y[i]))
    app.run_serial(24)
    assert app.server.iterations >= 24, app.server.iterations
    theta = np.asarray(app.server.theta)
    assert np.isfinite(theta).all(), f"non-finite theta ({slab_dtype})"
    return theta

for c in (0, 2, -1):
    # f32 contract: the incremental scatter path is BITWISE-invisible
    inc = run(c, "f32", incremental=True)
    full = run(c, "f32", incremental=False)
    assert inc.tobytes() == full.tobytes(), \
        f"perf smoke: incremental f32 slab diverged at consistency={c}"
    # bf16 slab storage trains end-to-end on every consistency model
    run(c, "bf16", incremental=True)
print("PERF_SMOKE_OK f32 bitwise + bf16 e2e at consistency 0/2/-1")
EOF
    exit $?
fi

if [[ "${1:-}" == "--tier" ]]; then
    timeout -k 10 540 env JAX_PLATFORMS=cpu python - <<'EOF'
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

# tiered-store smoke (docs/TIERING.md), all through the public CLI.
# Phase A: for each consistency model, an uncapped run vs a run whose
# hot tier holds ~1/13 of the parameter bytes (1 of 14 pages; warm 2
# more; the other 11 live as cold commit-log records) must produce
# bitwise-identical theta AND an identical eval CSV (timestamps
# stripped).  Phase B: SIGKILL a capped durable run mid-training,
# restart it, and replay its gradients partition through a fresh FULLY
# RESIDENT ServerNode — recovered-capped theta must equal the resident
# replay bit for bit.
root = tempfile.mkdtemp(prefix="kps-tier-")
repo = os.getcwd()
rng = np.random.default_rng(0)
x = rng.normal(size=(256, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.int32) + 1
train, test = os.path.join(root, "train.csv"), os.path.join(root, "test.csv")
for path, (xx, yy) in ((train, (x[:200], y[:200])),
                       (test, (x[200:], y[200:]))):
    with open(path, "w") as fh:
        fh.write(",".join(f"f{i}" for i in range(8)) + ",Score\n")
        for r, lab in zip(xx, yy):
            fh.write(",".join(f"{v:.6f}" for v in r) + f",{lab}\n")

env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
# logreg 8 features x 2 classes -> 27 params = 108 bytes.  Page 2
# params (8 bytes): hot 8 = 1 page (~1/13 of the model, under the 1/10
# acceptance cap), warm 16 = 2 pages, the remaining 11 pages cold.
TIER = ["--tier-hot-bytes", "8", "--tier-warm-bytes", "16",
        "--tier-page-params", "2"]

def run_arm(tag, consistency, max_it, tier, eval_every=1, extra=()):
    cwd = os.path.join(root, tag)
    os.makedirs(cwd, exist_ok=True)
    ckpt = os.path.join(cwd, "ckpt.npz")
    cmd = [sys.executable, "-m", "kafka_ps_tpu.cli.run",
           "-training", train, "-test", test, "--num_workers", "2",
           "--num_features", "8", "--num_classes", "2", "-min", "8",
           "-max", "32", "-p", "1", "-c", str(consistency),
           "--mode", "serial", "--eval_every", str(eval_every),
           "--max_iterations", str(max_it), "--logging",
           "--checkpoint", ckpt, "--checkpoint_every", "20"]
    if tier:
        cmd += [*TIER, "--durable-log", os.path.join(cwd, "log")]
    proc = subprocess.Popen([*cmd, *extra], env=env, cwd=cwd, text=True,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE)
    return proc, cwd, ckpt

def finish(proc, tag):
    rc = proc.wait(timeout=240)
    err = proc.stderr.read()
    assert rc == 0, f"{tag} rc={rc}\n{err[-4000:]}"

def csv_rows(cwd):
    # column 0 is the wall-clock timestamp — the only legal difference
    with open(os.path.join(cwd, "logs-server.csv")) as fh:
        return [";".join(ln.split(";")[1:]) for ln in fh.read().splitlines()]

# -- phase A: capped vs resident, all three consistency models ------------
MAX_IT = 80
for c in (0, 2, -1):
    pb, db, kb = run_arm(f"base-{c}", c, MAX_IT, tier=False)
    finish(pb, f"base-{c}")
    pt, dt, kt = run_arm(f"capped-{c}", c, MAX_IT, tier=True)
    finish(pt, f"capped-{c}")
    zb, zt = np.load(kb), np.load(kt)
    assert int(zt["iterations"]) >= MAX_IT <= int(zb["iterations"])
    tier_res = np.asarray(zt["tier_residency"])
    from kafka_ps_tpu.store import TIER_COLD
    assert (tier_res == TIER_COLD).sum() >= 8, \
        f"c={c}: capped arm was not actually tiered: {tier_res}"
    assert zt["theta"].tobytes() == zb["theta"].tobytes(), \
        f"c={c}: capped theta diverged from resident theta"
    assert csv_rows(dt) == csv_rows(db) != [], \
        f"c={c}: eval CSV diverged between capped and resident"

# -- phase B: SIGKILL the capped durable run, restart, resident replay ----
from kafka_ps_tpu.log import LogConfig
from kafka_ps_tpu.log.manager import LogManager
from kafka_ps_tpu.runtime import fabric as fabric_mod
from kafka_ps_tpu.runtime import serde
from kafka_ps_tpu.runtime.server import ServerNode
from kafka_ps_tpu.utils.config import (BufferConfig, ModelConfig, PSConfig,
                                       StreamConfig)

KILL_IT = 200
for c in (0, 2, -1):
    tag = f"crash-{c}"
    proc, cwd, ckpt = run_arm(tag, c, KILL_IT, tier=True,
                              eval_every=1000000)
    logdir = os.path.join(cwd, "log")
    grad_glob = os.path.join(logdir, "gradients", "*", "*.log")
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        segs = glob.glob(grad_glob)
        if (segs and sum(os.path.getsize(s) for s in segs) > 6000
                and os.path.exists(ckpt)):
            break
        if proc.poll() is not None:
            print(proc.stderr.read(), file=sys.stderr)
            raise SystemExit(f"{tag} exited before the kill point")
        time.sleep(0.05)
    else:
        raise SystemExit(f"{tag} gradient log never grew")
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    proc2, _, _ = run_arm(tag, c, KILL_IT, tier=True, eval_every=1000000)
    finish(proc2, f"{tag}-restarted")

    z = np.load(ckpt)
    from kafka_ps_tpu.store import TIER_COLD
    assert (np.asarray(z["tier_residency"]) == TIER_COLD).any(), \
        f"{tag}: final checkpoint recorded no cold pages"
    cold_segs = glob.glob(os.path.join(logdir, "param-cold", "*.log"))
    assert cold_segs and sum(os.path.getsize(s) for s in cold_segs) > 0, \
        f"{tag}: cold partition is empty — nothing was ever demoted"
    # resident replay: the gradients partition (offset 0 up to the
    # final checkpoint's committed offset) through a fresh UNTIERED
    # ServerNode — log order is processing order across both
    # incarnations and the tracker dedups redelivered slices, so a
    # bitwise match proves capped+crash+restart == fully resident
    end = json.loads(str(z["log_offsets"]))["gradients/0"]
    cfg = PSConfig(num_workers=2, consistency_model=c, task="logreg",
                   model=ModelConfig(num_features=8, num_classes=2),
                   buffer=BufferConfig(min_size=8, max_size=32),
                   stream=StreamConfig(time_per_event_ms=1),
                   use_gang=False)
    srv = ServerNode(cfg, fabric_mod.Fabric(), None, None, None)
    srv.start_training_loop()
    mgr = LogManager(logdir, LogConfig())
    n = 0
    for off, payload in mgr.get("gradients", 0).read_from(0):
        if off >= end:
            break
        srv.process(serde.from_bytes(payload))
        n += 1
    mgr.close()
    assert srv.iterations >= KILL_IT, (c, srv.iterations)
    replay = np.asarray(srv.theta, dtype=np.float32)
    assert replay.tobytes() == z["theta"].tobytes(), \
        f"{tag}: resident replay diverged from recovered capped theta"

print(f"TIER_SMOKE_OK models=0/2/-1 hot=8B/108B pages=1hot+2warm+11cold "
      f"phaseA_iters={MAX_IT} phaseB_iters={KILL_IT} "
      f"theta=bitwise csv=bitwise crash=recovered-bitwise")
EOF
    exit $?
fi

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
