#!/usr/bin/env bash
# Launch an N-process distributed job on ONE machine (CPU backend) —
# the zero-infrastructure way to see the multi-host path run, exactly
# what tests/test_multiprocess.py automates.  The reference's analogue
# is run.sh (worker JVM + server JVM against a local broker).
#
#   deploy/launch_local_multihost.sh [N_PROCESSES] [extra cli args...]
#
# Range-sharded split deployment (docs/SHARDING.md) on one machine —
# N shard-server processes, each owning a contiguous key range of
# theta (its own gate, checkpoint, and durable-log partition), plus
# one worker process connected to all of them:
#
#   deploy/launch_local_multihost.sh --sharded [N_SHARDS] [server args...]
#
# Hierarchical aggregation tier (docs/AGGREGATION.md) on one machine —
# one server, N aggregator-relay processes, and one worker process of
# 2 logical workers behind each relay, so the server sees N composite
# connections instead of 2N worker connections:
#
#   deploy/launch_local_multihost.sh --agg [N_RELAYS] [server args...]
#
# Writes logs-server.csv (+ logs-worker*.csv) into $PWD.
#
# CPU BY DEFAULT, on purpose: every mode here starts several processes
# on one machine, and an accelerator belongs to ONE process — a second
# process that reaches for a TPU dies at start-up ("Unable to
# initialize backend 'tpu': ... libtpu multi-process lockfile").  On a
# machine with a chip, at most one process of a fleet may hold it (for
# the server/worker split: the worker; the server stays on the CPU).
# The chip check is `python chip_smoke.py`, one process.
set -euo pipefail

NPROCS="${1:-2}"
shift || true
PORT=$(( 20000 + RANDOM % 20000 ))
REPO="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

if [ "$NPROCS" = "--sharded" ]; then
  NSHARDS="${1:-2}"
  shift || true
  export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
  if [ ! -f ./train.csv ]; then
    python -m kafka_ps_tpu.data.synth --out_dir . --rows 2000 \
        --test_rows 400 --hard --num_features 64
  fi
  pids=()
  addrs=""
  for i in $(seq 0 $((NSHARDS - 1))); do
    python -m kafka_ps_tpu.cli.server_runner \
        --listen "$((PORT + i))" --shards "$NSHARDS" --shard-id "$i" \
        -training ./train.csv -test ./test.csv --num_features 64 \
        -c 0 -p 1 --num_workers 2 --max_iterations 200 "$@" &
    pids+=($!)
    addrs="${addrs:+$addrs,}127.0.0.1:$((PORT + i))"
  done
  python -m kafka_ps_tpu.cli.worker_runner \
      --connect "$addrs" --worker_ids 0,1 -test ./test.csv \
      --num_features 64 -min 8 -max 32 &
  pids+=($!)
  for p in "${pids[@]}"; do wait "$p"; done
  echo "done: $NSHARDS shards, ranges reassembled by the worker pulls"
  exit 0
fi
if [ "$NPROCS" = "--agg" ]; then
  NAGG="${1:-2}"
  shift || true
  export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
  if [ ! -f ./train.csv ]; then
    python -m kafka_ps_tpu.data.synth --out_dir . --rows 2000 \
        --test_rows 400 --hard --num_features 64
  fi
  NWORKERS=$(( NAGG * 2 ))
  pids=()
  python -m kafka_ps_tpu.cli.server_runner \
      --listen "$PORT" -training ./train.csv -test ./test.csv \
      --num_features 64 -c 0 --bsp-order -p 1 \
      --num_workers "$NWORKERS" --max_iterations 200 "$@" &
  pids+=($!)
  for i in $(seq 0 $((NAGG - 1))); do
    ids="$((i * 2)),$((i * 2 + 1))"
    python -m kafka_ps_tpu.cli.agg_runner \
        --connect "127.0.0.1:$PORT" --listen "$((PORT + 1 + i))" \
        --agg-id "$i" --worker_ids "$ids" \
        --num_features 64 --num_workers "$NWORKERS" &
    pids+=($!)
    python -m kafka_ps_tpu.cli.worker_runner \
        --aggregate "127.0.0.1:$((PORT + 1 + i))" --worker_ids "$ids" \
        -test ./test.csv --num_features 64 -min 8 -max 32 \
        --num_workers "$NWORKERS" &
    pids+=($!)
  done
  for p in "${pids[@]}"; do wait "$p"; done
  echo "done: $NAGG relays pre-reduced $NWORKERS workers" \
       "into $NAGG server connections"
  exit 0
fi
export KPS_PLATFORM=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=2"
export KPS_COORDINATOR="127.0.0.1:$PORT"
export KPS_NUM_PROCESSES="$NPROCS"

if [ ! -f ./train.csv ]; then
  python -m kafka_ps_tpu.data.synth --out_dir . --rows 2000 \
      --test_rows 400 --hard --num_features 64
fi

pids=()
for i in $(seq 0 $((NPROCS - 1))); do
  KPS_PROCESS_ID="$i" python -m kafka_ps_tpu.cli.run \
      -training ./train.csv -test ./test.csv --num_features 64 \
      --num_workers "$((NPROCS * 2))" --fused -r -l -p 1 \
      --max_iterations 200 "$@" &
  pids+=($!)
done
for p in "${pids[@]}"; do wait "$p"; done
echo "done: $(wc -l < logs-server.csv) server log lines"
