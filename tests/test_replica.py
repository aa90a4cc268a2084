"""Log-following read replicas (serving/replica.py + log/tail.py).

The contracts:

  * the tailer is STRICTLY read-only and incremental — it never
    truncates a live writer's torn tail (that is `LogSegment._recover`'s
    job, for the OWNER, on restart), returns each record exactly once,
    and picks up a torn tail once the writer completes it;
  * an unsharded replica converges on the newest logged weights by
    vector clock (the incremental mirror of
    `DurableFabric.latest_logged_weights`);
  * a sharded replica (`DIR/shard<i>of<N>` — the `--shards N` split
    deployment's layout) serves the ASSEMBLED theta through
    FrontierCutPublisher: every published snapshot is a consistent
    frontier-stamped cut, proven never torn under concurrent shard
    writers.
"""

import os
import threading
import time

import numpy as np
import pytest

from kafka_ps_tpu.log import DurableFabric, LogConfig, records
from kafka_ps_tpu.log.tail import PartitionTailer, TopicTailer
from kafka_ps_tpu.runtime.messages import KeyRange, WeightsMessage
from kafka_ps_tpu.serving.replica import ReplicaFollower, discover_shards

CFG = LogConfig(fsync="none")


def wmsg(clock, lo, hi, fill):
    return WeightsMessage(clock, KeyRange(lo, hi),
                          np.full(hi - lo, float(fill), np.float32))


# -- the read-only tailer ----------------------------------------------------

def test_partition_tailer_incremental_and_torn_tail(tmp_path):
    part = tmp_path / "weights" / "0"
    part.mkdir(parents=True)
    seg = part / "00000000000000000000.log"
    r0 = records.pack_record(0, b"alpha")
    r1 = records.pack_record(1, b"beta")
    r2 = records.pack_record(2, b"gamma")
    seg.write_bytes(r0 + r1)

    tailer = PartitionTailer(str(part))
    assert tailer.poll() == [(0, b"alpha"), (1, b"beta")]
    assert tailer.poll() == []          # nothing new: no re-delivery

    # a torn tail (writer mid-append) yields nothing and NOTHING is
    # truncated; completing the record delivers it on the next poll
    size_before = seg.stat().st_size
    seg.write_bytes(r0 + r1 + r2[: len(r2) // 2])
    assert tailer.poll() == []
    assert seg.stat().st_size == size_before + len(r2) // 2
    seg.write_bytes(r0 + r1 + r2)
    assert tailer.poll() == [(2, b"gamma")]


def test_partition_tailer_segment_roll_and_missing_dir(tmp_path):
    part = tmp_path / "p"
    tailer = PartitionTailer(str(part))
    assert tailer.poll() == []          # not created yet: no error
    part.mkdir()
    (part / "00000000000000000000.log").write_bytes(
        records.pack_record(0, b"a"))
    assert tailer.poll() == [(0, b"a")]
    # a rolled segment appears as a new file and is read from offset 0
    (part / "00000000000000000001.log").write_bytes(
        records.pack_record(1, b"b"))
    assert tailer.poll() == [(1, b"b")]


def test_topic_tailer_discovers_new_partitions(tmp_path):
    root = tmp_path / "log"
    tailer = TopicTailer(str(root), "weights")
    assert tailer.poll() == []
    p0 = root / "weights" / "0"
    p0.mkdir(parents=True)
    (p0 / "00000000000000000000.log").write_bytes(
        records.pack_record(0, b"w0"))
    assert tailer.poll() == [(0, 0, b"w0")]
    p3 = root / "weights" / "3"         # late-joining worker partition
    p3.mkdir()
    (p3 / "00000000000000000000.log").write_bytes(
        records.pack_record(0, b"w3"))
    assert tailer.poll() == [(3, 0, b"w3")]
    assert tailer.keys() == (0, 3)


# -- unsharded replica -------------------------------------------------------

def test_replica_follows_unsharded_log_newest_by_clock(tmp_path):
    fab = DurableFabric(str(tmp_path), CFG)
    try:
        for clock in (1, 2, 3):
            for worker in (0, 1):
                fab.send("weights", worker, wmsg(clock, 0, 8, clock))
        rep = ReplicaFollower(str(tmp_path))
        assert rep.num_shards == 0 and discover_shards(str(tmp_path)) == []
        assert rep.catch_up() == 1
        assert rep.clock == 3
        np.testing.assert_array_equal(rep.registry.latest.theta,
                                      np.full(8, 3.0, np.float32))
        assert rep.catch_up() == 0      # idle poll: no duplicate publish
        fab.send("weights", 0, wmsg(4, 0, 8, 4))
        assert rep.catch_up() == 1 and rep.clock == 4
        assert rep.records_read == 7
    finally:
        fab.close()


def test_replica_background_thread_follows(tmp_path):
    fab = DurableFabric(str(tmp_path), CFG)
    rep = ReplicaFollower(str(tmp_path), poll_interval_s=0.01)
    try:
        rep.start()
        with pytest.raises(RuntimeError):
            rep.start()                 # double start is a bug
        fab.send("weights", 0, wmsg(11, 0, 4, 1))
        deadline = 50
        while rep.clock != 11 and deadline:
            threading.Event().wait(0.02)
            deadline -= 1
        assert rep.clock == 11
    finally:
        rep.stop()
        fab.close()


# -- sharded replica: assembled theta, frontier-stamped, never torn ----------

def shard_fabrics(root, n=2, width=4):
    fabs = []
    for i in range(n):
        fabs.append(DurableFabric(
            os.path.join(root, f"shard{i}of{n}"), CFG))
    ranges = [(i * width, (i + 1) * width) for i in range(n)]
    return fabs, ranges


def test_replica_serves_assembled_theta_from_split_deployment(tmp_path):
    """The PR 8 gap: a --shards 2 deployment cannot --serve; a replica
    following its per-shard logs serves the assembled full-range theta
    stamped with the frontier clock."""
    fabs, ranges = shard_fabrics(str(tmp_path))
    try:
        fabs[0].send("weights", 0, wmsg(5, *ranges[0], 5))
        rep = ReplicaFollower(str(tmp_path))
        assert rep.num_shards == 2
        assert rep.catch_up() == 0      # half a cut is not servable
        assert rep.registry.latest is None
        fabs[1].send("weights", 0, wmsg(7, *ranges[1], 7))
        assert rep.catch_up() == 1
        snap = rep.registry.latest
        assert snap.vector_clock == 5   # frontier = min(5, 7)
        np.testing.assert_array_equal(
            snap.theta, np.array([5] * 4 + [7] * 4, np.float32))
        # shard 0 advances: frontier moves to min(9, 7) = 7
        fabs[0].send("weights", 0, wmsg(9, *ranges[0], 9))
        assert rep.catch_up() == 1
        assert rep.registry.latest.vector_clock == 7
        # a stalled frontier never re-publishes (no duplicate cuts)
        fabs[0].send("weights", 0, wmsg(10, *ranges[0], 10))
        assert rep.catch_up() == 0
    finally:
        for f in fabs:
            f.close()


def test_sharded_replica_snapshots_never_torn_under_writers(tmp_path):
    """Concurrent shard writers + a polling replica: every snapshot the
    replica ever publishes must be a consistent cut — each shard slice
    uniform (no mid-message mixing), the stamp equal to the true
    frontier of the slices served, and frontiers strictly increasing."""
    fabs, ranges = shard_fabrics(str(tmp_path))
    stop = threading.Event()

    def writer(i):
        clock = 0
        while not stop.is_set():
            clock += 1
            # slice filled with its clock: any tear is visible
            fabs[i].send("weights", 0, wmsg(clock, *ranges[i], clock))

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    rep = ReplicaFollower(str(tmp_path))
    seen = []
    try:
        # 200 polls, and on a loaded box (the writers starved of the
        # interpreter while the replica polls) as many more as it takes
        # to see the race run, for at most 20 s
        deadline = time.monotonic() + 20.0
        polls = 0
        while polls < 200 or (len(seen) < 2 and time.monotonic() < deadline):
            polls += 1
            if rep.catch_up():
                seen.append(rep.registry.latest)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        for f in fabs:
            f.close()
    assert len(seen) >= 2               # the race actually ran
    last_frontier = -1
    for snap in seen:
        half0, half1 = snap.theta[:4], snap.theta[4:]
        assert len(set(half0.tolist())) == 1, snap.theta  # slice untorn
        assert len(set(half1.tolist())) == 1, snap.theta
        frontier = min(half0[0], half1[0])
        assert snap.vector_clock == frontier    # stamp IS the frontier
        assert frontier > last_frontier         # strictly advancing
        last_frontier = frontier


def test_replica_engine_serves_frontier_bounded_reads(tmp_path):
    """End to end in-process: engine over a replica registry answers
    min_clock reads at the frontier and rejects beyond it."""
    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.serving import StalenessError
    from kafka_ps_tpu.serving.engine import PredictionEngine
    from kafka_ps_tpu.utils.config import ModelConfig

    cfg = ModelConfig(num_features=4, num_classes=2)
    task = get_task("logreg", cfg)
    n = task.num_params
    fabs, _ = shard_fabrics(str(tmp_path), n=2, width=(n + 1) // 2)
    try:
        lo, hi = 0, (n + 1) // 2
        fabs[0].send("weights", 0, wmsg(3, lo, hi, 0.1))
        fabs[1].send("weights", 0, wmsg(4, hi, hi + (n - hi), 0.2))
        rep = ReplicaFollower(str(tmp_path))
        assert rep.catch_up() == 1
        engine = PredictionEngine(task, rep.registry)
        try:
            pred = engine.predict(np.ones(cfg.num_features, np.float32),
                                  min_clock=3)
            assert pred.vector_clock == 3
            with pytest.raises(StalenessError):
                engine.predict(np.ones(cfg.num_features, np.float32),
                               min_clock=4)
        finally:
            engine.close()
    finally:
        for f in fabs:
            f.close()
