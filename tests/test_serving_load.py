"""Serving at load (docs/SERVING.md, "Operating at load"): the load
generator, admission control / load shedding, multi-tenant fairness,
and the snapshot-ring wraparound edges of the staleness policy.

The shed tests drive the DETERMINISTIC paths — a stalled dispatch fn
so the admission queue fills on command, an injected EWMA so the
predictive shed fires without timing games — because "sheds under
load" as a wall-clock phenomenon is a load run's job
(serving/loadgen.py, `scripts/tier1.sh --load`), not a unit test's.
"""

import threading
import time

import numpy as np
import pytest

from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.serving import (OverloadedError, StalenessError, loadgen,
                                  policy)
from kafka_ps_tpu.serving.engine import PredictionEngine
from kafka_ps_tpu.serving.snapshot import (FrontierCutPublisher,
                                           SnapshotRegistry)
from kafka_ps_tpu.utils.config import ModelConfig


def make_engine(**kw):
    cfg = ModelConfig(num_features=4, num_classes=2)
    task = get_task("logreg", cfg)
    rng = np.random.default_rng(3)
    theta = rng.normal(size=task.num_params).astype(np.float32)
    registry = SnapshotRegistry()
    registry.publish(theta, vector_clock=7)
    return PredictionEngine(task, registry, **kw), cfg


def stall_dispatch(engine, hold: threading.Event, model_id: int = 0):
    """Replace the tenant's jit'd forward with one that blocks on
    `hold` — admitted requests pile up behind it deterministically.
    Pins the engine to the queued path (auto off): warmup calibrates
    the dispatch cost model, and an adaptive engine would otherwise
    bypass-serve the first request inline on the submitting thread —
    blocking the test on `hold` instead of piling up the queue."""
    engine.warmup(model_id)
    engine.auto = False
    tenant = engine._tenants[model_id]
    inner = tenant.predict

    def stalled(theta, xs):
        hold.wait(timeout=30.0)
        return inner(theta, xs)

    tenant.predict = stalled


# -- arrival processes -------------------------------------------------------

def test_poisson_arrivals_rate_and_span():
    rng = np.random.default_rng(0)
    times = loadgen.poisson_arrivals(1000.0, 2.0, rng)
    assert times[0] >= 0 and times[-1] < 2.0
    assert np.all(np.diff(times) >= 0)
    # mean rate within 10% at 2000 expected arrivals
    assert 1800 <= len(times) <= 2200


def test_bursty_arrivals_mean_preserved_on_rate_compressed():
    rng = np.random.default_rng(1)
    rate, dur = 2000.0, 2.0
    times = loadgen.bursty_arrivals(rate, dur, rng, period_s=0.5, duty=0.25)
    assert 0.9 * rate <= len(times) / dur <= 1.1 * rate
    # every arrival lands in its period's first `duty` fraction
    within = times % 0.5
    assert np.all(within <= 0.5 * 0.25 + 1e-9)
    with pytest.raises(ValueError):
        loadgen.bursty_arrivals(rate, dur, rng, duty=0.0)


# -- load loops against a real engine ----------------------------------------

def test_closed_loop_all_ok_with_percentiles():
    engine, cfg = make_engine()
    engine.warmup()
    try:
        res = loadgen.run_closed_loop(loadgen.EngineTarget(engine),
                                      cfg.num_features, concurrency=3,
                                      duration_s=0.4)
    finally:
        engine.close()
    assert res.ok == res.requests > 0
    assert res.shed == res.errors == res.stale == 0
    assert res.p50_ms is not None and res.p99_ms >= res.p50_ms
    assert res.meets(deadline_ms=10_000.0)
    assert res.offered_qps is None


def test_open_loop_honors_offered_rate_and_classifies():
    engine, cfg = make_engine()
    engine.warmup()
    try:
        res = loadgen.run_open_loop(loadgen.EngineTarget(engine),
                                    cfg.num_features, rate_qps=300.0,
                                    duration_s=0.5, concurrency=4)
        # an unsatisfiable bound classifies as stale, not error
        bound_target = loadgen.EngineTarget(
            engine, bound=policy.fresh(min_clock=10**9))
        stale = loadgen.run_open_loop(bound_target, cfg.num_features,
                                      rate_qps=200.0, duration_s=0.3,
                                      concurrency=2)
    finally:
        engine.close()
    assert res.offered_qps == 300.0
    # open loop issues the whole schedule: ~rate*duration requests
    assert 0.5 * 300 * 0.5 <= res.requests <= 1.5 * 300 * 0.5
    assert res.ok == res.requests
    assert stale.stale == stale.requests > 0 and stale.ok == 0
    assert not stale.meets(10_000.0)


def test_round_robin_target_spreads_threads():
    class Counting:
        def __init__(self):
            self.issues = 0

        def make_issue(self):
            self.issues += 1
            return lambda x: None

        def close(self):
            pass

    a, b = Counting(), Counting()
    rr = loadgen.RoundRobinTarget([a, b])
    for _ in range(4):
        rr.make_issue()
    assert (a.issues, b.issues) == (2, 2)
    with pytest.raises(ValueError):
        loadgen.RoundRobinTarget([])


def test_find_knee_brackets_capacity():
    # synthetic server: p99 blows past the deadline above 1000 qps
    def run_at(rate):
        ok = int(rate)
        return loadgen.LoadResult(
            requests=ok, ok=ok, stale=0, shed=0, errors=0,
            duration_s=1.0, achieved_qps=min(rate, 1000.0),
            p50_ms=1.0, p99_ms=2.0 if rate <= 1000.0 else 80.0,
            offered_qps=rate)

    out = loadgen.find_knee(run_at, deadline_ms=10.0, lo_qps=100.0,
                            bisect_steps=5)
    assert 800.0 <= out["knee_qps"] <= 1000.0
    assert all("p99_ms" in p for p in out["probes"])

    # floor rate already failing -> knee 0, probes still reported
    def always_bad(rate):
        return loadgen.LoadResult(requests=1, ok=0, stale=0, shed=1,
                                  errors=0, duration_s=1.0,
                                  achieved_qps=0.0, p50_ms=None,
                                  p99_ms=None, offered_qps=rate)

    out = loadgen.find_knee(always_bad, deadline_ms=10.0, lo_qps=50.0)
    assert out["knee_qps"] == 0.0 and len(out["probes"]) == 1


# -- admission control and shedding ------------------------------------------

def test_queue_limit_sheds_typed_and_recovers():
    engine, cfg = make_engine(queue_limit=2, max_batch=4, deadline_s=0.0)
    hold = threading.Event()
    stall_dispatch(engine, hold)
    x = np.zeros(cfg.num_features, np.float32)
    done = []
    try:
        sheds = 0
        for _ in range(12):
            try:
                engine.submit(x, callback=done.append)
            except OverloadedError as e:
                sheds += 1
                # the typed rejection carries the queue evidence
                assert e.queue_limit == 2 and e.queue_depth >= 2
                assert e.model_id == 0
        assert sheds > 0 and engine.stats()["sheds"] == sheds
        hold.set()                     # drain
        deadline = time.monotonic() + 10.0
        while len(done) < 12 - sheds and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(done) == 12 - sheds
        # queue drained: admission is open again
        assert engine.predict(x).label in (0, 1)
        assert engine.stats()["queue_depth"] == 0
    finally:
        hold.set()
        engine.close()


def test_predictive_shed_uses_ewma_service_time():
    engine, cfg = make_engine(queue_limit=0, max_batch=2,
                              shed_deadline_s=0.010)
    engine.warmup()
    x = np.zeros(cfg.num_features, np.float32)
    try:
        engine.predict(x)              # seeds the EWMA with a real batch
        # inject a pathological service time: every queued batch now
        # predicts 100ms >> the 10ms shed deadline
        with engine._admission:
            engine._ewma_batch_s = 0.1
        with pytest.raises(OverloadedError, match="predicted queueing"):
            engine.predict(x)
        # recovery: fast service time re-opens admission
        with engine._admission:
            engine._ewma_batch_s = 1e-5
        assert engine.predict(x).label in (0, 1)
    finally:
        engine.close()


def test_per_tenant_admission_budget_isolates_models():
    """One hot tenant filling its queue must not shed the other."""
    engine, cfg = make_engine(queue_limit=2, max_batch=4, deadline_s=0.0)
    task2 = get_task("logreg", ModelConfig(num_features=4, num_classes=2))
    reg2 = SnapshotRegistry()
    reg2.publish(np.ones(task2.num_params, np.float32), vector_clock=1)
    engine.add_model(5, task2, reg2)
    hold = threading.Event()
    stall_dispatch(engine, hold)
    stall_dispatch(engine, hold, model_id=5)
    x = np.zeros(cfg.num_features, np.float32)
    try:
        with pytest.raises(OverloadedError):
            for _ in range(6):
                engine.submit(x, model_id=0)
        # model 0 is saturated; model 5's budget is untouched
        engine.submit(x, model_id=5)
        engine.submit(x, model_id=5)
        with pytest.raises(OverloadedError) as ei:
            engine.submit(x, model_id=5)
        assert ei.value.model_id == 5
    finally:
        hold.set()
        engine.close()


def test_loadgen_ledger_classifies_shed_separately():
    engine, cfg = make_engine(queue_limit=1, max_batch=2, deadline_s=0.0)
    hold = threading.Event()
    stall_dispatch(engine, hold)
    target = loadgen.EngineTarget(engine, timeout=30.0)
    try:
        t = threading.Timer(0.3, hold.set)
        t.start()
        res = loadgen.run_closed_loop(target, cfg.num_features,
                                      concurrency=4, duration_s=0.5)
        t.join()
    finally:
        hold.set()
        engine.close()
    assert res.shed > 0                 # typed rejections, not errors
    assert res.errors == 0
    assert res.shed_rate > 0
    assert not res.meets(10_000.0)      # sheds break the SLO by definition


# -- staleness policy under snapshot-ring wraparound -------------------------

def test_min_clock_just_above_oldest_retained_serves_latest():
    reg = SnapshotRegistry(capacity=3)
    for clock in range(6):              # ring retains clocks 3, 4, 5
        reg.publish(np.full(2, float(clock)), vector_clock=clock)
    oldest = reg.snapshots()[0].vector_clock
    assert oldest == 3
    # a bound just above the oldest retained snapshot is a HIT (latest
    # satisfies it) even though the ring has wrapped past clocks 0-2
    assert reg.get(min_clock=oldest + 1).vector_clock == 5
    assert reg.get(min_clock=5).vector_clock == 5
    with pytest.raises(StalenessError):
        reg.get(min_clock=6)


def test_at_clock_exactly_at_frontier_cut():
    reg = SnapshotRegistry(capacity=4)
    pub = FrontierCutPublisher(reg)
    pub.maybe_publish([(np.full(2, 1.0), 10), (np.full(2, 2.0), 12)])
    pub.maybe_publish([(np.full(2, 3.0), 14), (np.full(2, 4.0), 12)])
    # frontiers are min(10,12)=10 and min(14,12)=12
    snap = reg.get(at_clock=10)
    assert snap.vector_clock == 10
    np.testing.assert_array_equal(snap.theta, [1.0, 1.0, 2.0, 2.0])
    snap = reg.get(at_clock=12)
    assert snap.vector_clock == 12
    np.testing.assert_array_equal(snap.theta, [3.0, 3.0, 4.0, 4.0])
    # a clock BETWEEN cuts was never published: error, not nearest-hit
    with pytest.raises(StalenessError):
        reg.get(at_clock=11)


def test_lapped_ring_raises_staleness_not_stale_hit():
    reg = SnapshotRegistry(capacity=2)
    for clock in (1, 2, 3, 4):
        reg.publish(np.full(2, float(clock)), vector_clock=clock)
    # clock 1 was served once but the ring has lapped it: an at_clock
    # audit read must FAIL (StalenessError) rather than silently
    # return a different snapshot
    with pytest.raises(StalenessError) as ei:
        reg.get(at_clock=1)
    assert ei.value.have_clock == 4
    # retained clocks still hit exactly
    assert reg.get(at_clock=3).vector_clock == 3
    assert reg.get(at_clock=4).vector_clock == 4
