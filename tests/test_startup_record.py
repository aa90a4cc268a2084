"""The start-up record (`kafka_ps_tpu/utils/device.py` `STARTUP`): what
a process's time before its first update went to, kept by the program
itself — phases that are spans too, the first update's mark, `jax.monitoring`'s
builds with whose they are read from where they lie, every drive call's
stamp, the first call whole, and the one `[startup]` line.  CPU; the
record is the process's, so every test starts from an emptied one."""

import glob
import os
import re
import time
from collections import deque

import jax
import jax.numpy as jnp
import pytest

from kafka_ps_tpu.data.synth import generate
from kafka_ps_tpu.runtime.app import StreamingPSApp
from kafka_ps_tpu.telemetry import NULL_TELEMETRY, Telemetry
from kafka_ps_tpu.utils import device, trace
from kafka_ps_tpu.utils.config import BufferConfig, ModelConfig, PSConfig
from kafka_ps_tpu.utils.trace import Tracer

WORKERS = 2


@pytest.fixture
def record(monkeypatch):
    """The process's record as a process finds it after
    `apply_platform_env`: the listeners standing, nothing kept yet."""
    device.env_ready()
    fresh = {
        "phases": deque(maxlen=device.KEPT_PHASES),
        "marks": dict.fromkeys(device.STARTUP["marks"]),
        "programs": {"anew": 0, "hit": 0},
        "builds": {kind: [] for kind in device.BUILD_KINDS},
        "dropped": {kind: [0, 0.0] for kind in device.BUILD_KINDS},
        "calls": deque(maxlen=device.KEPT_CALLS),
        "first_call": None, "tracer": None, "built_total": None}
    for key, value in fresh.items():
        monkeypatch.setitem(device.STARTUP, key, value)
    return device.STARTUP


def make_app(tracer=None, telemetry=None, task="logreg", eval_every=1):
    cfg = PSConfig(
        num_workers=WORKERS, task=task, eval_every=eval_every,
        model=ModelConfig(num_features=16, num_classes=3, hidden_dim=8),
        buffer=BufferConfig(min_size=4, max_size=8))
    x, y = generate(40, 16, 3, seed=0)
    app = StreamingPSApp(cfg, test_x=x[-8:], test_y=y[-8:], tracer=tracer,
                         telemetry=telemetry)
    for i in range(8 * WORKERS):
        app.data_sink(i % WORKERS, {j: float(x[i, j]) for j in range(16)},
                      int(y[i]))
    return app


def drive(app, path="serial", updates=8):
    target = app.server.iterations + updates * WORKERS
    if path == "fused":
        app.run_fused_bsp(max_server_iterations=target)
    elif path == "threaded":
        app.run_threaded(max_server_iterations=target)
    else:
        app.run_serial(max_server_iterations=target, pump=lambda: None)


def events(tracer, prefix):
    return [e for e in tracer._events if e["name"].startswith(prefix)]


# -- phases ---------------------------------------------------------------------

def test_a_phase_is_a_span_and_adds_its_seconds_once(record):
    tracer = Tracer()
    with device.setup_phase("app_init", tracer):
        time.sleep(0.02)
        with device.setup_phase("app_init", tracer):   # a phase in a phase
            time.sleep(0.01)
    (_, start, end), = [p for p in record["phases"] if p[2] - p[1] > 0.025]
    assert len(record["phases"]) == 2
    assert len(events(tracer, "setup.app_init")) == 2
    split = device.startup_split(start - 1.0, end + 1.0)
    # the inner phase lies in the outer one: each instant counts once
    assert split["seconds"]["app_init"] == pytest.approx(end - start)
    assert sum(split["seconds"].values()) == pytest.approx(split["total"])


def test_building_the_app_is_the_app_init_phase(record):
    tracer = Tracer()
    app = make_app(tracer)
    (name, start, end), = record["phases"]
    assert name == "app_init" and end > start
    span, = events(tracer, "setup.app_init")
    assert span["dur"] == pytest.approx((end - start) * 1e6, rel=0.05)
    app.close_logs()


def test_a_profiler_session_holds_the_phase_on_the_host_plane(record,
                                                              tmp_path):
    with trace.device_trace(str(tmp_path)):
        app = make_app()
    app.close_logs()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(
                 path).planes if plane.name.startswith("/host:CPU")
             for line in plane.lines for ev in line.events}
    assert "kps.setup.app_init" in names


def test_import_and_backend_are_known_afterwards_and_once(record,
                                                          monkeypatch):
    monkeypatch.setitem(record, "env_ready", None)
    registered = []
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        registered.append)
    monkeypatch.setattr(jax.monitoring, "register_event_time_span_listener",
                        registered.append)
    device.env_ready()
    device.env_ready()                  # every later entry point's call
    assert registered == [device._on_event, device._on_build]
    device.device_summary()
    device.device_summary()
    assert [p[0] for p in record["phases"]] == ["import", "backend"]
    (_, born, ready), (_, begun, up) = record["phases"]
    assert born == record["process_start"] <= device._IMPORTED
    assert ready == begun == record["env_ready"] <= up
    # a tracer made later still gets them, where they lie on its clock
    tracer = Tracer()
    device.attach(tracer)
    at = {e["name"]: e for e in tracer._events}
    assert set(at) == {"setup.import", "setup.backend"}
    assert at["setup.import"]["ts"] < 0 < at["setup.import"]["dur"]


def test_a_wall_stamp_lands_on_the_tracers_clock():
    tracer = Tracer(clock=lambda: 50.0)
    tracer._wall0 = 1000.0              # the anchor dump() exports
    tracer.span_at_wall("setup.import", 990.0, 992.5, program="p")
    event, = tracer._events
    assert (event["ts"], event["dur"]) == (-10e6, 2.5e6)
    assert event["args"] == {"program": "p"}
    tracer.enabled = False
    tracer.span_at_wall("setup.import", 990.0, 992.5)
    assert len(tracer._events) == 1


# -- the mark -------------------------------------------------------------------

def test_a_mark_is_set_once_and_never_moves(record):
    device.mark("first_update")
    first = record["marks"]["first_update"]
    assert first is not None
    time.sleep(0.002)
    device.mark("first_update")
    assert record["marks"] == {"first_update": first}


@pytest.mark.parametrize("path", ["serial", "fused", "threaded"])
def test_a_run_marks_its_first_update_inside_its_first_call(record, path):
    app = make_app(task="mlp" if path == "fused" else "logreg",
                   eval_every=8 if path == "fused" else 1)
    assert record["marks"]["first_update"] is None    # rows are no update
    drive(app, path)
    marks = dict(record["marks"])
    (started, seconds), = record["calls"]
    assert started < marks["first_update"] <= started + seconds
    drive(app, path)
    make_app().close_logs()             # another app of the process
    assert record["marks"] == marks
    assert app.last_run["path"] == path
    assert len(record["calls"]) == 2
    app.close_logs()


# -- builds ---------------------------------------------------------------------

@pytest.fixture
def compile_cache(tmp_path):
    """The persistent compile cache on, in a directory of the test's
    (tests/conftest.py turns it off for the suite)."""
    from jax.experimental.compilation_cache import compilation_cache
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before[0])
    jax.config.update("jax_compilation_cache_dir", before[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[2])
    compilation_cache.reset_cache()


def test_compiled_anew_then_read_from_the_cache(record, compile_cache):
    @jax.jit
    def a_toy_program_of_the_record(x):
        return jnp.tanh(x) * 3.0 + 1.0

    name = "jit(a_toy_program_of_the_record)"
    x = jax.block_until_ready(jnp.ones(7))         # its own builds first
    counts = [dict(record["programs"])]
    # both passes from one line: the cache keys on the call stack too
    # (utils/device.py `configure_compile_cache`)
    for _ in ("cold", "warm"):
        jax.clear_caches()
        with device.setup_phase("app_init", trace.NULL_TRACER):
            jax.block_until_ready(a_toy_program_of_the_record(x))
        counts.append(dict(record["programs"]))
    grown = [{k: after[k] - before[k] for k in after}
             for before, after in zip(counts, counts[1:])]
    assert grown == [{"anew": 1, "hit": 0}, {"anew": 0, "hit": 1}]
    (_, cold0, cold1), (_, warm0, warm1) = record["phases"]
    cold = device.startup_split(cold0, cold1)
    warm = device.startup_split(warm0, warm1)
    assert (cold["programs"], cold["anew"]) == (1, 1)
    assert (warm["programs"], warm["anew"]) == (1, 0)
    assert cold["seconds"]["compile"] > 0 == cold["seconds"]["cache_load"]
    assert warm["seconds"]["cache_load"] > 0 == warm["seconds"]["compile"]
    kinds = dict(warm["longest"])[name]
    assert kinds["cache_load"] > 0 and kinds["trace"] > 0
    # traced, lowered and built: three kinds of one program, by one name
    assert {kind for kind in device.BUILD_KINDS
            if any(b[1] == name for b in record["builds"][kind])} == set(
                device.BUILD_KINDS)


def test_a_build_outside_every_phase_and_call_is_not_the_programs(record):
    @jax.jit
    def a_reference_of_the_benchmarks(x):
        return x * 2.0 - 1.0

    x = jax.block_until_ready(jnp.ones(5))
    t0 = time.time()
    jax.block_until_ready(a_reference_of_the_benchmarks(x))
    t1 = time.time()
    assert any(b[1] == "jit(a_reference_of_the_benchmarks)"
               for b in record["builds"]["compile"])      # kept, apart
    split = device.startup_split(t0, t1)
    assert (split["programs"], split["anew"], split["longest"]) == (0, 0, [])
    assert split["seconds"]["other"] == pytest.approx(t1 - t0)
    # the same span under a drive call is the program's
    record["calls"].append((t0, t1 - t0))
    split = device.startup_split(t0, t1)
    assert split["programs"] == split["anew"] == 1
    assert split["seconds"]["compile"] > 0


def test_the_record_keeps_the_longest_builds_of_a_kind(record, monkeypatch):
    monkeypatch.setattr(device, "KEPT_BUILDS", 4)
    event = "/jax/core/compile/jaxpr_trace_duration"
    for i in range(10):
        device._on_build(event, 100.0, 100.0 + i, fun_name=f"f{i}")
    device._on_build("/jax/other", 0.0, 9.0, fun_name="not a build")
    assert sorted(b[0] for b in record["builds"]["trace"]) == [6, 7, 8, 9]
    # the six shorter ones: their number and seconds, of their kind
    assert record["dropped"] == {**dict.fromkeys(device.BUILD_KINDS, [0, 0.0]),
                                 "trace": [6, float(sum(range(6)))]}
    assert record["programs"] == {"anew": 0, "hit": 0}   # none compiled


def test_a_tracer_gets_every_build_as_a_span(record):
    tracer = Tracer()
    device.attach(tracer)

    @jax.jit
    def a_program_for_the_tracer(x):
        return x + 2.0

    jax.block_until_ready(a_program_for_the_tracer(jnp.ones(3)))
    mine = [e for e in events(tracer, "build.")
            if e["args"]["program"] == "jit(a_program_for_the_tracer)"]
    assert {e["name"] for e in mine} == {"build.trace", "build.lower",
                                         "build.compile"}
    # a tracer that is off is not kept
    record["tracer"] = None
    device.attach(trace.NULL_TRACER)
    assert record["tracer"] is None


def test_a_build_after_the_first_update_is_a_line(record, capsys):
    @jax.jit
    def met_before_the_first_update(x):
        return x - 3.0

    @jax.jit
    def met_after_the_first_update(x):
        return x * x

    x = jax.block_until_ready(jnp.ones(3))
    met_before_the_first_update(x)
    device.mark("first_update")
    met_after_the_first_update(x)
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[build] ")]
    assert len(lines) == 1
    assert re.fullmatch(
        r"\[build\] jit\(met_after_the_first_update\) \d+\.\d{3}s anew",
        lines[0])


# -- drive calls and the first update's line -------------------------------------

def test_the_first_call_is_kept_whole_and_the_list_is_bounded(record):
    app = make_app(task="mlp", eval_every=8)
    drive(app, "fused")
    first = dict(app.last_run)
    assert record["first_call"] == {**first,
                                    "started": record["calls"][0][0]}
    assert {"theta_up_s", "slab_refresh_s", "device_wait_s", "theta_down_s",
            "seconds", "path"} <= set(record["first_call"])
    drive(app, "fused")
    assert app.last_run != first
    assert record["first_call"] == {**first, "started": record["calls"][0][0]}
    (start1, secs1), (start2, secs2) = record["calls"]
    assert secs1 == first["seconds"] and secs2 == app.last_run["seconds"]
    assert start1 + secs1 <= start2 <= time.time()
    for i in range(3 * device.KEPT_CALLS):
        device.record_call({"seconds": float(i)}, NULL_TELEMETRY)
    assert len(record["calls"]) == device.KEPT_CALLS
    assert record["calls"][-1][1] == 3.0 * device.KEPT_CALLS - 1
    app.close_logs()


LINE = re.compile(
    r"\[startup\] first update after (?P<total>[\d.]+)s: import "
    r"(?P<import>[\d.]+) backend (?P<backend>[\d.]+) app_init "
    r"(?P<app_init>[\d.]+) build (?P<build>[\d.]+) \(trace (?P<trace>[\d.]+) "
    r"lower (?P<lower>[\d.]+) compile (?P<compile>[\d.]+) cache_load "
    r"(?P<cache_load>[\d.]+); (?P<programs>\d+) programs, (?P<anew>\d+) anew"
    r"(; longest (?P<longest>\S+) [\d.]+ (anew|cache))?\) first_call "
    r"(?P<first_call>[\d.]+) other (?P<other>[\d.]+); the (?P<path>\w+) "
    r"call whole (?P<whole>[\d.]+) \(theta_up (?P<theta_up>[\d.]+) "
    r"slab_refresh (?P<slab_refresh>[\d.]+) device_wait "
    r"(?P<device_wait>[\d.]+) theta_down (?P<theta_down>[\d.]+)\)")


def test_the_startup_line_is_printed_once_and_its_parts_sum(record, capsys):
    device.phase_at("import", record["process_start"], time.time())
    app = make_app()
    capsys.readouterr()
    drive(app)
    drive(app)
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[startup]")]
    assert len(lines) == 1
    found = LINE.fullmatch(lines[0])
    assert found, lines[0]
    n = {k: float(v) for k, v in found.groupdict().items()
         if k not in ("longest", "path") and v is not None}
    parts = ("import", "backend", "app_init", "build", "first_call", "other")
    assert sum(n[p] for p in parts) == pytest.approx(n["total"], abs=0.004)
    assert sum(n[k] for k in device.BUILD_KINDS) == pytest.approx(
        n["build"], abs=0.003)
    # to the first update applied, from the process's start
    assert n["total"] == pytest.approx(
        record["marks"]["first_update"] - record["process_start"], abs=0.001)
    assert n["import"] > 0 and n["app_init"] > 0
    assert n["programs"] >= n["anew"] >= 1 and found["longest"]
    # behind the parts, the call that paid the builds, whole
    first = record["first_call"]
    assert found["path"] == first["path"] == "serial"
    assert n["whole"] == pytest.approx(first["seconds"], abs=0.001)
    assert n["whole"] >= n["first_call"]
    for edge in ("theta_up", "slab_refresh", "device_wait", "theta_down"):
        assert n[edge] == pytest.approx(first[edge + "_s"], abs=0.001)
    app.close_logs()


@pytest.mark.parametrize("classes,want", [
    # a build inside a phase comes out of the phase
    ([("compile", [(2.0, 3.0)]), ("app_init", [(1.0, 4.0)])],
     {"compile": 1.0, "app_init": 2.0, "other": 7.0}),
    # two builds at once (two threads) count once; clipped at the ends
    ([("compile", [(-5.0, 1.0), (0.5, 2.0)]), ("call", [(1.5, 20.0)])],
     {"compile": 2.0, "call": 8.0, "other": 0.0}),
    # the first class wins wherever two lie over an instant
    ([("cache_load", [(3.0, 5.0)]), ("trace", [(2.0, 6.0)]),
      ("call", [(0.0, 4.0)])],
     {"cache_load": 2.0, "trace": 2.0, "call": 2.0, "other": 4.0}),
    ([("import", [])], {"import": 0.0, "other": 10.0}),
])
def test_an_instant_is_one_classs(classes, want):
    pieces = device._segments(0.0, 10.0, classes)
    # the pieces tile the interval, no two neighbours of one class
    assert pieces[0][0] == 0.0 and pieces[-1][1] == 10.0
    assert all(a[1] == b[0] and a[2] != b[2]
               for a, b in zip(pieces, pieces[1:]))
    got = dict.fromkeys(want, 0.0)
    for start, end, name in pieces:
        got[name] += end - start
    assert got == pytest.approx(want)


# -- telemetry --------------------------------------------------------------------

def test_armed_telemetry_gets_the_gauges_and_the_counters(record):
    telemetry = Telemetry()
    app = make_app(telemetry=telemetry)
    drive(app)
    x = jax.block_until_ready(jnp.ones(2))
    text = telemetry.prometheus_text()
    for phase in ("total", "import", "backend", "app_init", "build",
                  "first_call", "other"):
        assert f'kps_startup_seconds{{phase="{phase}"}}' in text
    built = {how: float(re.search(
        rf'kps_programs_built_total{{how="{how}"}} (\S+)', text).group(1))
        for how in ("anew", "cache")}
    assert built == {"anew": record["programs"]["anew"],
                     "cache": record["programs"]["hit"]}

    @jax.jit
    def a_later_program(x):
        return x / 7.0

    a_later_program(x)                  # after the first update: counted on
    assert (f'kps_programs_built_total{{how="anew"}} {built["anew"] + 1:g}'
            in telemetry.prometheus_text())
    app.close_logs()


def test_without_telemetry_nothing_is_created(record):
    app = make_app()
    assert app.telemetry is NULL_TELEMETRY
    drive(app)
    assert record["first_call"] is not None
    assert NULL_TELEMETRY.registry.families() == {}
    assert NULL_TELEMETRY.prometheus_text().strip() == ""
    app.close_logs()
