"""Where the process runs and where it keeps compiled programs — the
two start-up facts every entry point reports (cli/run.py's hook for the
runner and the socket roles, chip_smoke.py).

Compile cache: a cold process recompiles every solver, apply, eval-width
and scatter-bucket program, and the chip tool keeps nothing between
calls except what the caller places.  `JAX_COMPILATION_CACHE_DIR` set →
JAX reads it itself and this module sets no directory in code.  Unset →
one fixed path inside the checkout (the path is part of JAX's cache key,
so a directory that moves never hits).  The minimum-compile-time
threshold drops to 0 so the many sub-second programs (apply, log
stacker, scatter buckets) are kept too.  Operation metadata (named
scopes, source lines) is part of the key: left out, as JAX's default
has it, a cache warmed by an older build hands back executables that
carry that build's metadata, and a device trace then shows none of the
`kps.*` scopes (measured on the chip, PERF.md PR 24).  The price is one
compile of a program after an edit that moves the lines it was traced
from.

Start-up record (PR 50): `STARTUP`, one dict a process, always on the
way `StreamingPSApp.last_run` is, holds what the time before the first
update went to, in `time.time()` stamps (the clock of `jax.monitoring`'s
time spans), so a reader can cut it at any instant afterwards:

  phases  `import` (the process's start to `apply_platform_env`
          returned), `backend` (from there to the first `device_summary`:
          the runtime coming up, with whatever the entry point does
          between the two) and `app_init` (`StreamingPSApp.__init__`
          whole): (name, start, end), two clock reads each; the first
          two are known only afterwards (`Tracer.span_at_wall`), the
          third is a live `setup.app_init` span as well
  marks   the first update applied, set once: where the `[startup]`
          line cuts, and from where a build is a `[build]` line
  builds  every `jax.monitoring` trace / lower / compile time span: the
          longest `KEPT_BUILDS` a kind with program name and stamps, and
          of those let go their number and seconds a kind.  A compile
          span that follows a cache hit on its thread is a `cache_load`;
          programs compiled anew and read from the cache are counted
  calls   each drive call's start stamp and seconds (`_record_run`), and
          the first call's `last_run` whole, which the line prints

Whose a build is, is read from where it lies: inside a phase or a drive
call it is the program's, elsewhere (a benchmark's reference, a data
generator) it is not.  The listeners take a lock among themselves and
nothing a chunk or a clock runs takes it.
"""

from __future__ import annotations

import contextlib
import heapq
import os
import sys
import threading
import time
from collections import deque
from importlib import metadata

_IMPORTED = time.time()

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache somewhere durable; call
    before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def compile_cache_dir() -> str:
    """The directory in use — JAX's own setting, whoever placed it."""
    import jax
    return jax.config.jax_compilation_cache_dir


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


def device_summary() -> dict:
    """The device as JAX reports it, plus the stack versions — first
    backend use happens here."""
    import jax
    t = time.time()
    devices = jax.devices()
    if not any(name == "backend" for name, _, _ in STARTUP["phases"]):
        phase_at("backend", STARTUP["env_ready"] or t, time.time())
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "jax": jax.__version__,
            "jaxlib": _version("jaxlib"),
            "libtpu": _version("libtpu")}


def startup_line(solver: str | None = None) -> str:
    d = device_summary()
    line = (f"[device] platform={d['platform']} kind={d['kind']!r} "
            f"count={d['count']} jax={d['jax']} jaxlib={d['jaxlib']} "
            f"libtpu={d['libtpu']} compile_cache={compile_cache_dir()}")
    if solver is not None:
        line += f" solver={solver}"
    return line


# -- the start-up record -------------------------------------------------------

# as a build goes, and as the `[startup]` line prints them; where two
# lie over an instant the later kind has it (`startup_split`)
BUILD_KINDS = ("trace", "lower", "compile", "cache_load")
KEPT_BUILDS, KEPT_PHASES, KEPT_CALLS, LONGEST = 256, 256, 1024, 16
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _process_start() -> float:
    """When the process began, on the wall clock: its start time in
    `/proc/self/stat` against the machine's uptime (10 ms), else this
    module's import."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            up = float(fh.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 86400.0:
            return min(_IMPORTED, time.time() - age)
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED


STARTUP = {
    "process_start": _process_start(),
    "env_ready": None,        # apply_platform_env returned
    "phases": deque(maxlen=KEPT_PHASES),     # (name, start, end)
    "marks": {"first_update": None},
    "programs": {"anew": 0, "hit": 0},
    # a heap a kind, by seconds: (seconds, program, start, end), the
    # longest KEPT_BUILDS of the kind (a traced function's inner
    # functions are events of their own, by the thousand), and of the
    # shorter ones [how many, their seconds]: what a split of an
    # interval can lack at most, a kind
    "builds": {kind: [] for kind in BUILD_KINDS},
    "dropped": {kind: [0, 0.0] for kind in BUILD_KINDS},
    "calls": deque(maxlen=KEPT_CALLS),       # (start, seconds)
    # the process's first call's `last_run` whole, and `started`: the
    # call that paid the builds, for the `[startup]` line and the
    # benchmark's table (the next call overwrites `last_run`)
    "first_call": None,
    # where a build is also reported: an enabled Tracer (`attach`), the
    # registry's two counters once the first call has returned
    "tracer": None,
    "built_total": None,
}
_BUILDS = threading.Lock()        # the listeners', among themselves
_HIT = threading.local()          # a cache hit waits for its compile span


def mark(name: str) -> None:
    """Stamp `STARTUP["marks"][name]` where it is reached first; every
    later call leaves it."""
    marks = STARTUP["marks"]
    if marks[name] is None:
        marks[name] = time.time()


def phase_at(name: str, start: float, end: float) -> None:
    """A phase from two stamps already taken.  One known only
    afterwards (`import`, `backend`) has no span of its own: no tracer
    exists yet, and `attach` hands it to the one that comes."""
    STARTUP["phases"].append((name, start, end))


@contextlib.contextmanager
def setup_phase(name: str, tracer):
    """A phase round the work itself: the span `setup.<name>` of
    `tracer` (a profiler annotation `kps.setup.<name>` always) and the
    record's two stamps."""
    start = time.time()
    try:
        with tracer.span("setup." + name):
            yield
    finally:
        phase_at(name, start, time.time())


def attach(tracer) -> None:
    """Builds and retroactive phases go to `tracer` from here on, and
    what the record already holds goes to it now: the phases and builds
    of before the tracer existed."""
    if not tracer.enabled:
        return
    STARTUP["tracer"] = tracer
    for name, start, end in list(STARTUP["phases"]):
        tracer.span_at_wall("setup." + name, start, end)
    for kind, program, start, end in _kept_builds(STARTUP):
        tracer.span_at_wall("build." + kind, start, end, program=program)


def _kept_builds(record) -> list[tuple[str, str, float, float]]:
    """(kind, program, start, end) of every build `record` keeps."""
    with _BUILDS:
        return [(kind, *build[1:]) for kind, kept in record["builds"].items()
                for build in kept]


def env_ready() -> None:
    """`apply_platform_env` returns, once a process: the interpreter
    and the imports (JAX's among them) end here, the listeners for
    `jax.monitoring`'s build events stand, and what follows until the
    device is announced is the backend's."""
    if STARTUP["env_ready"] is None:
        import jax
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_build)
        STARTUP["env_ready"] = time.time()
        phase_at("import", STARTUP["process_start"], STARTUP["env_ready"])


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _HIT.pending = True


def _on_build(event: str, start: float, end: float, fun_name: str = "?",
              **kwargs) -> None:
    kind = _BUILD_EVENTS.get(event)
    if kind is None:
        return
    # a traced function is named bare, its module `jit(<name>)`
    program = fun_name if "(" in fun_name else f"jit({fun_name})"
    seconds = end - start
    if kind == "compile" and getattr(_HIT, "pending", False):
        _HIT.pending = False
        kind = "cache_load"
    built = kind in ("compile", "cache_load")
    with _BUILDS:
        if built:
            STARTUP["programs"]["anew" if kind == "compile" else "hit"] += 1
        kept = STARTUP["builds"][kind]
        heapq.heappush(kept, (seconds, program, start, end))
        if len(kept) > KEPT_BUILDS:
            dropped = STARTUP["dropped"][kind]
            dropped[0] += 1
            dropped[1] += heapq.heappop(kept)[0]
    tracer = STARTUP["tracer"]
    if tracer is not None:
        tracer.span_at_wall("build." + kind, start, end, program=program)
    if built and STARTUP["marks"]["first_update"] is not None:
        # a program the warm path did not meet: named, as it happens
        how = "anew" if kind == "compile" else "cache"
        if STARTUP["built_total"] is not None:
            STARTUP["built_total"][how].inc()
        print(f"[build] {program} {seconds:.3f}s {how}", file=sys.stderr,
              flush=True)


def _segments(t0: float, t1: float, layers) -> list[tuple[float, float, str]]:
    """[t0, t1] cut where a class begins or ends: (start, end, class).
    `layers` is [(class, [(start, end), ...])] in order of precedence: a
    piece is the first class's that has an interval over it, and
    `other`'s under none, so the pieces tile [t0, t1]."""
    edges = []
    for rank, (_, spans) in enumerate(layers):
        for start, end in spans:
            start, end = max(start, t0), min(end, t1)
            if end > start:
                edges += [(start, rank, 1), (end, rank, -1)]
    names = [name for name, _ in layers] + ["other"]
    open_now, at, out = [0] * len(layers), t0, []
    for t, rank, step in sorted(edges) + [(t1, 0, 0)]:
        if t > at:
            top = names[next((i for i, n in enumerate(open_now) if n), -1)]
            if out and out[-1][2] == top:
                out[-1] = (out[-1][0], t, top)
            else:
                out.append((at, t, top))
            at = t
        open_now[rank] += step
    return out


def startup_split(t0: float, t1: float, record: dict | None = None) -> dict:
    """What [t0, t1] went to, by `record` (the process's): the one sweep
    behind the `[startup]` line and the benchmark's set-up table.  Each
    instant is one class's, by precedence: a build of the program's
    (cache_load > compile > lower > trace; the program's are those that
    lie in a phase or a drive call, whatever thread they ran on) >
    `import` > `backend` > `app_init` > `call` > `other`, so a phase or
    a call counts less the builds inside it.  Gives the `segments`
    (start, end, class) and their `seconds` by class, which sum to
    t1 - t0; how many `programs` were built and how many `anew`; the
    `LONGEST` programs with their seconds by kind; and the kept builds
    in the interval that are `not_the_programs`.  Of the builds the
    record let go (`record["dropped"]`) it knows nothing: their seconds
    lie under whatever phase or call they ran in."""
    record = STARTUP if record is None else record
    phases = list(record["phases"])
    calls = [(start, start + seconds) for start, seconds in record["calls"]]
    owners = [(start, end) for _, start, end in phases] + calls
    mine, others = [], []
    for build in _kept_builds(record):
        if build[2] < t1 and build[3] > t0:
            (mine if any(build[2] < end and build[3] > start
                         for start, end in owners) else others).append(build)
    layers = [(kind, [b[2:] for b in mine if b[0] == kind])
              for kind in reversed(BUILD_KINDS)]
    layers += [(name, [p[1:] for p in phases if p[0] == name])
               for name in ("import", "backend", "app_init")]
    by_program: dict[str, dict] = {}
    for kind, program, start, end in mine:
        by_program.setdefault(program, dict.fromkeys(BUILD_KINDS, 0.0))[
            kind] += end - start
    segments = _segments(t0, t1, layers + [("call", calls)])
    seconds = dict.fromkeys([name for name, _ in layers] + ["call", "other"],
                            0.0)
    for start, end, name in segments:
        seconds[name] += end - start
    return {"total": t1 - t0, "segments": segments, "seconds": seconds,
            "build": sum(seconds[kind] for kind in BUILD_KINDS),
            "programs": sum(b[0] in ("compile", "cache_load") for b in mine),
            "anew": sum(b[0] == "compile" for b in mine),
            "longest": sorted(by_program.items(),
                              key=lambda kv: -sum(kv[1].values()))[:LONGEST],
            "not_the_programs": others}


# the first call's edges in the `[startup]` line (`last_run`'s keys)
_CALL_EDGES = ("theta_up_s", "slab_refresh_s", "device_wait_s",
               "theta_down_s")


def first_update_line(split: dict, first_call: dict) -> str:
    """The ONE `[startup]` line: the total, then its parts, which sum
    to it; behind them the first call whole — the one that paid the
    builds — with the edges the next call's `last_run` overwrites."""
    s = split["seconds"]
    longest = ""
    if split["longest"]:
        program, kinds = split["longest"][0]
        longest = (f"; longest {program} {sum(kinds.values()):.3f} "
                   f"{'anew' if kinds['compile'] else 'cache'}")
    return (f"[startup] first update after {split['total']:.3f}s: "
            f"import {s['import']:.3f} backend {s['backend']:.3f} "
            f"app_init {s['app_init']:.3f} build {split['build']:.3f} ("
            + " ".join(f"{kind} {s[kind]:.3f}" for kind in BUILD_KINDS)
            + f"; {split['programs']} programs, {split['anew']} anew"
            f"{longest}) first_call {s['call']:.3f} other {s['other']:.3f}"
            f"; the {first_call['path']} call whole "
            f"{first_call['seconds']:.3f} ("
            + " ".join(f"{key[:-2]} {first_call[key]:.3f}"
                       for key in _CALL_EDGES) + ")")


def record_call(last_run: dict, telemetry) -> None:
    """A drive call has just returned (`StreamingPSApp._record_run`):
    its start stamp and seconds join the record; the process's first is
    kept whole under its start stamp and reported — the `[startup]` line
    beside `[device]`, the parts as gauges where `telemetry` is armed."""
    now = time.time()
    started = now - last_run["seconds"]
    STARTUP["calls"].append((started, last_run["seconds"]))
    if STARTUP["first_call"] is not None:
        return
    STARTUP["first_call"] = {**last_run, "started": started}
    split = startup_split(STARTUP["process_start"],
                          STARTUP["marks"]["first_update"] or now)
    print(first_update_line(split, last_run), file=sys.stderr, flush=True)
    seconds = split["seconds"]
    parts = {"total": split["total"], "first_call": seconds["call"],
             "build": split["build"],
             **{name: seconds[name]
                for name in ("import", "backend", "app_init", "other")}}
    for phase, value in parts.items():
        telemetry.gauge(
            "kps_startup_seconds", "process start to the first update "
            "applied, by what it went to", phase=phase).set(value)
    built = {how: telemetry.counter(
        "kps_programs_built_total", "programs compiled anew or read "
        "from the persistent cache", how=how) for how in ("anew", "cache")}
    built["anew"].inc(STARTUP["programs"]["anew"])
    built["cache"].inc(STARTUP["programs"]["hit"])
    STARTUP["built_total"] = built
