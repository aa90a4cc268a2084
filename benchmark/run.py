#!/usr/bin/env python3
"""benchmark/run.py — one cell of BENCHMARK.json, one process, one JSON
line last.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration (`benchmark/configs/<config>.json`) and traffic
(`benchmark/workloads/<cell>.json`) are data; this file never names one.
Whatever depends on the family of the model is files too, which the
configuration names (`"family": "<name>"` stands for
`benchmark/families/<name>/`; without the key the files are the ones
beside this one):

    reference.py  shapes(cfg), init_params(shapes), Reference(shapes) with
                  .run(theta0, slabs, clocks, keep_every) -> (parameters
                  after each kept clock, each clock's mean loss) and
                  .evaluate(theta, test) -> {name: value}; LOG_COLUMN
                  {name: column of the program's server log};
                  param_gap(theta_prog, theta_ref, theta0, shapes);
                  CONTROLS {name: Reference keywords} for control.py
    datagen.py    make(seed, cfg, data) -> (train, test), each a tuple of
                  arrays over the items, test as StreamingPSApp takes it;
                  feed(sink, train, num_workers) through the program's
                  own data_sink; slabs(train, num_workers), what the
                  buffers then hold (control.py runs no program)
    costs.py      update(cfg), evaluation(cfg, test) -> (operations,
                  bytes), for the roofline readers
    tiny.json     the flags and data the CPU tests shrink the cells to

It builds the app the way `kafka_ps_tpu/cli/run.py` does (the CLI's own
parser, `cfg_from_args`, `StreamingPSApp` with the CSV log sinks), feeds
the buffers through `StreamingPSApp.data_sink`, and drives the app's own
loop (`run_fused_bsp` or `run_serial`): one `run_*` call to an update
count, then a device sync.  The cell's file says how the window is cut
(`window.mode`): `slices` is the run of whole fixed-size calls that fits
in `--seconds` (each a sample of the slice readers); `one_call` is a single
call sized to last `--seconds`, from two timed warm-up calls.  Everything
before the window is `setup_s`, except the reference's own time, which is
reported apart.

`correct` is decided by the family's reference (the default one: plain
float32 at `highest` precision) against the limits in the cell's file;
every number compared is printed beside its limit, and once more as the
last lines of standard error and under `compared`, last in the result.
A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import re  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PROBE_TRIES = 3       # Run.probe


def say(*parts) -> None:
    """An earlier output line (the last line is the result alone)."""
    print("[bench]", *parts, flush=True)


def load_json(*path):
    with open(os.path.join(*path)) as fh:
        return json.load(fh)


def load_cell(name: str, manifest_path: str | None = None) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration and
    traffic files, found by name, and the directory of its family's
    files.  `manifest_path` (tests only) is another manifest, whose
    files lie beside it as the benchmark's lie beside BENCHMARK.json."""
    manifest_path = os.path.abspath(manifest_path or os.path.join(
        ROOT, "BENCHMARK.json"))
    root = os.path.dirname(manifest_path)
    manifest = load_json(manifest_path)
    base = os.path.normpath(os.path.join(root, manifest["paths"][0]))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = load_json(root, cfg_entry["file"])
    family = config.get("family")
    return {"entry": entry, "manifest": manifest, "config": config,
            "traffic": load_json(base, "workloads", name + ".json"),
            "family": family,
            "family_dir": (os.path.join(base, "families", family) if family
                           else HERE)}


def cell_metrics(cell: dict, group: str) -> list[dict]:
    name = cell["entry"]["name"]
    return [m for m in cell["manifest"][group]
            if "workloads" not in m or name in m["workloads"]]


def load_module(path: str, name: str):
    """A module by its path (a family's file, a metric's reader), once
    a path: the tests and the harness then hold the same module."""
    known = sys.modules.get(name)
    if known is not None and getattr(known, "__file__", None) == path:
        return known
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module        # dataclasses look their module up
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


class Family:
    """The cell's family files as modules: `.reference`, `.datagen`,
    `.costs` (this file's docstring has what each holds)."""

    PARTS = ("reference", "datagen", "costs")

    def __init__(self, cell: dict):
        prefix = ("family_" + re.sub(r"\W", "_", cell["family"]) + "_"
                  if cell["family"] else "")
        for part in self.PARTS:
            setattr(self, part, load_module(
                os.path.join(cell["family_dir"], part + ".py"),
                prefix + part))


def relative_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def bsp_spread(rows: list[tuple[int, int]], num_workers: int) -> int:
    """Largest max-min of the workers' newest logged clocks, walking the
    worker log in file order.  Under BSP it may never pass 1."""
    newest = [None] * num_workers
    worst = 0
    for worker, clock in rows:
        newest[worker] = clock
        seen = [c for c in newest if c is not None]
        if len(seen) == num_workers:
            worst = max(worst, max(seen) - min(seen))
    return worst


def read_log(path: str) -> tuple[list[str], list[list[str]]]:
    """A CSV log of the program's: (its header's columns, its rows)."""
    with open(path) as fh:
        lines = [ln.rstrip("\n").split(";") for ln in fh]
    return lines[0], lines[1:]


def cli_flags(cell: dict, shrink: dict | None) -> list[str]:
    """The cell's command line: the deployment's flags, then the
    traffic's.  `shrink` (tests only) replaces values by flag name."""
    flags = list(cell["config"]["flags"]) + list(cell["traffic"]["flags"])
    for i, word in enumerate(flags[:-1]):
        if shrink and word in shrink:
            flags[i + 1] = str(shrink[word])
    return flags + ["-p", "0", "-l"]


class Compiles:
    """Programs built since the last `take()`: all of them (compiled, or
    loaded from the persistent cache), and those compiled anew.
    jax.monitoring's own events: the one that spans the compiler or the
    cache's read, and before it, on the same thread, the cache's hit."""

    def __init__(self):
        import jax
        self._built = self._anew = 0
        self._hit_on: set[int] = set()       # threads with a hit pending
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_built)

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self._hit_on.add(threading.get_ident())

    def _on_built(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            with self._lock:
                self._built += 1
                if threading.get_ident() in self._hit_on:
                    self._hit_on.discard(threading.get_ident())
                else:
                    self._anew += 1

    def take(self) -> tuple[int, int]:
        with self._lock:
            taken = self._built, self._anew
            self._built = self._anew = 0
        return taken


class Run:
    """One run of one cell.  Attributes are what the layer-metric
    readers see (benchmark/layer_metrics/<metric>.py `read(run, spec)`)."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 shrink: dict | None = None, shrink_data: dict | None = None,
                 break_step=None, trace_layout: dict | None = None):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed, seconds,
                                                          trace)
        self.traffic = cell["traffic"]
        self.drive_name = self.traffic["drive"]
        self.mode = self.traffic["window"]["mode"]
        self.flags = cli_flags(cell, shrink)
        self.data = dict(cell["config"]["data"], **(shrink_data or {}))
        self.break_step = break_step      # tests only: sabotage hook
        # one long call is profiled from beside it: no annotation of the
        # harness's is whole in such a trace (trace_reduce.py)
        self.trace_cfg = dict(
            load_json(HERE, "trace.json"),
            window_from=("device_ops" if self.mode == "one_call"
                         else "annotations"),
            **(trace_layout or {}))
        self.reference_s = 0.0
        self.compared: dict[str, dict] = {}   # every number beside its limit
        self.call_times: list[float] = []     # one per call of the window
        self.call_builds: list[int] = []      # programs built in each
        self.call_clocks = 0                  # clocks a window call asks for
        self.window_compiles = 0
        self.trace_dir = None
        self.trace_summary = None
        # updates applied while the profiler ran, where the harness knows
        # them exactly (slices: whole calls between two device syncs)
        self.traced_updates = 0

    # -- set-up --------------------------------------------------------------

    def build(self) -> None:
        """The app, the way cli/run.py builds it, in a directory of its
        own (the CSV sinks write there)."""
        import jax

        from kafka_ps_tpu.cli import run as cli
        from kafka_ps_tpu.runtime.app import StreamingPSApp
        from kafka_ps_tpu.utils.csvlog import (CsvLogSink, SERVER_HEADER,
                                               WORKER_HEADER)

        self.args = args = cli.build_parser().parse_args(self.flags)
        self.cfg = cfg = cli.cfg_from_args(args)
        self.workers = cfg.num_workers
        self.family = family = Family(self.cell)
        self.shapes = family.reference.shapes(cfg)
        cli.announce_device(cfg, fused=args.fused)

        t = time.time()
        train, self.test = family.datagen.make(self.seed, cfg, self.data)
        say(f"data: {len(train[0])} train + {len(self.test[0])} test items "
            f"from seed {self.seed} in {time.time() - t:.2f}s")

        self.run_dir = tempfile.mkdtemp(prefix="kps-bench-")
        self.server_csv = os.path.join(self.run_dir, "logs-server.csv")
        self.worker_csv = os.path.join(self.run_dir, "logs-worker.csv")
        self.sinks = (CsvLogSink(self.server_csv, SERVER_HEADER),
                      CsvLogSink(self.worker_csv, WORKER_HEADER))
        self.app = StreamingPSApp(cfg, test_x=self.test[0],
                                  test_y=self.test[1],
                                  server_log=self.sinks[0],
                                  worker_log=self.sinks[1])
        self.mesh = None
        if args.fused and args.remote:
            from kafka_ps_tpu.parallel import multihost
            multihost.initialize()        # unconfigured: one process
            self.mesh = multihost.global_worker_mesh()
            if self.workers % self.mesh.devices.size:
                raise SystemExit(f"{self.workers} workers do not divide "
                                 f"over {self.mesh.devices.size} devices")
        t = time.time()
        family.datagen.feed(self.app.data_sink, train, self.workers)
        say(f"fed {len(train[0])} items through StreamingPSApp.data_sink in "
            f"{time.time() - t:.2f}s; buffers hold "
            f"{sorted({b.count for b in self.app.buffers})} rows")
        self.slabs = [b.snapshot() for b in self.app.buffers]
        self.devices = (list(self.mesh.devices.flat) if self.mesh is not None
                        else jax.devices()[:1])
        # a fused call is a whole number of the app's scan chunks
        self.chunk_clocks = (self.app.FUSED_CHUNK_ROUNDS
                             if self.drive_name == "fused" else 1)
        if self.break_step is not None:
            self.break_step(self)

    def drive(self, clocks: int) -> float:
        """One call of the app's own loop for `clocks` clocks, then the
        device drained; the seconds it took."""
        import jax
        t = time.time()
        app = self.app
        target = app.server.iterations + clocks * self.workers
        with self._note("bench.run_slice"):
            if self.drive_name == "fused":
                app.run_fused_bsp(max_server_iterations=target,
                                  mesh=self.mesh)
            elif self.drive_name == "serial":
                app.run_serial(max_server_iterations=target,
                               pump=lambda: None)
            else:
                raise SystemExit(f"unknown drive {self.drive_name!r}")
        with self._note("bench.sync"):
            jax.block_until_ready(app.server.theta)
        return time.time() - t

    def _note(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- the first clocks against the reference --------------------------------

    def check_first_clocks(self) -> None:
        """The first `check.clocks` clocks through the app from its
        initial parameters, by the window's own call, against as many
        reference rounds on the same slabs."""
        import numpy as np

        reference = self.family.reference
        chk = self.traffic["check"]
        clocks, stride = chk["clocks"], chk["stride_clocks"]
        t = time.time()
        ref = self.reference = reference.Reference(self.shapes)
        theta0 = np.asarray(reference.init_params(self.shapes))
        # the parameters after every `stride` clocks, the ones compared,
        # and no others: a large family cannot keep a copy a clock
        ref_thetas, ref_losses = ref.run(theta0, self.slabs, clocks,
                                         keep_every=stride)
        self.reference_s += time.time() - t
        self.live_before_program = self.live_peak()
        say(f"reference: {clocks} clocks x {self.workers} workers in "
            f"{time.time() - t:.2f}s (not in setup_s); live-buffer peak so "
            f"far {self.live_before_program} bytes")

        prog0 = np.asarray(self.app.server.theta)
        self.number("init_theta_max_abs_gap",
                    float(np.max(np.abs(prog0 - theta0))),
                    chk["limits"]["init_theta_max_abs_gap"])
        worst = 0.0
        for kept in ref_thetas:
            self.drive(stride)
            worst = max(worst, reference.param_gap(
                np.asarray(self.app.server.theta), kept, theta0,
                self.shapes))
        self.number("delta_norm_gap", worst, chk["limits"]["delta_norm_gap"])
        losses = self.logged_losses()
        gaps = [relative_gap(losses.get(c, math.nan),
                             ref_losses[c - self.first_clock()])
                for c in range(self.first_clock(),
                               self.first_clock() + clocks)]
        self.number("loss_gap", max(gaps), chk["limits"]["loss_gap"])

    def first_clock(self) -> int:
        """The vector clock of the first round's worker rows: the fused
        loop logs the clock a round ends on, the per-node loop the clock
        of the weights it started from."""
        return 1 if self.drive_name == "fused" else 0

    def worker_rows(self) -> list[list[str]]:
        return read_log(self.worker_csv)[1]

    def logged_losses(self) -> dict[int, float]:
        """Mean over the workers of each clock's logged loss."""
        by_clock: dict[int, list[float]] = {}
        for row in self.worker_rows():
            by_clock.setdefault(int(row[2]), []).append(float(row[3]))
        return {c: sum(v) / len(v) for c, v in by_clock.items()
                if len(v) == self.workers}

    def number(self, name: str, value: float, limit: float) -> None:
        self.compared[name] = {"value": value, "limit": limit,
                               "ok": value <= limit}      # nan fails
        say(self.compare_line(name))

    def compare_line(self, name: str) -> str:
        c = self.compared[name]
        return (f"compare {name} = {c['value']!r} limit {c['limit']!r} "
                f"{'ok' if c['ok'] else 'FAIL'}")

    @property
    def faults(self) -> list[str]:
        return [name for name, c in self.compared.items() if not c["ok"]]

    # -- warm-up and the window ----------------------------------------------

    def warm_up_slices(self, compiles: Compiles) -> None:
        """Whole slices through the window's own call until
        `clean_slices` in a row build no program, under a time cap.
        Which programs a slice meets follows thread timing (the eval
        engine batches whatever is pending, the log sinks fetch whatever
        is ready): nothing is built here that a slice does not reach by
        itself, and what the window still meets, `compiles_in_window`
        reports."""
        w = self.traffic["window"]
        self.call_clocks = w["slice_clocks"]
        cap, want = w["warmup_cap_s"], w["clean_slices"]
        t, n, clean, built_all = time.time(), 0, 0, 0
        compiles.take()
        while clean < want:
            self.drive(self.call_clocks)
            n += 1
            built = compiles.take()[0]
            built_all += built
            clean = 0 if built else clean + 1
            if time.time() - t > cap:
                say(f"WARM-UP HIT ITS CAP of {cap}s after {n} slices: the "
                    f"last slice still built {built} programs")
                break
        say(f"warm-up: {n} slices of {self.call_clocks} clocks in "
            f"{time.time() - t:.2f}s built {built_all} programs; the last "
            f"{clean} built none")

    def probe(self, chunks: int, compiles: Compiles) -> float:
        """The seconds a warm call of `chunks` scan chunks takes.  What
        a process with a cold compile cache pays once is not a call's
        cost: a call in which a program was compiled anew is made again,
        at most PROBE_TRIES times, so the first run in a checkout sizes
        its window as the later ones do, and a run that finds every
        program in the cache makes each probe once.  Every call is
        printed."""
        for attempt in range(1, PROBE_TRIES + 1):
            compiles.take()
            took = self.drive(chunks * self.chunk_clocks)
            built, anew = compiles.take()
            say(f"warm-up: probe call {attempt} of {chunks} chunks took "
                f"{took:.4f}s and built {built} programs, {anew} of them "
                f"compiled anew: {'not a warm call' if anew else 'timed'}")
            if not anew:
                break
        return took

    def size_one_call(self, compiles: Compiles) -> None:
        """Two timed warm calls of different length give a call's fixed
        cost (every run_fused_bsp call snapshots and uploads the slabs
        anew) and its cost per scan chunk; the window's one call is the
        whole number of chunks that then lasts `--seconds`."""
        w = self.traffic["window"]
        few, many = w["probe_chunks"]
        t_few = self.probe(few, compiles)
        t_many = self.probe(many, compiles)
        # at least a quarter of the longer call is taken to be chunks:
        # a difference lost in the clock's noise must not size the window
        per_chunk = max((t_many - t_few) / (many - few),
                        t_many / (4 * many))
        fixed = max(t_few - few * per_chunk, 0.0)
        chunks = max(1, int((self.seconds - fixed) / per_chunk))
        self.call_clocks = chunks * self.chunk_clocks
        say(f"warm-up: calls of {few} and {many} chunks took {t_few:.4f}s "
            f"and {t_many:.4f}s: {fixed:.4f}s a call + {per_chunk:.4f}s a "
            f"chunk of {self.chunk_clocks} clocks, so the window is one "
            f"call of {chunks} chunks")

    def window(self, compiles: Compiles) -> None:
        if self.mode == "one_call":
            self.size_one_call(compiles)
        else:
            self.warm_up_slices(compiles)
        self.updates_before = self.app.server.iterations
        self.theta_before = self.app.server.theta
        compiles.take()
        self.setup_s = time.time() - T0 - self.reference_s
        start = time.time()
        if self.mode == "one_call":
            tracer = self._trace_from_a_thread() if self.trace else None
            self.call_times.append(self.drive(self.call_clocks))
            self.call_builds.append(compiles.take()[0])
            if tracer is not None:
                tracer.join()
        else:
            self._slices(start, compiles)
        self.window_s = time.time() - start
        # what the calls built, and what was built between them
        self.window_compiles = sum(self.call_builds) + compiles.take()[0]
        self.updates_asked = (len(self.call_times) * self.call_clocks
                              * self.workers)
        self.updates_applied = (self.app.server.iterations
                                - self.updates_before)

    def _slices(self, start: float, compiles: Compiles) -> None:
        """Whole slices while the next one still fits.  A traced run
        profiles the slices from `start_after_s` until `seconds` have
        passed; the profiler's start and stop are not part of a slice."""
        cfg, tracing, last = self.trace_cfg, False, start
        while True:
            longest = max(self.call_times[-5:], default=0.0)
            if last - start + longest > self.seconds and self.call_times:
                break
            if (self.trace and not tracing and self.trace_dir is None
                    and last - start >= cfg["start_after_s"]):
                self._start_trace()
                tracing = True
            took = self.drive(self.call_clocks)
            self.call_times.append(took)
            self.call_builds.append(compiles.take()[0])
            last = time.time()
            if tracing:
                self.traced_updates += self.call_clocks * self.workers
                if last - self.trace_t0 >= cfg["seconds"]:
                    self._stop_trace()
                    tracing = False
                    last = time.time()
        if tracing:
            self._stop_trace()

    def _trace_from_a_thread(self) -> threading.Thread:
        """The window is one call, so the profiler is started and stopped
        beside it: `seconds` of the steady state, from `start_after_s`
        into the call.  The harness's annotations opened before that are
        not in such a trace; its window runs from the first to the last
        device operation (benchmark/trace_reduce.py)."""
        cfg = self.trace_cfg

        def profile():
            time.sleep(cfg["start_after_s"])
            self._start_trace()
            time.sleep(cfg["seconds"])
            self._stop_trace()
        thread = threading.Thread(target=profile, name="bench-profiler")
        thread.start()
        return thread

    def _start_trace(self) -> None:
        import jax
        self.trace_dir = os.path.join(self.run_dir, "profile")
        app = self.app
        flush = app.flush_logs

        def noted_flush():
            with self._note("bench.flush_logs"):
                flush()
        app.flush_logs = noted_flush      # observed, not altered
        # the Python call tracer is off: it hooks every call of the
        # per-node runtime, and nothing here reads its events (the
        # annotations are TraceMe events, which stay)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.trace_t0 = time.time()

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()
        del self.app.flush_logs

    # -- after the window ------------------------------------------------------

    def check_after(self) -> None:
        import numpy as np

        lim = self.traffic["check"]["limits"]
        self.number("updates_not_applied",
                    float(self.updates_asked - self.updates_applied), 0.0)
        clocks_now = self.app.server.iterations // self.workers
        every = self.cfg.eval_every
        first = self.first_clock()
        owed = [c for c in range(first, clocks_now + first) if c % every == 0]
        columns, server = read_log(self.server_csv)
        got = [int(r[2]) for r in server]
        self.number("eval_rows_missing_or_extra",
                    float(len(set(owed) ^ set(got)) + len(got)
                          - len(set(got))), 0.0)
        values = [float(v) for r in server for v in r[3:6]]
        wrows = self.worker_rows()
        values += [float(r[3]) for r in wrows]
        self.number("non_finite_log_values",
                    float(sum(not math.isfinite(v) for v in values)), 0.0)
        self.number("worker_rows_missing",
                    float(clocks_now * self.workers - len(wrows)), 0.0)
        spread = bsp_spread([(int(r[1]), int(r[2])) for r in wrows],
                            self.workers)
        self.number("clock_spread_over_bsp_bound", float(max(0, spread - 1)),
                    0.0)
        tracker = self.app.server.tracker
        ends = {tracker.tracker[w].vector_clock
                for w in tracker.active_workers}
        self.number("final_clock_spread", float(max(ends) - min(ends)), 0.0)

        theta = np.asarray(self.app.server.theta)
        moved = float(np.linalg.norm(theta - np.asarray(self.theta_before)))
        self.number("window_left_theta_unchanged", float(moved == 0.0), 0.0)
        t = time.time()
        want = self.reference.evaluate(theta, self.test)
        self.reference_s += time.time() - t
        # each evaluated number the cell's file gives a limit, against
        # the last server row's column of it: the loss by its relative
        # gap, the others (shares of the test set) by their absolute one
        column_of = self.family.reference.LOG_COLUMN
        for name, limit in lim.items():
            found = re.fullmatch(r"final_eval_(.+)_gap", name)
            if not found:
                continue
            key = found.group(1)
            logged = float(server[-1][columns.index(column_of[key])])
            self.number(name, (relative_gap(logged, want[key])
                               if key == "loss"
                               else abs(logged - want[key])), limit)

    def live_peak(self) -> int:
        """Peak of live buffers on the fullest chip, as the backend
        reports it (`memory_stats()["peak_bytes_in_use"]`)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def memory(self) -> dict:
        """What the fullest chip had to hold, in two parts that are
        reported apart.  `live`: the backend's peak of live buffers over
        the process.  `scratch`: the compiler's temporary allocation
        (`temp_size_in_bytes`) of the executable the window drives: on
        this runtime `peak_bytes_in_use` leaves it out (a program with
        1 GiB of scratch moved the peak by 0.002 GB, chip probe, PERF.md
        PR 23), yet the chip holds it whenever that program runs.  The
        window's programs are named in the cell's file
        (`window_programs`, the XLA module names a traced run shows);
        among the backend's live executables of those names the largest
        scratch counts, and every match is printed.  `memory_peak_bytes`
        is the sum: an upper estimate, since the two peaks need not
        fall at the same instant."""
        import jax.extend.backend
        patterns = [re.compile(p) for p in self.traffic["window_programs"]]
        found = []
        for exe in jax.extend.backend.get_backend().live_executables():
            for module in exe.hlo_modules():
                if any(p.search(module.name) for p in patterns):
                    found.append((
                        int(exe.get_compiled_memory_stats()
                            .temp_size_in_bytes), module.name))
        scratch, module = max(found, default=(0, "none"))
        live = self.live_peak()
        say(f"memory: live-buffer peak {live} bytes over the process "
            f"({self.live_before_program} before the program's first call) "
            f"+ scratch {scratch} bytes of {module}, the largest of the "
            f"window's programs {sorted(set(found), reverse=True)[:6]} = "
            f"{live + scratch}")
        return {"memory_peak_bytes": live + scratch,
                "memory_live_peak_bytes": live,
                "memory_scratch_bytes": scratch}

    def close(self) -> None:
        try:
            self.app.close_logs()
            for sink in self.sinks:
                sink.close()
        finally:
            shutil.rmtree(self.run_dir, ignore_errors=True)

    # -- metrics ---------------------------------------------------------------

    def end_to_end(self) -> dict:
        per_clock_ms = sorted(1e3 * s / self.call_clocks
                              for s in self.call_times)
        p95 = per_clock_ms[min(len(per_clock_ms) - 1,
                               math.ceil(0.95 * len(per_clock_ms)) - 1)]
        say(f"window: {len(self.call_times)} call(s) of "
            f"{self.call_clocks} clocks x {self.workers} "
            f"workers in {self.window_s:.4f}s; clock ms median "
            f"{statistics.median(per_clock_ms):.4f} p95 {p95:.4f} max "
            f"{per_clock_ms[-1]:.4f} (n={len(per_clock_ms)}); compiles in "
            f"window {self.window_compiles}; reference {self.reference_s:.2f}s")
        if len(self.call_times) > 1:
            say("slices ms:", " ".join(
                f"{1e3 * s:.1f}" + (f"[built {b}]" if b else "")
                for s, b in zip(self.call_times, self.call_builds)))
        values = {"updates_per_s": self.updates_applied / self.window_s,
                  "setup_s": self.setup_s}
        return {m["name"]: values[m["name"]]
                for m in cell_metrics(self.cell, "end_to_end")}

    def per_layer(self) -> dict:
        """Each per-layer metric through its own reader; a reader that
        finds nothing to read returns None and the metric is left out."""
        out = {}
        for metric in cell_metrics(self.cell, "per_layer"):
            name = metric["name"]
            spec = load_json(HERE, "layer_metrics", name + ".json")
            module = load_module(
                os.path.join(HERE, "layer_metrics", name + ".py"),
                "layer_metric_" + re.sub(r"\W", "_", name))
            value = module.read(self, spec)
            if value is not None:
                out[name] = value
        return out


def find_devices(chips: int, platform: str):
    """The accelerator, or exit 2 with no result."""
    import jax
    devices = jax.devices()
    found = devices[0].platform
    if found != platform or len(devices) < chips:
        print(f"benchmark/run.py: the cell needs {chips} {platform} "
              f"device(s); JAX found {len(devices)} x {found!r} — nothing "
              "was run", file=sys.stderr)
        raise SystemExit(2)
    return devices


def main(argv=None, *, platform: str = "tpu", shrink: dict | None = None,
         shrink_data: dict | None = None, break_step=None,
         trace_layout: dict | None = None,
         manifest: str | None = None) -> int:
    """`platform`, `shrink`, `shrink_data`, `break_step`, `trace_layout`
    and `manifest` are for benchmark/tests only; the command line cannot
    set them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ns = ap.parse_args(argv)

    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    cell = load_cell(ns.workload, manifest)
    try:
        from kafka_ps_tpu.cli import run as cli
    except ImportError as e:
        print(f"benchmark/run.py: the system under test is not in this "
              f"directory ({e})", file=sys.stderr)
        return 3
    cli.apply_platform_env()      # KPS_PLATFORM + the compile cache
    import jax
    devices = find_devices(cell["entry"]["chips"], platform)
    compiles = Compiles()
    say(f"cell {ns.workload} seed {ns.seed} seconds {ns.seconds} trace "
        f"{ns.trace}; compile cache {jax.config.jax_compilation_cache_dir}")

    run = Run(cell, ns.seed, ns.seconds, bool(ns.trace), shrink, shrink_data,
              break_step, trace_layout)
    run.build()
    try:
        run.check_first_clocks()
        run.window(compiles)
        run.check_after()
        memory = run.memory()
        if ns.trace:
            import trace_reduce
            run.trace_summary = trace_reduce.reduce_dir(
                run.trace_dir, run.trace_cfg, chips=len(run.devices))
            say("trace:", json.dumps(run.trace_summary["printed"]))
            metrics = run.per_layer()
            run.end_to_end()
        else:
            metrics = run.end_to_end()
    finally:
        run.close()

    units = {m["name"]: m["unit"]
             for group in ("end_to_end", "per_layer")
             for m in cell["manifest"][group]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), **memory}
    result = {"correct": not run.faults,
              "attempted": run.updates_asked,
              "failed": run.updates_asked - run.updates_applied,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": device}
    if ns.trace:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = run.trace_summary["breakdown"]
    if run.faults:
        say("NOT CORRECT:", ", ".join(run.faults))
    # each number compared beside its limit: last on standard error,
    # and last in the result's line
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in run.compared.items()}
    for name in run.compared:
        print(run.compare_line(name), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
