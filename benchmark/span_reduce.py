"""From the same profiler trace to what the program's own spans say:
which span of the dispatching thread each idle instant of the device
falls under, and which named scope each device operation of the solver
programs belongs to.

The program opens a `jax.profiler.TraceAnnotation` named `kps.<name>`
for every `Tracer.span` (kafka_ps_tpu/utils/trace.py), so its spans lie
on the host plane of the `.xplane.pb`, on the profiler's clock, one line
a thread.  A program that opens none (the parent of the PR that brought
them) leaves every reader here with nothing to read: the attributed
shares are left out, and all idle time is unattributed.

  * window and gaps are `device_idle_share`'s: the window of
    trace_reduce.reduce (`window_from`), device operations clipped to
    it, a gap wherever no operation runs on a chip; the seconds are
    averaged over the chips used, as `busy_s` is;
  * the dispatching thread is the host line that holds the most events
    named in `dispatch_markers` (the harness's `bench.run_slice`, or the
    program's own step span where the trace was cut out of one call);
  * each idle instant goes to the innermost span open on that line at
    that instant; spans of other lines attribute nothing and are listed.
"""

from __future__ import annotations

import bisect
import json
import os
import re

from trace_reduce import find_xplane, load, merged, module_name, read_planes

HERE = os.path.dirname(os.path.abspath(__file__))
NO_SPAN = ""


def spec() -> dict:
    with open(os.path.join(HERE, "span_reduce.json")) as fh:
        return json.load(fh)


def host_lines(data, cfg: dict, prefixes: tuple[str, ...]) -> list[dict]:
    """[{"line": name, "events": [(name, s, e)]}] for every host-plane
    line that holds an event whose name starts with one of `prefixes`."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith(cfg["host_plane_prefix"]):
            continue
        for i, line in enumerate(plane.lines):
            events = [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in line.events if ev.name.startswith(prefixes)]
            if events:       # thread names repeat ("python"): number them
                out.append({"line": f"{line.name}#{i}", "events": events})
    return out


def dispatch_line(lines: list[dict], markers: list[str]) -> dict | None:
    """The line with the most marker events, or None."""
    def count(line):
        return sum(name in markers for name, _, _ in line["events"])
    best = max(lines, key=count, default=None)
    return best if best is not None and count(best) else None


def innermost_timeline(spans) -> list[tuple[float, float, str]]:
    """Spans of one thread (they nest) flattened to disjoint segments
    (s, e, name), each named after the innermost span open in it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        open_now = [(e - s, name) for name, s, e in spans if s <= mid < e]
        if open_now:
            out.append((lo, hi, min(open_now)[1]))
    return out


def share_out(gaps, timeline) -> dict[str, float]:
    """Seconds of `gaps` (disjoint, sorted) under each segment name of
    `timeline` (disjoint, sorted); what no segment covers is NO_SPAN."""
    out: dict[str, float] = {}
    starts = [s for s, _, _ in timeline]
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(timeline) and timeline[i][0] < b:
            s, e, name = timeline[i]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a) - covered
    return out


def window_and_ops(planes: dict, cfg: dict, chips: int):
    """(t0, t1, [per chip used: operations clipped to the window]) —
    the window rule of trace_reduce.reduce."""
    devices = [d for d in planes["devices"] if d["ops"]][:chips]
    if planes["host"] and cfg.get("window_from",
                                  "annotations") == "annotations":
        t0 = min(s for _, s, _ in planes["host"])
        t1 = max(e for _, _, e in planes["host"])
    else:
        t0 = min(s for d in devices for _, s, _ in d["ops"])
        t1 = max(e for d in devices for _, _, e in d["ops"])
    clipped = [[(n, max(s, t0), min(e, t1)) for n, s, e in d["ops"]
                if e > t0 and s < t1] for d in devices]
    return t0, t1, clipped


def idle_by_span(data, cfg: dict, chips: int, what: dict) -> dict:
    """{"window_s", "idle_s", "by_span_s": {span name or NO_SPAN: s},
    "dispatch_line", "other_lines": {line: {span: seconds open}}}."""
    planes = read_planes(data, cfg)
    t0, t1, per_chip = window_and_ops(planes, cfg, chips)
    prefix = what["span_prefix"]
    lines = host_lines(data, cfg, (prefix, cfg["host_annotation_prefix"]))
    main = dispatch_line(lines, what["dispatch_markers"])
    spans = [ev for ev in (main["events"] if main else [])
             if ev[0].startswith(prefix)]
    timeline = innermost_timeline(spans)
    by_span: dict[str, float] = {}
    for ops in per_chip:
        busy = merged([(s, e) for _, s, e in ops])
        edges = [(t0, t0)] + busy + [(t1, t1)]
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
                if s1 > e0]
        for name, secs in share_out(gaps, timeline).items():
            by_span[name] = by_span.get(name, 0.0) + secs / len(per_chip)
    others = {}
    for line in lines:
        if line is main:
            continue
        table: dict[str, float] = {}
        for name, s, e in line["events"]:
            if name.startswith(prefix):
                table[name] = table.get(name, 0.0) + (e - s)
        if table:
            others[line["line"]] = table
    return {"window_s": t1 - t0, "idle_s": sum(by_span.values()),
            "by_span_s": by_span, "spans_on_dispatch_line": len(spans),
            "dispatch_line": main["line"] if main else None,
            "other_lines": others}


# -- named scopes of device operations --------------------------------------

def scope_of(op_name: str, scopes: list[str]) -> str:
    """The first of `scopes` (the order decides between nested ones)
    that the operation's `op_name` metadata lies under, or NO_SPAN."""
    for scope in scopes:
        if scope in op_name:
            return scope
    return NO_SPAN


def op_names_from_hlo(text: str) -> dict[str, str]:
    """{instruction name: op_name metadata} from an HLO module's text.
    A fusion instruction carries the metadata of its root."""
    out = {}
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?"
            r"op_name=\"([^\"]*)\"", text, re.M):
        out[m.group(1)] = m.group(2)
    return out


def executables_op_names(patterns: list[str]) -> dict[str, list[dict]]:
    """{module name: [{instruction: op_name}, one per executable]} for
    the backend's live executables whose module name matches one of
    `patterns` (the way Run.memory reaches them)."""
    import jax.extend.backend
    found = [re.compile(p) for p in patterns]
    out: dict[str, list[dict]] = {}
    for exe in jax.extend.backend.get_backend().live_executables():
        for module in exe.hlo_modules():
            if any(p.search(module.name) for p in found):
                out.setdefault(module.name, []).append(
                    op_names_from_hlo(module.to_string()))
    return out


def instruction_name(event_name: str) -> str:
    """`%fusion.84 = f32[...] fusion(...)` -> `fusion.84`: the device
    plane names an operation by its whole HLO instruction."""
    return event_name.lstrip("%").split(" ", 1)[0]


def device_op_events(data, cfg: dict):
    """Chip 0's operations and its program runs:
    ([(name, s, e)], [(module, s, e)])."""
    planes = read_planes(data, cfg)
    for dev in planes["devices"]:
        if dev["ops"]:
            return dev["ops"], [(module_name(n), s, e)
                                for n, s, e in dev["modules"]]
    return [], []


def seconds_by_scope(data, cfg: dict, scopes: list[str], module_patterns,
                     hlo_op_names: dict[str, list[dict]]) -> dict | None:
    """Device seconds of the leaf operations (no loop, call or
    conditional: those cover their bodies) of the matching programs on
    chip 0, by named scope: {"by_scope_s", "programs_s"}.  An
    operation's scope comes from the `op_name` metadata of its
    instruction in the program's own HLO text (`hlo_op_names`; where
    several executables share a module name, the one that names the
    most of the traced operations).  The profiler keeps that metadata
    with the event's metadata, which jax.profiler.ProfileData does not
    hand out, so no statistic of the trace is read.  None where no
    operation lies under any of `scopes`: the program carries none (the
    parent of the PR that brought them)."""
    ops, modules = device_op_events(data, cfg)
    wanted = [re.compile(p) for p in module_patterns]
    runs = sorted((s, e, m) for m, s, e in modules
                  if any(p.search(m) for p in wanted))
    if not runs or not ops:
        return None
    starts = [s for s, _, _ in runs]
    container = re.compile(cfg["container_op_pattern"])
    by_module: dict[str, list] = {}
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1] and not container.search(name):
            by_module.setdefault(runs[i][2], []).append(
                (instruction_name(name), e - s))
    by_scope: dict[str, float] = {}
    for module, leaves in by_module.items():
        names = {n for n, _ in leaves}
        table = max(hlo_op_names.get(module, [{}]),
                    key=lambda t: len(names & t.keys()))
        for name, secs in leaves:
            scope = scope_of(table.get(name, ""), scopes)
            by_scope[scope] = by_scope.get(scope, 0.0) + secs
    if not any(by_scope):
        return None
    return {"by_scope_s": by_scope,
            "programs_s": sum(e - s for s, e, _ in runs)}


# -- one reduction a run, shared by the metrics' readers ---------------------

def trace_data(run):
    """The run's trace, loaded once (each reader is a module of its
    own).  None where the run has none."""
    if getattr(run, "trace_dir", None) is None:
        return None
    if getattr(run, "span_trace_data", None) is None:
        run.span_trace_data = load(find_xplane(run.trace_dir))
    return run.span_trace_data


def percent(table: dict[str, float], of: float, blank: str) -> str:
    return json.dumps({k or blank: round(100 * v / of, 4) for k, v in
                       sorted(table.items(), key=lambda kv: -kv[1])})


def idle_summary(run) -> dict | None:
    """idle_by_span for this run, made and printed once."""
    data = trace_data(run)
    if data is None:
        return None
    if getattr(run, "span_idle", None) is None:
        idle = run.span_idle = idle_by_span(
            data, run.trace_cfg, len(run.devices), spec())
        window = idle["window_s"]
        print("[bench] idle by program span, % of the traced window "
              f"{window:.6f}s (dispatching line {idle['dispatch_line']!r}, "
              f"{idle['spans_on_dispatch_line']} spans): "
              f"{percent(idle['by_span_s'], window, '(no span)')}; "
              f"sum {100 * idle['idle_s'] / window:.4f}", flush=True)
        print("[bench] spans of other threads, seconds open (they "
              f"attribute nothing): {json.dumps(idle['other_lines'])}",
              flush=True)
    return run.span_idle


def idle_share(run, metric_spec: dict) -> float | None:
    """100 x idle seconds under the spans whose names start with one of
    the metric's `span_prefixes` / traced window.  None where the
    dispatching line holds no span of the program's."""
    idle = idle_summary(run)
    if idle is None or not idle["spans_on_dispatch_line"]:
        return None
    prefixes = tuple(metric_spec["span_prefixes"])
    secs = sum(v for name, v in idle["by_span_s"].items()
               if name and name.startswith(prefixes))
    return 100.0 * secs / idle["window_s"]


def scope_share(run, metric_spec: dict) -> float | None:
    """100 x device seconds under the metric's `scope` / device seconds
    of the programs in `solver_module_patterns`; the whole table by
    scope is printed."""
    data = trace_data(run)
    if data is None:
        return None
    patterns = metric_spec["solver_module_patterns"]
    found = seconds_by_scope(data, run.trace_cfg, metric_spec["scopes"],
                             patterns, executables_op_names(patterns))
    if found is None:
        return None
    print("[bench] solver programs' device time by named scope, % of "
          f"their {found['programs_s']:.6f}s on chip 0 (leaf operations): "
          f"{percent(found['by_scope_s'], found['programs_s'], '(no scope)')}",
          flush=True)
    return (100.0 * found["by_scope_s"].get(metric_spec["scope"], 0.0)
            / found["programs_s"])
