"""From a profiler trace (`.xplane.pb`) to numbers: device busy time,
time per device program, collective time and its exposed part, and the
longest idle gaps with what the host was doing in them.

Reads the trace with `jax.profiler.ProfileData` and nothing else.  What
the planes and lines are called on the chip is data
(`benchmark/trace.json`), looked up by hand once (PERF.md, Findings).

  * a device plane is one whose name starts with `device_plane_prefix`;
  * the lines whose names match `op_line` hold one event per operation
    that ran on that device; busy time is the union of those intervals;
  * the lines whose names match `module_line` hold one event per program
    run, named after the XLA module (`jit_<function>(<id>)`);
  * host annotations (`jax.profiler.TraceAnnotation`, written by
    benchmark/run.py around its own calls) are events on host-plane
    lines whose names start with `host_annotation_prefix`: they set the
    window;
  * an idle gap is named after the host events whose names start with
    one of `idle_gap_prefixes` (the harness's annotations and the
    program's own spans, `kps.*`), of every thread.
"""

from __future__ import annotations

import glob
import gzip
import os
import re


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract_length(intervals, covers) -> float:
    """Length of `intervals` (merged) not covered by `covers` (merged)."""
    total = 0.0
    for s, e in intervals:
        at = s
        for cs, ce in covers:
            if ce <= at:
                continue
            if cs >= e:
                break
            if cs > at:
                total += cs - at
            at = max(at, ce)
            if at >= e:
                break
        if at < e:
            total += e - at
    return total


def module_name(event_name: str) -> str:
    """`jit_scanned(1234567)` -> `jit_scanned`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def read_planes(data, cfg: dict) -> dict:
    """{"devices": [{"name", "ops": [(name, s, e)], "modules": [...]}],
    "host": [(name, s, e)], "activity": [(name, s, e)]}, times in
    seconds: the harness's annotations, and every host event that may
    name an idle gap."""
    devices, host, activity = [], [], []
    gap_prefixes = tuple(cfg["idle_gap_prefixes"])
    op_line, module_line = (re.compile(cfg["op_line"]),
                            re.compile(cfg["module_line"]))
    for plane in data.planes:
        if plane.name.startswith(cfg["device_plane_prefix"]):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = ("ops" if op_line.fullmatch(line.name) else
                       "modules" if module_line.fullmatch(line.name)
                       else None)
                if key is None:
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s = ev.start_ns * 1e-9
                    dev[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
            devices.append(dev)
        if plane.name.startswith(cfg["host_plane_prefix"]):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    event = (ev.name, s, s + ev.duration_ns * 1e-9)
                    if ev.name.startswith(cfg["host_annotation_prefix"]):
                        host.append(event)
                    if ev.name.startswith(gap_prefixes):
                        activity.append(event)
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host, "activity": activity}


def split_by_host_activity(at: float, end: float,
                           host) -> dict[str, float]:
    """The gap [at, end) shared out among the host annotations that
    cover it: each instant goes to the innermost (shortest) annotation
    open at that instant, or to "unannotated"."""
    cuts = {at, end}
    for _, s, e in host:
        cuts.update(t for t in (s, e) if at < t < end)
    out: dict[str, float] = {}
    edges = sorted(cuts)
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        open_now = [(e - s, name) for name, s, e in host if s <= mid < e]
        name = min(open_now)[1] if open_now else "unannotated"
        out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def host_activity(at: float, end: float, host) -> str:
    """What the host was mostly doing in the gap [at, end)."""
    shares = split_by_host_activity(at, end, host)
    return max(shares, key=shares.get)


def reduce(data, cfg: dict, chips: int) -> dict:
    planes = read_planes(data, cfg)
    devices = [d for d in planes["devices"] if d["ops"]][:chips]
    if not devices:
        raise RuntimeError(
            "the trace holds no device operation: planes "
            f"{[p.name for p in data.planes]}")
    collective = re.compile(cfg["collective_op_pattern"])
    # a loop or call covers every operation of its body: it is device
    # time (busy), but not compute that hides a collective
    container = re.compile(cfg["container_op_pattern"])
    # the traced window.  `window_from: annotations` (the default): the
    # calls the harness annotated, first `bench.*` start to last end, so
    # the host's work before a call's first dispatch counts as device
    # idle; host and device planes share the profiler's clock.
    # `window_from: device_ops` (a trace cut out of the middle of one
    # long call, or one without annotations): first operation start to
    # last operation end.
    if planes["host"] and cfg.get("window_from",
                                  "annotations") == "annotations":
        t0 = min(s for _, s, _ in planes["host"])
        t1 = max(e for _, _, e in planes["host"])
        cut_at_edges = False
    else:
        t0 = min(s for d in devices for _, s, _ in d["ops"])
        t1 = max(e for d in devices for _, _, e in d["ops"])
        cut_at_edges = True
    window = t1 - t0
    for d in devices:
        for key in ("ops", "modules"):
            d[key] = [(n, max(s, t0), min(e, t1)) for n, s, e in d[key]
                      if e > t0 and s < t1]

    busy, coll, exposed = [], [], []
    op_time: dict[str, float] = {}
    module_time: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    # runs that are surely whole: where the trace may have cut a run at
    # either edge, each program's earliest and latest run are left out
    whole_runs: dict[str, float] = {}
    whole_time: dict[str, float] = {}
    gaps = []
    for i, d in enumerate(devices):
        spans = merged([(s, e) for _, s, e in d["ops"]])
        busy.append(sum(e - s for s, e in spans))
        c_spans = merged([(s, e) for n, s, e in d["ops"]
                          if collective.search(n)])
        x_spans = merged([(s, e) for n, s, e in d["ops"]
                          if not collective.search(n)
                          and not container.search(n)])
        coll.append(sum(e - s for s, e in c_spans))
        exposed.append(subtract_length(c_spans, x_spans))
        for n, s, e in d["ops"]:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / len(devices)
        for n, s, e in d["modules"]:
            m = module_name(n)
            module_time[m] = module_time.get(m, 0.0) + (e - s) / len(devices)
            if i == 0:
                module_runs[m] = module_runs.get(m, 0) + 1
        by_module: dict[str, list[float]] = {}
        for n, s, e in sorted(d["modules"], key=lambda ev: ev[1]):
            by_module.setdefault(module_name(n), []).append(e - s)
        for m, runs in by_module.items():
            whole = runs[1:-1] if cut_at_edges else runs
            whole_runs[m] = whole_runs.get(m, 0.0) + len(whole) / len(devices)
            whole_time[m] = whole_time.get(m, 0.0) + sum(whole) / len(devices)
        if i == 0:
            edges = [(t0, t0)] + spans + [(t1, t1)]
            for (_, e0), (s1, _) in zip(edges, edges[1:]):
                if s1 - e0 > 0:
                    gaps.append((e0, s1))
    n = len(devices)
    busy_s = sum(busy) / n

    by_activity: dict[str, float] = {}
    for s, e in gaps:
        for what, secs in split_by_host_activity(
                s, e, planes["activity"]).items():
            by_activity[what] = by_activity.get(what, 0.0) + secs
    longest = [[host_activity(s, e, planes["activity"]), e - s]
               for s, e in sorted(gaps, key=lambda g: g[0] - g[1])
               [:cfg["gaps_listed"]]]

    def top(table: dict[str, float], k: int = 10):
        return [[name, secs] for name, secs in
                sorted(table.items(), key=lambda kv: -kv[1])[:k]]

    breakdown = {
        "device_ops": top(module_time if module_time else op_time),
        "idle_gaps": top(by_activity),
    }
    return {
        "window_s": window,
        "busy_s": busy_s,
        "chips": n,
        "collective_s": sum(coll) / n,
        "collective_exposed_s": sum(exposed) / n,
        "module_time_s": module_time,
        "module_runs": module_runs,
        "module_whole_runs": whole_runs,
        "module_whole_time_s": whole_time,
        "op_time_s": op_time,
        "idle_by_host_activity_s": by_activity,
        "longest_gaps_s": longest,
        "breakdown": breakdown,
        "printed": {
            "window_s": window, "busy_s": busy_s, "chips": n,
            "idle_share": 1 - busy_s / window,
            "collective_s": sum(coll) / n,
            "collective_exposed_s": sum(exposed) / n,
            "modules": top(module_time, 6),
            "module_runs": module_runs,
            "ops": top(op_time, 8),
            "idle_by_host_activity_s": top(by_activity),
            "longest_gaps_s": longest[:5],
        },
    }


def reduce_dir(trace_dir: str, cfg: dict, chips: int) -> dict:
    return reduce(load(find_xplane(trace_dir)), cfg, chips)


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and their commonest event names — the by-hand look
    at a new trace."""
    out = []
    for plane in load(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            names: dict[str, list[float]] = {}
            for ev in line.events:
                rec = names.setdefault(module_name(ev.name), [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns * 1e-9
            out.append(f"  LINE {line.name!r}: {sum(r[0] for r in names.values())} events")
            for name, (cnt, secs) in sorted(
                    names.items(), key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"      {cnt:7d} x {name[:90]!r} {secs:.6f}s")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
