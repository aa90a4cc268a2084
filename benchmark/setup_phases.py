"""Set-up by phase: the program's own start-up record
(`kafka_ps_tpu/utils/device.py` `STARTUP`: phases, builds, drive calls
and marks in `time.time()` stamps, always on) laid over the interval the
harness calls set-up.

`setup_s` runs from the harness's `T0` (the top of run.py) to the start
of the window, less the reference's seconds.  The harness has no hook
there, so the interval is found in the record: the window's calls are
the LAST `len(run.call_times)` drive calls of the process, the first of
them starts the window, and only what lies in `[T0, window start]` is
read (benchmark/tests run `main()` several times a process, and the
record is the process's).

The sweep is the program's own, `device.startup_split` — the one behind
its `[startup]` line, so the line and this table split an interval the
same way.  Each instant of the interval is one class's, by precedence:

  a build of the program's (cache_load > compile > lower > trace: the
  `jax.monitoring` time spans that lie inside a program phase or a drive
  call, whatever thread they ran on) > `setup.import` > `setup.backend` >
  `setup.app_init` > a drive call before the window > nothing of the
  program's

so a phase or a call counts less the builds inside it, and the classes'
seconds with the last one's sum to the interval by construction.  The
reference runs wholly under "nothing of the program's" (before the first
drive call, inside no phase), and so do its builds, which are listed
apart as a check; `outside` is that class less the reference's seconds,
i.e. `setup_s` less every class of the program's, which makes the five
shares sum to 100.  What this file adds to the sweep: the interval, the
reference taken out, the gaps named by what borders them.

The record keeps the 256 longest builds a kind; of the shorter ones (a
traced function's inner functions, by the thousand) only their number
and seconds, for the process whole and each counted alone though they
nest.  Their time lies under the phase or call they ran in, so the build
share is a lower bound, short by at most the seconds printed as dropped.

One table a traced run, printed once and kept on the run object, as
`self_time.table` keeps its own; the six `setup_*` readers take their
numbers from it.  Against a program without the record: None.
"""

from __future__ import annotations

import json
import sys

try:
    from kafka_ps_tpu.utils import device as program
except ImportError:
    program = None
# the program's sweep and record, None where it has none
startup_split = getattr(program, "startup_split", None)

BUILD_KINDS = ("compile", "cache_load", "lower", "trace")
PHASES = ("import", "backend", "app_init")
CLASSES = BUILD_KINDS + PHASES + ("call",)
OUTSIDE = "other"   # the sweep's name for what no class has
LISTED = 16       # the longest builds printed
GAPS_LISTED = 8   # and the largest gaps


def reduce(record: dict, window_calls: int, setup_s: float,
           reference_s: float, t0: float | None = None) -> dict | None:
    """The table of one run.  `window_calls`: how many of the record's
    last drive calls are the window's; `t0`: the harness's T0 where it
    is known, else the window's start less `setup_s` less
    `reference_s`."""
    calls = list(record["calls"])
    if not 0 < window_calls <= len(calls) or setup_s <= 0:
        return None
    t1 = calls[-window_calls][0]
    if t0 is None:
        t0 = t1 - setup_s - reference_s
    split = startup_split(t0, t1, record)
    seconds = split["seconds"]
    program_s = sum(seconds[name] for name in CLASSES)
    # what borders a gap: a phase, a call, the interval's ends
    before = [(s, s + d) for s, d in calls[:-window_calls] if s + d > t0]
    points = [(t0, "T0"), (t1, "the window")]
    for name, s, e in record["phases"]:
        points += [(s, "setup." + name), (e, "setup." + name)]
    for i, (s, e) in enumerate(before, 1):
        points += [(s, f"call {i}"), (e, f"call {i}")]
    others = split["not_the_programs"]
    gaps = []
    for start, end, name in split["segments"]:
        if name != OUTSIDE:
            continue
        after = max((p for p in points if p[0] <= start), default=points[0])
        until = min((p for p in points if p[0] >= end), default=points[1])
        gaps.append({"seconds": end - start, "after": after[1],
                     "before": until[1],
                     "builds_not_the_programs": sum(
                         start <= b[2] < end for b in others)})
    first_update = record["marks"]["first_update"]
    first_call = record["first_call"]
    return {
        "t0": t0, "window_start": t1, "setup_s": setup_s,
        "reference_s": reference_s, "seconds": seconds,
        "program_s": program_s, "outside_s": setup_s - program_s,
        "built": split["programs"], "anew": split["anew"],
        "calls_before_window": len(before),
        "first_update_after_s": (first_update - t0 if first_update is not None
                                 and t0 <= first_update <= t1 else None),
        # the call that paid the builds, whole: the next one overwrote
        # the app's `last_run`
        "first_call": (first_call if first_call is not None
                       and t0 <= first_call["started"] < t1 else None),
        "longest": split["longest"][:LISTED],
        "gaps": sorted(gaps, key=lambda g: -g["seconds"])[:GAPS_LISTED],
        "not_the_programs": {
            "built": sum(b[0] in ("compile", "cache_load") for b in others),
            "anew": sum(b[0] == "compile" for b in others),
            **{kind: sum(b[3] - b[2] for b in others if b[0] == kind)
               for kind in BUILD_KINDS}},
        "dropped": {kind: list(record["dropped"][kind])
                    for kind in BUILD_KINDS}}


def shares(found: dict) -> dict[str, float]:
    """The five shares of `setup_s`, which sum to 100: the last is what
    the first four leave."""
    s, total = found["seconds"], found["setup_s"]
    out = {"backend": 100.0 * (s["import"] + s["backend"]) / total,
           "app_init": 100.0 * s["app_init"] / total,
           "build": 100.0 * sum(s[kind] for kind in BUILD_KINDS) / total,
           "warm_calls": 100.0 * s["call"] / total}
    out["outside_program"] = 100.0 - sum(out.values())
    return out


def printed(found: dict) -> str:
    """The one table a traced run prints: seconds and share of
    `setup_s` a class, the longest builds and the largest gaps by
    name."""
    s, total = found["seconds"], found["setup_s"]
    rows = {name: [round(s[name], 4), round(100.0 * s[name] / total, 3)]
            for name in CLASSES}
    rows["outside"] = [round(found["outside_s"], 4),
                       round(100.0 * found["outside_s"] / total, 3)]
    longest = [[program, round(sum(kinds.values()), 4),
                {k: round(v, 4) for k, v in kinds.items() if v}]
               for program, kinds in found["longest"]]
    gaps = [[round(g["seconds"], 4), g["after"], g["before"],
             g["builds_not_the_programs"]] for g in found["gaps"]]
    call = found["first_call"]
    first_call = "not in it" if call is None else json.dumps(
        {key: round(value, 4) if isinstance(value, float) else value
         for key, value in call.items()
         if key in ("path", "seconds") or key.endswith("_s")})
    dropped = {kind: [n, round(seconds, 4)]
               for kind, (n, seconds) in found["dropped"].items()}
    other = {k: (round(v, 4) if isinstance(v, float) else v)
             for k, v in found["not_the_programs"].items()}
    first = found["first_update_after_s"]
    span = found["window_start"] - found["t0"]
    return (f"[bench] set-up by phase: setup_s {total:.4f} = {span:.4f}s "
            f"from T0 to the window's first call less {span - total:.4f}s of "
            f"the reference before it (reference_s of the whole run "
            f"{found['reference_s']:.4f}); first update after "
            f"{'no mark in it' if first is None else f'{first:.4f}s'}; "
            f"{found['calls_before_window']} drive calls before the window, "
            f"the first of them whole {first_call}; "
            f"the program built {found['built']} programs, {found['anew']} "
            f"anew; [seconds, % of setup_s]: {json.dumps(rows)}; the "
            f"longest builds by [program, seconds, by kind]: "
            f"{json.dumps(longest)}; under nothing of the program's "
            f"{s[OUTSIDE]:.4f}s, the reference's seconds among them, the "
            f"largest gaps by [seconds, after, before, builds not the "
            f"program's begun in it]: {json.dumps(gaps)}; builds not the "
            f"program's (beside reference_s): {json.dumps(other)}; builds "
            f"the record dropped, shorter than every one it kept, by "
            f"[number, seconds] of the process whole, nested ones each "
            f"counted — the most the build seconds can lack, lying under "
            f"the phase or call they ran in: {json.dumps(dropped)}")


def table(run) -> dict | None:
    """`reduce` for this run, made and printed once."""
    if not hasattr(run, "setup_phases_table"):
        record = (None if startup_split is None
                  else getattr(program, "STARTUP", None))
        harness = sys.modules.get(type(run).__module__)
        run.setup_phases_table = None if record is None else reduce(
            record, len(run.call_times), run.setup_s, run.reference_s,
            getattr(harness, "T0", None))
        if run.setup_phases_table is not None:
            print(printed(run.setup_phases_table), flush=True)
    return run.setup_phases_table


def share(run, name: str) -> float | None:
    found = table(run)
    return None if found is None else shares(found)[name]
