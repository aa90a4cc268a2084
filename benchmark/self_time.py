"""A language-model window read once an instruction: the solver
programs' device time by named scope as SELF time, with the operations
the compiler added adopted by the scope they serve, and what is left
listed by instruction.

`span_reduce.seconds_by_scope` (accepted) counts an event and the events
nested in it where no name pattern marks the outer one a container: its
lines sum to 118-146% of the language-model programs' time (PERF.md §7).
This reader takes nothing from a name:

  * self time by interval nesting on chip 0's operation line: each
    instant of the window goes to the event that started last of those
    open at it (the innermost, where events nest), so an event's
    seconds are its duration less what its children cover, and the
    lines sum to the union of the events — the programs' device time —
    by construction;
  * whole updates only: the window is cut to a whole number of periods
    of the instruction under `marker_scope` that runs once a worker
    update (`lm_update_roofline_share.marker_period`), from the first
    operation of the solver programs in the trace; the programs repeat
    with that period, so such a window holds as much of one part of an
    update as of any other, wherever it starts;
  * an operation's scope is the first of `scopes` its `op_name`
    metadata lies under, read from the executables' own HLO text.  One
    that lies under none of them — under the solver's `weak_scopes`
    alone (`kps.fit.grad`: a name that says nothing a change could act
    on), or under nothing, as the layout copies, broadcasts and
    transposes the compiler adds — is ADOPTED: it takes the scope that
    its users in the same computation agree on, else the one its
    operands agree on, else stays unnamed (rounds of this, so a
    `copy-start` follows its `copy-done`).  `outer_scopes` enclose the
    weak ones (`kps.bsp.fold` round the workers' loop) and name only
    what lies under none of those and no neighbour adopted: the loop
    itself, the slabs sliced for a worker;
  * the remainder is listed by opcode and result shape, largest first.

One table a traced run, printed once and kept on the run object, as
`span_reduce.trace_data` keeps the trace; the readers
`lm_unnamed_self_share` and `moe_placement_self_share` take their
numbers from it.  Its parameters are the `table` block of
benchmark/layer_metrics/lm_unnamed_self_share.json.
"""

from __future__ import annotations

import bisect
import importlib.util
import json
import os
import re
import sys

import span_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
UNNAMED = span_reduce.NO_SPAN

# opcodes that run nothing on the device, and those that cover the
# instructions of a computation they call
NOT_RUN = frozenset({"parameter", "get-tuple-element", "tuple", "bitcast",
                     "constant", "after-all", "partition-id", "replica-id"})
CONTAINERS = frozenset({"while", "conditional", "call"})
# custom calls that run nothing either: the compiler's own allocation
ALLOCATIONS = frozenset({"AllocateBuffer"})
# what carries values between instructions that have nothing else in
# common (a loop's whole state goes through one tuple): these neither
# adopt a scope nor hand one on.  A bitcast has one operand and stays
PLUMBING = (NOT_RUN - {"bitcast"}) | CONTAINERS
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "f32": 4, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8, "c64": 8}


def table_spec() -> dict:
    with open(os.path.join(HERE, "layer_metrics",
                           "lm_unnamed_self_share.json")) as fh:
        return json.load(fh)["table"]


def marker_period(*args):
    """`lm_update_roofline_share.marker_period`, the accepted reader's
    own (the module run.py loads, where it has loaded it)."""
    name = "layer_metric_lm_update_roofline_share"
    module = sys.modules.get(name)
    if module is None:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            HERE, "layer_metrics", "lm_update_roofline_share.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[name] = module
    return module.marker_period(*args)


# -- the module's HLO text: instructions, operands, users ----------------------

_HEAD = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](\{[\d,]*)?")
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_PAIRS = {"(": ")", "[": "]", "{": "}"}


def _closing(text: str, at: int) -> int:
    """The index after the bracket that closes the one at `text[at]`."""
    depth = 0
    for i in range(at, len(text)):
        c = text[i]
        if c in _PAIRS:
            depth += 1
        elif c in ")]}":
            depth -= 1
            if not depth:
                return i + 1
    return len(text)


def _operands(text: str) -> list[str]:
    """The instruction names in an operand list (`f32[8]{0} %x, %y`)."""
    out, depth, start = [], 0, 0
    for i, c in enumerate(text + ","):
        if c in _PAIRS:
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and not depth:
            words = text[start:i].split()
            if words:
                out.append(words[-1].lstrip("%"))
            start = i + 1
    return out


def parse_hlo(text: str) -> dict:
    """One HLO module's text as {"entry": computation, "computations":
    {name: [instruction names, in the text's order]}, "roots":
    {computation: its ROOT}, "instructions": {name: {"computation",
    "opcode", "shape" (an array's `dtype[dims]{minor-to-major}`, a
    tuple's `(n results)`), "bytes" (of the result, a tuple's summed),
    "operands" [names], "op_name", "target" (a custom call's), "called"
    [the computations a loop, a conditional or a call runs]}}}.  A line
    this cannot read is left out, and nothing is looked up inside a
    fusion: `called` is empty for one."""
    entry, comp = None, None
    computations: dict[str, list[str]] = {}
    roots: dict[str, str] = {}
    instructions: dict[str, dict] = {}
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(2)
            computations[comp] = []
            if head.group(1):
                entry = comp
            continue
        found = _HEAD.match(line)
        if comp is None or not found:
            continue
        # `/*index=5*/` stands before every fifth part of a tuple
        rest = re.sub(r"/\*.*?\*/", "", line[found.end():])
        if rest.startswith("("):
            end = _closing(rest, 0)
        else:
            array = re.match(r"\w+\[[^\]]*\](?:\{[^{}]*\})?", rest)
            if not array:
                continue
            end = array.end()
        opcode = re.match(r"\s*([\w\-]+)\(", rest[end:])
        if not opcode:
            continue
        result = rest[:end]
        arrays = _ARRAY.findall(result)
        size = 0
        for dtype, dims, _ in arrays:
            n = _DTYPE_BYTES.get(dtype, 0)
            for d in filter(None, dims.split(",")):
                n *= int(d)
            size += n
        if result.startswith("("):
            shape = f"({len(arrays)} results)"
        else:
            dtype, dims, layout = arrays[0]
            shape = f"{dtype}[{dims}]" + (layout + "}" if layout[1:] else "")
        open_at = end + opcode.end() - 1
        close = _closing(rest, open_at)
        attrs = rest[close:]
        op_name = re.search(r'op_name="([^"]*)"', attrs)
        target = re.search(r'custom_call_target="([^"]*)"', attrs)
        called = _CALLED.findall(attrs)
        for group in _BRANCHES.findall(attrs):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        name = found.group(2)
        computations[comp].append(name)
        if found.group(1):
            roots[comp] = name
        instructions[name] = {
            "computation": comp, "opcode": opcode.group(1), "shape": shape,
            "bytes": size, "operands": _operands(rest[open_at + 1:close - 1]),
            "op_name": op_name.group(1) if op_name else "",
            "target": target.group(1) if target else "",
            "called": called if opcode.group(1) in CONTAINERS else []}
    return {"entry": entry, "computations": computations, "roots": roots,
            "instructions": instructions}


def run_on_the_device(module: dict) -> list[str]:
    """The instructions the device runs one after another: those of the
    entry computation and of every computation a loop, a conditional or
    a call reaches from it (not a fusion's own, not a reduction's), less
    the opcodes and the allocations that run nothing."""
    seen, todo, out = set(), [module["entry"]], []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in module["computations"]:
            continue
        seen.add(comp)
        for name in module["computations"][comp]:
            inst = module["instructions"][name]
            todo += inst["called"]
            if inst["opcode"] not in NOT_RUN and \
                    inst["target"] not in ALLOCATIONS:
                out.append(name)
    return out


def adopted_scopes(module: dict, spec: dict) -> dict[str, tuple[str, bool]]:
    """{instruction: (scope or UNNAMED, whether it was adopted)} for
    every instruction of `module` (parse_hlo).  An instruction under
    none of `scopes` takes the scope that those of its users which have
    one agree on, else the one its operands agree on; `adoption_rounds`
    rounds, each from the round before, so that a chain of the
    compiler's operations is named from its named end.  PLUMBING takes
    no part.  What no round names, and no weak scope holds, goes to the
    first of `outer_scopes` it lies under."""
    insts = module["instructions"]
    scope = {name: span_reduce.scope_of(inst["op_name"], spec["scopes"])
             for name, inst in insts.items()}

    def lends(name):     # a loop or a conditional lends the scope it has
        return name in insts and (insts[name]["opcode"] not in PLUMBING
                                  or (insts[name]["opcode"] in CONTAINERS
                                      and scope[name]))

    def same(a, b):
        return insts[a]["computation"] == insts[b]["computation"]
    # who reads an instruction, and what it reads; a loop's state goes
    # in through a tuple and comes out through get-tuple-element, and
    # only through those does the LOOP count as the reader or the read;
    # what a branch or a body returns through its ROOT tuple, the
    # conditional or the loop that called it reads
    users: dict[str, list[str]] = {name: [] for name in insts}
    reads: dict[str, list[str]] = {name: [] for name in insts}
    for name, inst in insts.items():
        for operand in inst["operands"]:
            if operand not in insts or not same(operand, name):
                continue
            if lends(name):
                users[operand].append(name)
            if lends(operand):
                reads[name].append(operand)
            elif insts[operand]["opcode"] == "get-tuple-element":
                reads[name] += [c for c in insts[operand]["operands"]
                                if lends(c) and
                                insts[c]["opcode"] in CONTAINERS]
        if inst["opcode"] in CONTAINERS and lends(name):
            handed = [o for o in inst["operands"] if o in insts]
            handed += [module["roots"][c] for c in inst["called"]
                       if c in module["roots"]]
            for tuple_ in handed:
                if insts[tuple_]["opcode"] == "tuple":
                    for part in insts[tuple_]["operands"]:
                        if part in insts and same(part, tuple_):
                            users[part].append(name)
    adopted: dict[str, str] = {}
    for _ in range(spec["adoption_rounds"]):
        now = {}
        for name, inst in insts.items():
            if scope[name] or name in adopted \
                    or inst["opcode"] in PLUMBING:
                continue
            for near in (users[name], reads[name]):
                named = {scope[n] or adopted.get(n, UNNAMED) for n in near}
                named.discard(UNNAMED)
                if len(named) == 1:
                    now[name] = named.pop()
                    break
        if not now:
            break
        adopted.update(now)
    out = {}
    for name, inst in insts.items():
        if scope[name] or name in adopted:
            out[name] = (scope[name] or adopted[name], name in adopted)
        elif span_reduce.scope_of(inst["op_name"], spec["weak_scopes"]):
            out[name] = (UNNAMED, False)
        else:
            out[name] = (span_reduce.scope_of(inst["op_name"],
                                              spec["outer_scopes"]), False)
    return out


def unnamed_label(op_name: str, spec: dict) -> str:
    weak = span_reduce.scope_of(op_name, spec["weak_scopes"])
    return f"{weak} alone" if weak else "(no scope)"


def unnamed_byte_share(module: dict, spec: dict) -> float:
    """The share of the result bytes of the instructions the device
    runs (loops, conditionals and calls left out: their results are
    their bodies') that lies under no scope after adoption.  A count
    from the program's text — no time."""
    scopes = adopted_scopes(module, spec)
    total = unnamed = 0
    for name in run_on_the_device(module):
        inst = module["instructions"][name]
        if inst["opcode"] in CONTAINERS or inst["opcode"].endswith("-start"):
            continue        # a `-start`'s result is its `-done`'s again
        total += inst["bytes"]
        if not scopes[name][0]:
            unnamed += inst["bytes"]
    return unnamed / total if total else 0.0


# -- the trace: self time --------------------------------------------------------

def self_seconds(events) -> dict:
    """{key: seconds} of `events` [(key, start, end)] on one line: each
    instant goes to the event that started last among those open at it.
    Where events nest that is an event's duration less what its
    children cover; the values sum to the length of the events' union
    whatever their shape."""
    out: dict = {}
    stack: list[tuple[float, object]] = []      # (end, key), innermost last
    at = 0.0
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        at = _advance(stack, out, at, s)
        stack.append((e, key))
    _advance(stack, out, at, float("inf"))
    return out


def _advance(stack, out, at, to):
    while stack:
        end, key = stack[-1]
        if end > at:
            upto = min(end, to)
            out[key] = out.get(key, 0.0) + upto - at
            at = upto
        if end > to:
            break
        stack.pop()
    return max(at, to)


def hlo_texts(patterns: list[str]) -> dict[str, list[str]]:
    """{module name: [HLO text, one per executable]} of the backend's
    live executables whose module name matches one of `patterns`, as
    `span_reduce.executables_op_names` reaches them."""
    import jax.extend.backend
    found = [re.compile(p) for p in patterns]
    out: dict[str, list[str]] = {}
    for exe in jax.extend.backend.get_backend().live_executables():
        for module in exe.hlo_modules():
            if any(p.search(module.name) for p in found):
                out.setdefault(module.name, []).append(module.to_string())
    return out


def reduce(data, cfg: dict, spec: dict, texts: dict[str, list[str]]
           ) -> dict | None:
    """The table (module docstring) of the solver programs on chip 0,
    or None where there is nothing to make it from: no run of them in
    the trace, no HLO text, no marker that ran three times, or a
    program that carries none of `needs_one_of` — one older than the
    scopes this reader was written for."""
    ops, modules = span_reduce.device_op_events(data, cfg)
    wanted = [re.compile(p) for p in spec["solver_module_patterns"]]
    runs = sorted((s, e, m) for m, s, e in modules
                  if any(p.search(m) for p in wanted))
    if not runs or not ops:
        return None
    starts = [s for s, _, _ in runs]
    inside: dict[str, list] = {}       # module: its events (name, s, e)
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            inside.setdefault(runs[i][2], []).append(
                (span_reduce.instruction_name(name), s, e))
    parsed = {}
    for module, events in inside.items():
        names = {n for n, _, _ in events}
        candidates = [parse_hlo(t) for t in texts.get(module, [])]
        if candidates:
            parsed[module] = max(candidates, key=lambda p: len(
                names & p["instructions"].keys()))
    if not parsed or not any(
            needed in inst["op_name"] for p in parsed.values()
            for inst in p["instructions"].values()
            for needed in spec["needs_one_of"]):
        return None
    found = marker_period(
        [ev for events in inside.values() for ev in events],
        [(s, e) for s, e, _ in runs],
        [{n: i["op_name"] for n, i in p["instructions"].items()}
         for p in parsed.values()], spec["marker_scope"])
    if found is None:
        return None
    period = found[0]
    t0 = min(s for events in inside.values() for _, s, _ in events)
    t1 = max(e for events in inside.values() for _, _, e in events)
    updates = int((t1 - t0) / period + 1e-9)
    if updates < 1:
        return None
    t1 = t0 + updates * period
    programs_s = sum(min(e, t1) - max(s, t0) for s, e, _ in runs
                     if e > t0 and s < t1)

    by_scope: dict[str, dict[str, float]] = {}
    unnamed: dict[str, float] = {}
    left: dict[tuple[str, str], list] = {}      # (opcode, shape): [s, events]
    for module, events in inside.items():
        if module not in parsed:
            continue
        insts = parsed[module]["instructions"]
        scopes = adopted_scopes(parsed[module], spec)
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in events
                   if e > t0 and s < t1]
        count: dict[str, int] = {}
        for n, _, _ in clipped:
            count[n] = count.get(n, 0) + 1
        for name, secs in self_seconds(clipped).items():
            scope, was_adopted = scopes.get(name, (UNNAMED, False))
            if scope:
                line = by_scope.setdefault(scope, {"own": 0.0, "adopted": 0.0})
                line["adopted" if was_adopted else "own"] += secs
                continue
            inst = insts.get(name, {"op_name": "", "opcode": "?",
                                    "shape": "?"})
            label = unnamed_label(inst["op_name"], spec)
            unnamed[label] = unnamed.get(label, 0.0) + secs
            entry = left.setdefault((inst["opcode"], inst["shape"]), [0.0, 0])
            entry[0] += secs
            entry[1] += count[name]
    largest = sorted(left.items(), key=lambda kv: -kv[1][0])
    return {"updates": updates, "period_s": period, "window_s": t1 - t0,
            "programs_s": programs_s,
            "self_s": (sum(v["own"] + v["adopted"] for v in by_scope.values())
                       + sum(unnamed.values())),
            "by_scope_s": by_scope, "unnamed_s": unnamed,
            "largest_unnamed": [
                {"opcode": op, "shape": shape, "seconds": secs,
                 "events": n}
                for (op, shape), (secs, n) in largest[:spec["listed"]]]}


def printed(found: dict) -> str:
    """The one line a traced run prints: ms an update."""
    per = 1e3 / found["updates"]
    scopes = {k: [round(v["own"] * per, 4), round(v["adopted"] * per, 4)]
              for k, v in sorted(found["by_scope_s"].items(),
                                 key=lambda kv: -sum(kv[1].values()))}
    unnamed = {k: round(v * per, 4) for k, v in
               sorted(found["unnamed_s"].items(), key=lambda kv: -kv[1])}
    largest = [[e["opcode"], e["shape"], round(e["seconds"] * per, 4),
                round(e["events"] / found["updates"], 2)]
               for e in found["largest_unnamed"]]
    return (f"[bench] self time by scope, ms an update over "
            f"{found['updates']} whole updates of "
            f"{found['period_s'] * 1e3:.4f} ms on chip 0 (the solver "
            f"programs' device time in them {found['programs_s']:.6f}s; the "
            f"lines sum to {found['self_s']:.6f}s, "
            f"{100 * found['self_s'] / found['programs_s']:.4f}%) "
            f"[own, adopted]: {json.dumps(scopes)}; unnamed: "
            f"{json.dumps(unnamed)}; the largest unnamed by [opcode, "
            f"result, ms an update, runs an update]: {json.dumps(largest)}")


def table(run) -> dict | None:
    """`reduce` for this run, made and printed once."""
    data = span_reduce.trace_data(run)
    if data is None:
        return None
    if not hasattr(run, "self_time_table"):
        spec = table_spec()
        run.self_time_table = reduce(
            data, run.trace_cfg, spec,
            hlo_texts(spec["solver_module_patterns"]))
        if run.self_time_table is not None:
            print(printed(run.self_time_table), flush=True)
    return run.self_time_table


def share(found: dict, scopes: list[str]) -> float:
    """100 x self seconds (own + adopted) under `scopes` / the programs'
    device time."""
    return 100.0 * sum(sum(found["by_scope_s"].get(s, {}).values())
                       for s in scopes) / found["programs_s"]
