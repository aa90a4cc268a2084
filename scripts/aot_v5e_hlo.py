"""Compile the solver programs for a TPU v5e that is described, not
attached, and list what the compiler made of them (PERF.md §3, solver).

libtpu on a host without a chip compiles for `v5e:2x2` from shapes
alone (`jax.experimental.topologies`), a few seconds a program.  The
programs compiled this way are the chip's: the same instruction names
as a device trace shows and the same `temp_size_in_bytes` as the
ledger's `memory_scratch_bytes`.  Nothing runs, so this gives no time —
only the instructions, their shapes, XLA's own cycle estimates and the
program's bytes.

    JAX_PLATFORMS=cpu python scripts/aot_v5e_hlo.py            # cell sizes
    JAX_PLATFORMS=cpu python scripts/aot_v5e_hlo.py --hidden 512 --workers 8

What to look for: an instruction outside the fused computations whose
result is as large as W1 times the workers on a chip — a `copy` or a
`slice` there is a relayout of every worker's parameters, which is
what carrying the flat key-space vector through the local solver cost
(PERF.md §6, PR 25).
"""

from __future__ import annotations

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROGRAMS = ("bsp_scan", "bsp_scan_mesh", "gang")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1,
                "f16": 2, "s8": 1, "u8": 1}
_RESULT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]")


def describe_v5e():
    """The `v5e:2x2` topology, or raises what libtpu raises where it
    offers none."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def compile_program(program: str, topo, *, hidden: int = 4096,
                    workers: int = 64, rows: int = 1024, features: int = 1024,
                    classes: int = 5, test_rows: int = 2000, rounds: int = 8):
    """One solver program of the benchmark's cells (`workers` a chip),
    compiled for the described chip(s) from shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.parallel import bsp
    from kafka_ps_tpu.parallel.mesh import WORKER_AXIS
    from kafka_ps_tpu.runtime import gang
    from kafka_ps_tpu.utils.config import ModelConfig

    cfg = ModelConfig(num_features=features, num_classes=classes,
                      hidden_dim=hidden, num_max_iter=2,
                      local_learning_rate=0.005)
    task = get_task("mlp", cfg)
    if program == "bsp_scan_mesh":
        import numpy as np
        mesh = Mesh(np.array(topo.devices), (WORKER_AXIS,))
        n = workers * mesh.devices.size
        shared = NamedSharding(mesh, P())
        split = NamedSharding(mesh, P(WORKER_AXIS))
    else:
        mesh, n = None, workers
        shared = split = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype, sharding):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    theta = shape((task.num_params,), jnp.float32, shared)
    if program == "gang":
        fn = gang._gang_solver_fns("mlp", cfg)["update_eval_bcast"]
        args = (theta,
                (shape((rows, features), jnp.float32, shared),) * n,
                (shape((rows,), jnp.int32, shared),) * n,
                (shape((rows,), jnp.float32, shared),) * n,
                shape((test_rows, features), jnp.float32, shared),
                shape((test_rows,), jnp.int32, shared))
    else:
        fn = bsp.make_bsp_multi_step(cfg, n, 1.0 / n, rounds, mesh=mesh,
                                     task=task)
        args = (theta, shape((n, rows, features), jnp.float32, split),
                shape((n, rows), jnp.int32, split),
                shape((n, rows), jnp.float32, split))
    return fn.lower(*args).compile()


def compile_folded_chunk(task_name: str, model_json: str, topo, *,
                         workers: int = 4, rows: int = 1, rounds: int = 8,
                         local_iterations: int = 2, lr: float = 0.001):
    """The folded scan chunk (`jit_scanned`, parallel/bsp.py) of a
    language-model family's cell, compiled for the described chip from
    the model file's shapes → (the task, the compiled program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kafka_ps_tpu.models.task import get_task
    from kafka_ps_tpu.parallel import bsp
    from kafka_ps_tpu.utils.config import ModelConfig

    cfg = ModelConfig(num_max_iter=local_iterations, local_learning_rate=lr,
                      model_json=model_json)
    task = get_task(task_name, cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    leaves = {n: shaped(s, jnp.float32) for n, s in task.specs}
    chunk = bsp.make_bsp_multi_step(cfg, workers, 1.0 / workers, rounds,
                                    task=task)
    return task, chunk.lower(
        leaves, shaped((workers, rows, task.row_width), jnp.int32),
        shaped((workers, rows), jnp.int32),
        shaped((workers, rows), jnp.float32)).compile()


def computations(hlo_text: str) -> dict[str, list[str]]:
    """Every computation's instruction lines, in the order of the text
    (of a compiled module: the schedule's)."""
    comps: dict[str, list[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = comps[head.group(1)] = []
        elif comp is not None and " = " in line:
            comp.append(line)
    return comps


def _result_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype, 4)
    for d in filter(None, dims.split(",")):
        size *= int(d)
    return size


def _fused_computations(hlo_text: str) -> set[str]:
    return set(re.findall(r"fusion\(.*?calls=%?([\w.\-]+)", hlo_text))


def top_level_instructions(hlo_text: str):
    """(computation, name, line, result bytes, XLA's estimated cycles)
    for every instruction that is not inside a fused computation: what
    the device runs one after another."""
    fused = _fused_computations(hlo_text)
    out = []
    for comp, lines in computations(hlo_text).items():
        for line in lines:
            m = _RESULT.match(line)
            if comp in fused or not m:
                continue
            name, dtype, dims = m.groups()
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
            out.append((comp, name, line.strip(), _result_bytes(dtype, dims),
                        int(cycles.group(1)) if cycles else 0))
    return out


def big_relayouts(hlo_text: str, at_least_bytes: int):
    """Top-level `copy` and `slice` instructions whose result holds at
    least `at_least_bytes`."""
    return [(comp, name, size)
            for comp, name, _, size, _ in top_level_instructions(hlo_text)
            if size >= at_least_bytes
            and re.match(r"(copy|slice)[.\d]*$", name)]


def zeros_in_taken_branches(hlo_text: str, width: int):
    """(computation, name, result bytes) of every `broadcast` of a
    constant that stands alone (inside no fusion: an array written to
    memory) in a computation that is branch 0 of a `conditional` — of a
    `jax.lax.cond` the branch its predicate's False takes, in
    models/lm_common.py `routed_experts` the pass under the bound — and
    whose result has `width` rows or columns.  What a `cond` under
    `jax.grad` makes of the OTHER branch's residuals: the taken branch
    hands each back as zeros (PERF.md section 6, PR 40)."""
    comps = computations(hlo_text)
    taken = re.findall(r" conditional\(.*?branch_computations=\{%?([\w.\-]+),",
                       hlo_text)
    made = []
    for comp in taken:
        for line in comps[comp]:
            m = _RESULT.match(line)
            if not m or not re.search(r" broadcast\(%?constant[\w.\-]*\)", line):
                continue
            name, dtype, dims = m.groups()
            if dims.count(",") and str(width) in dims.split(","):
                made.append((comp, name, _result_bytes(dtype, dims)))
    return made


def in_run_order(hlo_text: str):
    """The instruction lines of a scheduled module in the order the
    device meets them: the entry computation's, and behind a `while`
    its body's (once).  Fused computations are not entered."""
    comps = computations(hlo_text)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", hlo_text, re.M).group(1)

    def walk(name):
        for line in comps[name]:
            yield line
            body = re.search(r" while\(.* body=%?([\w.\-]+)", line)
            if body:
                yield from walk(body.group(1))
    return walk(entry)


def made_before(hlo_text: str, dtype: str, dims: tuple, until: str):
    """Names of the instructions that MAKE a `dtype[dims]` array (not a
    parameter, a tuple's element or a bitcast of one) before the first
    instruction whose `op_name` matches the pattern `until` runs.
    Raises where none matches: the reader would see nothing."""
    shape = ",".join(str(d) for d in dims)
    made = []
    for line in in_run_order(hlo_text):
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and re.search(until, op_name.group(1)):
            return made
        m = _RESULT.match(line)
        if m and m.group(2) == dtype and m.group(3) == shape and not \
                re.search(r" (parameter|get-tuple-element|bitcast)\(", line):
            made.append(m.group(1))
    raise ValueError(f"no instruction's op_name matches {until!r}")


def called_from(comps: dict, root: str) -> set[str]:
    """`root` and every computation it reaches: the bodies and
    conditions of its loops, the branches of its conditionals, its
    fusions and calls."""
    seen, todo = set(), [root]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            todo += re.findall(
                r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", line)
            for branches in re.findall(r"branch_computations=\{([^}]*)\}",
                                       line):
                todo += [b.strip().lstrip("%") for b in branches.split(",")]
    return seen


# `_RESULT`, then the layout's order of the axes, the opcode, the operands
_MADE = re.compile(_RESULT.pattern
                   + r"(?:\{([\d,]*))?\S* ([\w\-]+)\((.*)")
_RELAYS = ("convert", "transpose", "copy")


def _dims(text: str) -> tuple:
    return tuple(int(d) for d in text.split(",") if d)


def worker_loop(comps: dict) -> set[str]:
    """The computations of the folded round's loop over the workers
    (parallel/bsp.py `_make_folded_round`): the body of the one `while`
    traced right under `kps.bsp.fold`, and all it calls."""
    bodies = [re.search(r"body=%?([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line
              and re.search(r'op_name="[^"]*kps\.bsp\.fold/while"', line)]
    if len(bodies) != 1:
        raise ValueError(f"the fold's loop over the workers: {bodies}")
    return called_from(comps, bodies[0])


def under_the_carry(hlo_text: str, leaf_shapes):
    """(name, opcode, result bytes) of what the device runs under
    `kps.bsp.carry` — the barrier that ties the shared leaves inside
    the worker loop — with a result of a leaf's shape, tuple elements
    apart: what a pass through the barrier writes to memory."""
    shapes = {tuple(s) for s in leaf_shapes}
    made = []
    for _, name, line, size, _ in top_level_instructions(hlo_text):
        m = _MADE.match(line)
        if "kps.bsp.carry" in line and m and _dims(m.group(3)) in shapes \
                and m.group(5) != "get-tuple-element":
            made.append((name, m.group(5), size))
    return made


def relayouts_outside_the_worker_loop(hlo_text: str, leaf_shapes):
    """(computation, name, opcode) of every instruction OUTSIDE the
    worker loop that makes an array of a leaf's dimensions, in any
    order and of any type, by a `convert`, a `transpose` or a `copy`:
    the instruction itself, a fusion that holds one of that size, or an
    asynchronous copy whose result is laid out or typed other than its
    source (a prefetch into faster memory and its way back are not).
    What the barrier in the worker loop is there against: a relayout or
    rounding of a weight for a worker's first step, hoisted out of the
    loop and kept beside the leaves."""
    comps = computations(hlo_text)
    weights = {tuple(sorted(s)) for s in leaf_shapes if len(s) > 1}
    fused = _fused_computations(hlo_text)

    def relays(comp):
        return [m for m in map(_MADE.match, comps.get(comp, ()))
                if m and m.group(5) in _RELAYS
                and tuple(sorted(_dims(m.group(3)))) in weights]

    found = []
    for comp in comps.keys() - worker_loop(comps) - fused:
        lines = comps[comp]
        by_name = {line.split(" = ", 1)[0].split("%")[-1]: line
                   for line in lines}
        for m in filter(None, map(_MADE.match, lines)):
            name, dtype, dims, order, opcode, rest = m.groups()
            if tuple(sorted(_dims(dims))) not in weights:
                continue
            if opcode == "fusion":
                call = re.search(r"calls=%?([\w.\-]+)", rest)
                hit = bool(call and relays(call.group(1)))
            elif opcode == "copy-done":
                start = by_name.get(rest.split(")")[0].lstrip("%"), "")
                src = re.search(r" copy-start\(%?([\w.\-]+)", start)
                src = src and _MADE.match(by_name.get(src.group(1), ""))
                hit = not src or src.group(2, 3, 4) != (dtype, dims, order)
            else:
                hit = opcode in _RELAYS
            if hit:
                found.append((comp, name, opcode))
    return found


def ragged_dot_calls(hlo_text: str):
    """((m, k, n), the kernel's tiles "tm,tk,tn") of every grouped
    product the chip's kernel runs: the Mosaic calls `ragged-dot-*`
    with their `ragged_dot_tiling` frontend attribute, which carries
    the compiler's choice or the program's hint (models/lm_common.py
    `grouped_tiles`).  The shape is the call's own: rows `[m, k]` by
    matrices `[g, k, n]`, or for a dW rows `[m, k]` by `[m, n]` into
    `[g, k, n]`."""
    calls = []
    for line in hlo_text.splitlines():
        result = re.match(r"\s*(?:ROOT )?%ragged-dot-none[.\d]* = "
                          r"f32\[([\d,]+)\].* custom-call\(", line)
        if not result:
            continue
        laid = line.split("operand_layout_constraints={", 1)[1].split(
            "frontend_attributes", 1)[0]
        lhs = re.findall(r"f32\[([\d,]+)\]", laid)[-2].split(",")
        tiles = re.search(r'ragged_dot_tiling="([\d,]+)"', line)
        calls.append(((int(lhs[0]), int(lhs[1]),
                       int(result.group(1).split(",")[-1])),
                      tiles and tiles.group(1)))
    return calls


# the first matrix product of a local update's first gradient (a hoisted
# sum of the mask lies under the scope too, and runs earlier)
FIRST_GRAD_PRODUCT = r"kps\.fit\.grad.*dot_general"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--workers", type=int, default=64,
                    help="logical workers a chip")
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--programs", nargs="*", default=list(PROGRAMS),
                    choices=PROGRAMS)
    ap.add_argument("--folded", nargs=2, metavar=("TASK", "MODEL_JSON"),
                    help="compile a language-model family's folded scan "
                         "chunk instead (4 workers, --rows rows a worker): "
                         "scratch, donated bytes, seconds to compile")
    ap.add_argument("--dump", help="directory for each program's HLO text")
    ap.add_argument("--top", type=int, default=14,
                    help="costliest top-level instructions to list a program")
    args = ap.parse_args(argv)

    topo = describe_v5e()
    if args.folded:
        import time
        t = time.time()
        task, compiled = compile_folded_chunk(*args.folded, topo,
                                              rows=args.rows)
        mem = compiled.memory_analysis()
        print(f"== folded chunk of {args.folded[0]}: {task.num_params} "
              f"parameters, scratch {mem.temp_size_in_bytes / 1e9:.4f} GB "
              f"+ donated leaves {mem.alias_size_in_bytes / 1e9:.4f} GB, "
              f"alive at once {mem.peak_memory_in_bytes / 1e9:.4f} GB, "
              f"compiled in {time.time() - t:.0f} s")
        text = compiled.as_text()
        shapes = [shape for _, shape in task.specs]
        passed = under_the_carry(text, shapes)
        print(f"  under kps.bsp.carry, of a leaf's shape: {len(passed)} "
              f"instructions {sorted({op for _, op, _ in passed})}, "
              f"{sum(size for *_, size in passed) / 1e9:.4f} GB of results; "
              f"weight-shaped relayouts outside the worker loop: "
              f"{len(relayouts_outside_the_worker_loop(text, shapes))}")
        calls = ragged_dot_calls(text)
        for call in sorted(set(calls)):
            print(f"  {calls.count(call):3d} grouped products "
                  f"[{call[0][0]}, {call[0][1]}] x [.., {call[0][2]}] "
                  f"in tiles {call[1]}")
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, args.folded[0] + ".hlo.txt"),
                      "w") as f:
                f.write(text)
        return 0
    w1_bytes = args.hidden * 1024 * 4
    for program in args.programs:
        compiled = compile_program(program, topo, hidden=args.hidden,
                                   workers=args.workers, rows=args.rows)
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        print(f"== {program}: scratch {mem.temp_size_in_bytes / 1e9:.4f} GB, "
              f"bytes accessed {cost.get('bytes accessed', 0) / 1e9:.2f} GB")
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, program + ".hlo.txt"),
                      "w") as f:
                f.write(text)
        instrs = top_level_instructions(text)
        by_comp: dict[str, int] = {}
        for comp, *_, cycles in instrs:
            by_comp[comp] = by_comp.get(comp, 0) + cycles
        print("   estimated cycles by computation (M): " + ", ".join(
            f"{c[:24]} {n / 1e6:.1f}" for c, n in sorted(
                by_comp.items(), key=lambda kv: -kv[1])[:4]))
        for comp, name, line, size, cycles in sorted(
                instrs, key=lambda r: -r[4])[:args.top]:
            print(f"   {cycles / 1e6:7.2f} Mcyc {size / 1e6:8.1f} MB  "
                  f"{comp[:20]:20s} {line[:100]}")
        bad = big_relayouts(text, (args.workers * w1_bytes) // 2)
        print(f"   copy/slice results of half of workers x W1 or more: "
              f"{[(c[:24], n) for c, n, _ in bad] or 'none'}")
        early = made_before(text, "f32", (args.workers, args.hidden, 1024),
                            FIRST_GRAD_PRODUCT)
        print(f"   f32[workers, H, F] made before the first gradient's "
              f"first product: {early or 'none'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
