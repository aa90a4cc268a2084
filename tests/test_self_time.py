"""benchmark/self_time.py on a hand-made operation line and HLO text
(tests/fixtures/self_time_tiny.json), where every line of the table is
known by hand; and the named scopes it reads, in the programs the three
language-model families trace at their tiny sizes.  CPU; no time here
is a device's."""

import importlib.util
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from kafka_ps_tpu.models.task import get_task
from kafka_ps_tpu.parallel import bsp
from kafka_ps_tpu.utils.config import ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def self_time():
    """benchmark/self_time.py, by path, as benchmark/run.py's readers
    import it (the benchmark's directory on the path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, "self_time.py")
    known = sys.modules.get("self_time")
    if known is not None and getattr(known, "__file__", None) == path:
        return known
    spec = importlib.util.spec_from_file_location("self_time", path)
    module = sys.modules["self_time"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "self_time_tiny.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def module(self_time, tiny):
    return self_time.parse_hlo("\n".join(tiny["hlo"]))


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=round(start * 1e9),
                                 duration_ns=round((end - start) * 1e9))


def traced(tiny, events=None):
    """A profiler trace of one chip, as jax.profiler.ProfileData hands
    it out, of the fixture's operation line."""
    line = types.SimpleNamespace
    return types.SimpleNamespace(planes=[line(name="/device:TPU:0", lines=[
        line(name="XLA Ops", events=[
            _event(*e) for e in (events or tiny["events"])]),
        line(name="XLA Modules", events=[
            _event(*m) for m in tiny["modules"]])])])


def trace_cfg():
    with open(os.path.join(BENCH, "trace.json")) as fh:
        return dict(json.load(fh), window_from="device_ops")


# -- the HLO text ---------------------------------------------------------------

def test_the_text_is_read_an_instruction_a_line(self_time, module):
    insts = module["instructions"]
    assert module["entry"] == "main.9"
    assert set(module["computations"]) == {
        "fused_computation.1", "placed_under_the_bound", "placed_whole",
        "fold_body", "fold_cond", "main.9"}
    loop = insts["while.1"]
    assert loop["opcode"] == "while" and loop["shape"] == "(3 results)"
    assert loop["bytes"] == 4 + 32 + 32        # the comment is no array
    assert loop["called"] == ["fold_cond", "fold_body"]
    assert loop["operands"] == ["tuple.61"]
    assert insts["conditional.10"]["called"] == [
        "placed_under_the_bound", "placed_whole"]
    assert insts["conditional.10"]["operands"] == [
        "constant.40", "tuple.41", "tuple.41"]
    assert insts["tuple.42"]["operands"] == [      # `/*index=2*/%fusion.11`
        "dynamic-slice.16", "fusion.15", "fusion.11"]
    copy = insts["copy-done.13"]
    assert (copy["opcode"], copy["shape"], copy["bytes"], copy["op_name"]) \
        == ("copy-done", "f32[8]{0}", 32, "")
    assert insts["dynamic-slice.16"]["shape"] == "s32[]"
    assert insts["fusion.6"]["op_name"].endswith("kps.attn.qkv/dot_general")
    assert insts["fusion.6"]["called"] == []   # a fusion covers no event
    # what the device runs: the loop's and the conditional's bodies,
    # not a fusion's, and nothing that only carries a value
    assert self_time.run_on_the_device(module) == [
        "while.1", "copy.5", "fusion.6", "copy.7", "fusion.8", "fusion.9",
        "conditional.10", "fusion.11", "copy-start.12", "copy-done.13",
        "fusion.14", "fusion.15", "dynamic-slice.16", "fusion.2",
        "ragged-dot.4", "fusion.3"]


@pytest.mark.parametrize("name,want", [
    ("fusion.2", ("kps.moe.place", False)),        # a part before its whole
    ("conditional.10", ("kps.moe.experts", False)),
    ("ragged-dot.4", ("ragged-dot", False)),
    ("copy.5", ("kps.attn.qkv", True)),            # its one user's
    ("copy.7", ("", False)),                       # its users disagree
    ("copy-done.13", ("kps.fit.param_step", True)),
    ("copy-start.12", ("kps.fit.param_step", True)),   # a round later
    ("fusion.15", ("", False)),        # weak alone, its operands disagree
    ("dynamic-slice.16", ("kps.bsp.fold", False)),     # the outer scope
    ("while.1", ("kps.bsp.fold", False)),
    ("tuple.41", ("", False))])        # what carries values takes no part
def test_an_unscoped_instruction_is_adopted_by_its_neighbours(
        self_time, module, name, want):
    spec = self_time.table_spec()
    assert self_time.adopted_scopes(module, spec)[name] == want


def test_adoption_goes_by_rounds_and_not_through_a_tuple(self_time, module,
                                                        tiny):
    spec = self_time.table_spec()
    one = self_time.adopted_scopes(module, dict(spec, adoption_rounds=1))
    assert one["copy-done.13"] == ("kps.fit.param_step", True)
    assert one["copy-start.12"] == ("", False)
    # were tuple.41 to hand a scope on, copy.7 would still meet two;
    # and the loop's init tuple hands the leaf nothing
    assert self_time.adopted_scopes(module, spec)["leaf"] == ("", False)
    # where the users agree the operands are not asked
    text = "\n".join(tiny["hlo"]).replace("kps.mlp", "kps.lm.norm")
    again = self_time.adopted_scopes(self_time.parse_hlo(text), spec)
    assert again["copy.7"] == ("kps.lm.norm", True)


def test_what_a_branch_returns_is_the_conditionals(self_time):
    """The zeros a branch hands back for what only the other branch
    computes reach no instruction of their own computation: through
    the ROOT tuple the conditional reads them, and lends its scope."""
    text = """HloModule m, is_scheduled=true

%branch_0 (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %constant.1 = f32[] constant(0)
  %broadcast.2 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(f)/while/body/closed_call"}
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%p, %broadcast.2)
}

%branch_1 (q: f32[8]) -> (f32[8], f32[8]) {
  %q = f32[8]{0} parameter(0)
  ROOT %tuple.4 = (f32[8]{0}, f32[8]{0}) tuple(%q, %q)
}

ENTRY %main (a: f32[8], i: s32[]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %i = s32[] parameter(1)
  %copy.5 = f32[8]{0} copy(%a)
  %conditional.6 = (f32[8]{0}, f32[8]{0}) conditional(%i, %copy.5, %copy.5), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(f)/kps.fit.grad/kps.moe.experts/cond"}
  ROOT %get-tuple-element.7 = f32[8]{0} get-tuple-element(%conditional.6), index=0
}
"""
    module = self_time.parse_hlo(text)
    assert module["roots"] == {"branch_0": "tuple.3", "branch_1": "tuple.4",
                               "main": "get-tuple-element.7"}
    scopes = self_time.adopted_scopes(module, self_time.table_spec())
    assert scopes["broadcast.2"] == ("kps.moe.experts", True)
    assert scopes["copy.5"] == ("kps.moe.experts", True)   # it reads it
    assert scopes["p"] == ("", False)


def test_the_unnamed_share_of_the_result_bytes(self_time, module):
    """Of the 13 instructions the device runs that are no loop, no
    conditional and no `-start`: 12 of 32 bytes and one of 4; copy.7
    and fusion.15 stay unnamed."""
    spec = self_time.table_spec()
    assert self_time.unnamed_byte_share(module, spec) == pytest.approx(
        64 / (12 * 32 + 4))


# -- self time -------------------------------------------------------------------

def test_nested_events_sum_to_the_outer_one(self_time):
    """A `while` over a `conditional` over two fusions and a custom
    call: each event keeps its duration less what its children cover."""
    events = [("while", 0.0, 10.0), ("conditional", 1.0, 8.0),
              ("fusion.a", 1.5, 3.0), ("custom-call", 3.0, 6.5),
              ("fusion.b", 7.0, 8.0), ("fusion.c", 8.5, 9.75)]
    got = self_time.self_seconds(events)
    assert got == pytest.approx({
        "while": 1.0 + 0.5 + 0.25, "conditional": 0.5 + 0.5,
        "fusion.a": 1.5, "custom-call": 3.5, "fusion.b": 1.0,
        "fusion.c": 1.25})
    assert abs(sum(got.values()) - 10.0) < 1e-9
    # the same instruction again is the same line; events that overlap
    # without nesting (an asynchronous copy under a fusion) still sum to
    # their union
    events += [("fusion.a", 12.0, 13.0), ("copy", 12.5, 14.0)]
    got = self_time.self_seconds(events)
    assert got["fusion.a"] == pytest.approx(1.5 + 0.5)
    assert abs(sum(got.values()) - 12.0) < 1e-9


def test_the_table_is_known_by_hand(self_time, tiny):
    """Three whole updates of 10 s (the fourth, cut by the trace's end,
    is trimmed away): every line an update, own and adopted, and the
    lines sum to the program's device time."""
    found = self_time.reduce(traced(tiny), trace_cfg(),
                             self_time.table_spec(),
                             {"jit_scanned": ["\n".join(tiny["hlo"])]})
    assert (found["updates"], found["period_s"]) == (3, pytest.approx(10.0))
    assert found["window_s"] == found["programs_s"] == pytest.approx(30.0)
    assert abs(found["self_s"] - found["programs_s"]) < 1e-9
    per_update = {scope: (v["own"] / 3, v["adopted"] / 3)
                  for scope, v in found["by_scope_s"].items()}
    assert per_update == {
        "kps.attn.qkv": pytest.approx((1.0, 1.0)),
        "kps.lm.norm": pytest.approx((0.25, 0.0)),
        "kps.mlp": pytest.approx((0.25, 0.0)),
        "kps.moe.place": pytest.approx((1.0, 0.0)),
        "ragged-dot": pytest.approx((1.5, 0.0)),
        "kps.moe.combine": pytest.approx((1.0, 0.0)),
        "kps.moe.experts": pytest.approx((0.5, 0.0)),   # the cond's own
        "kps.fit.delta": pytest.approx((1.0, 0.0)),
        "kps.fit.param_step": pytest.approx((0.5, 0.6)),
        "kps.bsp.fold": pytest.approx((0.1 + 0.5, 0.0))}  # the loop's own
    assert found["unnamed_s"] == {
        "(no scope)": pytest.approx(3 * 0.5),
        "kps.fit.grad alone": pytest.approx(3 * 0.3)}
    assert [(e["opcode"], e["shape"], e["events"])
            for e in found["largest_unnamed"]] == [
        ("copy", "f32[8]{0}", 3), ("fusion", "f32[8]{0}", 3)]
    assert found["largest_unnamed"][0]["seconds"] == pytest.approx(1.5)
    line = self_time.printed(found)
    assert line.startswith("[bench] self time by scope")
    assert '"kps.fit.param_step": [500.0, 600.0]' in line
    assert '["copy", "f32[8]{0}", 500.0, 1.0]' in line     # ms an update


def test_the_window_is_whole_updates_wherever_the_trace_begins(self_time,
                                                              tiny):
    """A trace that begins 4 s into an update: the window runs from its
    first operation over three periods, and holds as much of every part
    of an update as the one that began with an update."""
    late = [(n, max(s, 4.0), e) for n, s, e in tiny["events"] if e > 4.0]
    found = self_time.reduce(traced(tiny, late), trace_cfg(),
                             self_time.table_spec(),
                             {"jit_scanned": ["\n".join(tiny["hlo"])]})
    assert found["updates"] == 3
    assert found["window_s"] == pytest.approx(30.0)
    assert abs(found["self_s"] - found["programs_s"]) < 1e-9
    assert found["by_scope_s"]["kps.moe.place"]["own"] == pytest.approx(3.0)
    assert found["by_scope_s"]["kps.attn.qkv"] == pytest.approx(
        {"own": 3.0, "adopted": 3.0})


@pytest.mark.parametrize("spoil", ["no_run", "no_text", "no_marker",
                                   "older_program"])
def test_nothing_to_read_gives_none(self_time, tiny, spoil):
    spec, text = self_time.table_spec(), "\n".join(tiny["hlo"])
    data, texts = traced(tiny), {"jit_scanned": [text]}
    if spoil == "no_run":
        spec = dict(spec, solver_module_patterns=["^jit_other$"])
    elif spoil == "no_text":
        texts = {}
    elif spoil == "no_marker":
        texts = {"jit_scanned": [text.replace("kps.fit.delta", "kps.fit.d")]}
    else:       # the parent's program: the scopes this reader needs
        for scope in spec["needs_one_of"]:
            text = text.replace("/" + scope, "")
        texts = {"jit_scanned": [text]}
    assert self_time.reduce(data, trace_cfg(), spec, texts) is None


# -- the scopes, in the programs the families trace --------------------------------

FAMILIES = {
    "glm4_moe_lite": ("benchmark/families/glm4-moe-lite/tiny.model.json", [
        ("kps.attn.qkv", "kps.mla"), ("kps.attn.norm_rope", "kps.mla"),
        ("kps.attn.out", "kps.mla"), ("kps.lm.layers", "kps.fit.")]),
    "nemotron_h": ("benchmark/families/nemotron-h/tiny.model.json", [
        ("kps.attn.qkv", "kps.attn"), ("kps.attn.out", "kps.attn")]),
    "afmoe": ("benchmark/families/afmoe/tiny.model.json", [
        ("kps.attn.qkv", "kps.attn.proj"),
        ("kps.attn.norm_rope", "kps.attn.proj"),
        ("kps.attn.out", "kps.attn.proj"), ("kps.attn.proj", "kps.attn")]),
}
SHARED = [("kps.moe.sort", "kps.moe.experts"),
          ("kps.moe.place", "kps.moe.experts"),
          ("kps.moe.expert_fn", "kps.moe.experts"),
          ("kps.moe.combine", "kps.moe.experts"),
          ("kps.lm.norm", "kps.fit."), ("kps.bsp.carry", "kps.bsp.fold"),
          ("kps.fit.grad", "kps.bsp.fold"), ("kps.fit.delta", "kps.bsp.fold")]


@pytest.fixture(scope="module")
def chunk_op_names():
    cache = {}

    def op_names(family):
        """The `op_name` of every instruction of the family's tiny
        chunk as the CPU compiles it: what a reader of the chip's HLO
        text matches scopes against."""
        if family not in cache:
            cfg = ModelConfig(num_max_iter=2, local_learning_rate=0.05,
                              model_json=FAMILIES[family][0])
            task = get_task(family, cfg)
            leaves = jax.eval_shape(task.unflatten, jax.ShapeDtypeStruct(
                (task.num_params,), jnp.float32))
            w, cap = 2, 1
            shaped = jax.ShapeDtypeStruct
            text = bsp.make_bsp_multi_step(
                cfg, w, 1.0 / w, 8, task=task).lower(
                    leaves, shaped((w, cap, task.row_width), jnp.int32),
                    shaped((w, cap), jnp.int32),
                    shaped((w, cap), jnp.float32)).compile().as_text()
            cache[family] = re.findall(r'op_name="([^"]*)"', text)
        return cache[family]
    return op_names


@pytest.mark.parametrize("family,child,parent", [
    (family, child, parent) for family, (_, own) in FAMILIES.items()
    for child, parent in own + SHARED])
def test_the_compiled_chunk_holds_the_scope_under_its_parent(
        chunk_op_names, family, child, parent):
    """An instruction's `op_name` names the scopes it was traced under,
    outermost first: the new scopes lie INSIDE the ones the accepted
    readers match by substring, never in their place.  (A reduction's
    own computation is named from where it begins, not from the
    program's start: those names are not whole.)"""
    under = [n for n in chunk_op_names(family) if n.startswith("jit(")
             and re.search(rf"{re.escape(child)}(?![.\w])", n)]
    assert under, child
    assert all(re.search(rf"{re.escape(parent)}.*{re.escape(child)}", n)
               for n in under), (child, parent)
