"""BENCHMARK.json against the benchmark's contract, and against the
files the harness finds by name."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
SLICE_READERS = ("clock_ms_tail_p95", "clock_ms_median", "slow_slice_share")


def cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert MANIFEST["command"][-1].startswith("benchmark/")
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 4)


def test_names_units_and_one_line_strings():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            names.append((group, entry["name"]))
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads",
                                              "per_layer"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200, (entry["name"], key,
                                                   len(text))
                    assert "\n" not in text and "\t" not in text
    assert len(set(names)) == len(names)
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in MANIFEST[g]]
    assert len(set(metric_names)) == len(metric_names)
    for w in CELLS.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(set(pairs)) == len(pairs)


def test_every_config_has_its_file_and_a_cell():
    used = {w["config"] for w in CELLS.values()}
    files = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["name"] == c["name"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for key in ("source", "flags", "data", "assumed", "guarantees"):
            assert body[key], (c["name"], key)
        assert len(c["reduced"]) <= 16
    assert used == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_has_its_traffic_file(cell):
    body = json.load(open(os.path.join(BENCH, "workloads", cell + ".json")))
    entry = CELLS[cell]
    assert (body["config"], body["traffic"], body["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert body["drive"] in ("fused", "serial")
    assert body["why"] == entry["why"]
    window = body["window"]
    assert window["mode"] in ("one_call", "slices")
    assert body["check"]["clocks"] % body["check"]["stride_clocks"] == 0
    assert body["window_programs"]
    if body["drive"] == "fused":
        # scan chunks stay whole: calls and check strides are multiples
        # of StreamingPSApp.FUSED_CHUNK_ROUNDS
        from kafka_ps_tpu.runtime.app import StreamingPSApp
        assert body["check"]["stride_clocks"] % \
            StreamingPSApp.FUSED_CHUNK_ROUNDS == 0
    if window["mode"] == "one_call":
        few, many = window["probe_chunks"]
        assert 1 <= few < many
        # one call gives one sample: no tail, body or share of it
        for reader in SLICE_READERS:
            assert cell not in cells_of(PER_LAYER[reader])
    else:
        assert window["slice_clocks"] >= 1 and window["clean_slices"] >= 1
    for limit in body["check"]["limits"].values():
        assert 0 <= limit < 1


def test_end_to_end_metrics():
    assert "setup_s" in END_TO_END
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for cell in CELLS:
        reported = [m for m in MANIFEST["end_to_end"]
                    if cell in cells_of(m)]
        assert len(reported) >= 2 and any(
            m["name"] == "setup_s" for m in reported)


def test_per_layer_metrics_move_a_metric_their_cells_report():
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        moved = END_TO_END[m["moves"]]
        for cell in cells_of(m):
            assert cell in CELLS
            assert cell in cells_of(moved)
        for ext in (".json", ".py"):
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics", m["name"] + ext)), m["name"]
        spec = json.load(open(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".json")))
        assert (spec["name"], spec["unit"], spec["source"], spec["layer"]) \
            == (m["name"], m["unit"], m["source"], m["layer"])
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # one spelling per layer
    assert all(len(v) == 1 for v in layers.values())
    for cell in CELLS:
        assert any(cell in cells_of(m) for m in MANIFEST["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(top, f), ROOT)
            assert ok.match(rel), rel


FAMILY_FILES = ("reference.py", "datagen.py", "costs.py", "tiny.json")
FIXTURE_MANIFEST = os.path.join(BENCH, "tests", "fixtures", "BENCHMARK.json")


def family_dirs(manifest_path):
    """{configuration: the directory of its family's files}, as the
    harness finds it."""
    import run as harness
    manifest = json.load(open(manifest_path))
    return {w["config"]: harness.load_cell(w["name"],
                                           manifest_path)["family_dir"]
            for w in manifest["workloads"]}


@pytest.mark.parametrize("manifest_path", [
    os.path.join(ROOT, "BENCHMARK.json"), FIXTURE_MANIFEST])
def test_a_family_has_its_four_files(manifest_path):
    for config, directory in family_dirs(manifest_path).items():
        for name in FAMILY_FILES:
            assert os.path.isfile(os.path.join(directory, name)), (config,
                                                                   name)
        size = json.load(open(os.path.join(directory, "tiny.json")))
        assert set(size) >= {"shrink", "data"}
    # a family that is named lies under families/, the default beside
    # the harness
    named = family_dirs(FIXTURE_MANIFEST)
    assert all(os.path.basename(os.path.dirname(d)) == "families"
               for d in named.values())


def test_the_harness_names_no_family():
    """What depends on the family of the model comes from its files:
    benchmark/run.py and control.py hold no task, no width and no
    column of a row."""
    words = re.compile("mlp|logreg|hidden_dim|num_features|num_classes")
    for name in ("run.py", "control.py"):
        body = open(os.path.join(BENCH, name)).read()
        assert not words.search(body), (name, words.findall(body))


def test_the_yardstick_imports_nothing_it_measures():
    references = {os.path.join(d, "reference.py")
                  for m in (os.path.join(ROOT, "BENCHMARK.json"),
                            FIXTURE_MANIFEST)
                  for d in family_dirs(m).values()}
    assert os.path.join(BENCH, "reference.py") in references
    for path in references:
        ref = open(path).read()
        assert "kafka_ps_tpu" not in re.sub(r'""".*?"""', "", ref,
                                            flags=re.S), path
    for name in os.listdir(BENCH):
        if name.endswith(".py"):
            body = open(os.path.join(BENCH, name)).read()
            assert not re.search(r"^\s*(import|from)\s+bench\b", body,
                                 flags=re.M), name


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    import costs
    import peaks
    assert peaks.device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.device_peaks("TPU v9 imaginary")
    flops, bytes_ = costs.update_cost("mlp", 1024, 1024, 4096, 6, 2)
    # forward 2*b*h*(f+c1); backward w.r.t. the parameters only
    # 2*b*h*(f+2*c1): two steps and the final loss are 43.3 GFLOP
    fwd, bwd = 2.0 * 1024 * 4096 * 1030, 2.0 * 1024 * 4096 * 1036
    assert flops == pytest.approx(2 * (fwd + bwd) + fwd)
    assert flops == pytest.approx(43.3e9, rel=2e-3)
    # 0.2198 ms at 197 TFLOP/s against 256.5 MB = 0.3132 ms at 819 GB/s:
    # the update is memory bound
    least, bound = peaks.least_seconds(flops, bytes_, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(bytes_ / 819e9)
    assert peaks.least_seconds(flops, 1.0, "TPU v5 lite") == (
        pytest.approx(flops / 197e12), "compute")
    e_flops, e_bytes = costs.eval_cost("mlp", 2000, 1024, 4096, 6)
    assert e_flops == pytest.approx(2.0 * 2000 * 4096 * 1030)
    assert e_bytes == pytest.approx(2000 * 1024 * 4 + 2 * 2000 * 4096 * 4
                                    + 4096 * 1030 * 4)


def _fake_run(summary, traced_updates, test_rows=2000):
    """What the roofline reader sees of a run of mlp-4096 on one chip."""
    from types import SimpleNamespace as NS

    import costs
    model = NS(num_features=1024, hidden_dim=4096, num_rows=6, num_max_iter=2)
    return NS(trace_summary=summary, traced_updates=traced_updates,
              chunk_clocks=8, workers=64, test=(None, [0] * test_rows),
              family=NS(costs=costs),
              cfg=NS(task="mlp", model=model, buffer=NS(max_size=1024)),
              devices=[NS(device_kind="TPU v5 lite")])


def _roofline_reader():
    import importlib.util
    path = os.path.join(BENCH, "layer_metrics", "step_roofline_share.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, json.load(open(path[:-3] + ".json"))


def test_roofline_share_of_whole_chunks_cut_out_of_one_call():
    import costs
    import peaks
    reader, spec = _roofline_reader()
    # six whole runs of the scan program, 0.48 s each: 8 clocks x 64
    # workers a run, 0.9375 ms an update
    summary = {"chips": 1, "module_time_s": {"jit__unknown": 3.5},
               "module_whole_runs": {"jit__unknown": 6.0, "jit__lambda": 9.0},
               "module_whole_time_s": {"jit__unknown": 2.88,
                                       "jit__lambda": 0.001}}
    least, _ = peaks.least_seconds(
        *costs.update_cost("mlp", 1024, 1024, 4096, 6, 2), "TPU v5 lite")
    got = reader.read(_fake_run(summary, 0), spec)
    assert got == pytest.approx(100 * least * 6 * 512 / 2.88)
    assert 30 < got < 40


def test_roofline_share_counts_the_evaluation_where_it_rides_along():
    import costs
    import peaks
    reader, spec = _roofline_reader()
    summary = {"chips": 1, "module_time_s": {"jit_update_eval_bcast": 3.631,
                                             "jit_chain": 0.06}}
    u = costs.update_cost("mlp", 1024, 1024, 4096, 6, 2)
    e = costs.eval_cost("mlp", 2000, 1024, 4096, 6)
    least, _ = peaks.least_seconds(u[0] + e[0], u[1] + e[1], "TPU v5 lite")
    got = reader.read(_fake_run(summary, 2560), spec)
    assert got == pytest.approx(100 * least * 2560 / 3.631)
    # a solver program without the evaluation beside it: not counted
    summary["module_time_s"]["jit_step"] = 1.0
    least, _ = peaks.least_seconds(*u, "TPU v5 lite")
    assert reader.read(_fake_run(summary, 2560), spec) == pytest.approx(
        100 * least * 2560 / 4.631)
