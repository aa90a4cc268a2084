"""chip_smoke.py on the CPU: the script refuses to run off the chip,
and its phase functions — the same code the chip run executes — hold at
a tiny width.  Plus the start-up contract the
script shares with every CLI entry: the compile-cache hook."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    num_features=128, fused_hidden=32, buffer_min=8,
    buffer_max=64, train_rows=512, test_rows=128, per_node_clocks=12,
    fused_rounds=16, multichip_rounds=8,
    center_scale=1.0,           # too few rows to learn the hard regime
    grouped_rows=128, grouped_widths=(640, 384),    # the rule still hints
    core_shape=(1, 384, 1, 2, 128), core_shape_halves=(1, 384, 2, 2, 64),
    core_window=200, core_block=128,
    core_calls=1,
    placement_shape=(512, 1024, 128), placement_groups=(90, 0, 37, 60),
    norm_rope_positions=40, norm_rope_heads=(8, 2),
    scan_shapes=(((1, 256, 8, 64), 1, 128, 128),))


def _run(script_dir, env_extra, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *args], cwd=script_dir, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu_backend_naming_it():
    r = _run(REPO, {}, "chip_smoke.py")
    assert r.returncode == 2
    assert "platform='cpu'" in r.stderr and "not a TPU" in r.stderr
    assert r.stdout == ""            # no result of any kind


def test_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    r = _run(tmp_path, {"PYTHONPATH": ""}, "chip_smoke.py")
    assert r.returncode not in (0, 2)
    assert "kafka_ps_tpu" in r.stderr
    assert r.stdout == ""


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    workdir = tmp_path_factory.mktemp("chip_smoke")
    train, test = chip_smoke.make_data(str(workdir), TINY)
    return str(workdir), train, test


@pytest.mark.parametrize("consistency", [0, 2, -1])
def test_per_node_phase(data, consistency):
    workdir, train, test = data
    rec = chip_smoke.phase_per_node(workdir, train, test, TINY, "cpu",
                                    consistency)
    assert rec["solver"] == "xla"
    assert rec["eval_rows"] >= 1 and rec["loss"][1] < rec["loss"][0]


@pytest.mark.parametrize("eval_every", [1, 8])
def test_fused_phase(data, eval_every):
    workdir, train, test = data
    rec = chip_smoke.phase_fused(workdir, train, test, TINY, "cpu",
                                 eval_every)
    assert rec["program"] == ("bsp-step" if eval_every == 1
                              else "bsp-scan-8")
    assert rec["eval_rows"] == TINY.fused_rounds // eval_every


def test_grouped_products_phase():
    rec = chip_smoke.phase_grouped_products(TINY, "cpu")
    assert (rec["up_tiles"], rec["down_tiles"]) == ("128,640,384",
                                                    "128,384,640")
    assert rec["live_rows"] == 32 and rec["up_dW_gap"] == 0.0
    with pytest.raises(chip_smoke.SmokeFailure, match="tells the kernel"):
        chip_smoke.phase_grouped_products(
            chip_smoke.Sizes(grouped_rows=128, grouped_widths=(512, 64)),
            "cpu")


@pytest.mark.parametrize("shape", [TINY.core_shape, TINY.core_shape_halves])
def test_attention_core_phase(shape):
    """The kernel in Pallas's interpreter against the plain tiles, at
    heads of a lane vector and of half of one: the gaps are bfloat16's,
    the times are the CPU's and mean nothing."""
    rec = chip_smoke.phase_attention_core(
        dataclasses.replace(TINY, core_shape=shape), "cpu", interpret=True)
    for kind in ("window", "full"):
        for what in ("out", "dq", "dk", "dv"):
            assert 0 < rec[f"{kind}_{what}_gap"] < 0.02
    with pytest.raises(chip_smoke.SmokeFailure, match="does not take"):
        chip_smoke.phase_attention_core(
            chip_smoke.Sizes(core_shape=(1, 64, 1, 2, 8), core_block=8),
            "cpu", interpret=True)


def test_norm_rope_phase():
    """The kernel in Pallas's interpreter against the plain lines: the
    gaps are float32's, the times are the CPU's and mean nothing."""
    rec = chip_smoke.phase_norm_rope(TINY, "cpu", interpret=True)
    for case in ("q", "k", "q_norm_only"):
        for what in ("out", "dx", "dw"):
            assert rec[f"{case}_{what}_gap"] < 1e-5
    with pytest.raises(chip_smoke.SmokeFailure, match="does not take"):
        chip_smoke.phase_norm_rope(
            chip_smoke.Sizes(norm_rope_positions=40, norm_rope_dim=16),
            "cpu", interpret=True)


def test_ssd_scan_phase():
    """The kernels in Pallas's interpreter against the einsums on a row
    of two chunks: the gaps are bfloat16's (the CPU's einsums are
    float32), the times are the CPU's and mean nothing."""
    rec = chip_smoke.phase_ssd_scan(TINY, "cpu", interpret=True)
    for what in ("y", "dx", "ddt", "da", "db", "dc", "dd"):
        assert 0 < rec[f"g1_q128_{what}_gap"] < 0.02
    with pytest.raises(chip_smoke.SmokeFailure, match="does not take"):
        chip_smoke.phase_ssd_scan(
            chip_smoke.Sizes(scan_shapes=(((1, 64, 8, 64), 1, 128, 16),)),
            "cpu", interpret=True)


def test_placement_products_phase(monkeypatch):
    """The two kernels in Pallas's interpreter against the products
    with the matrix written out, at a shape of whole tiles and blocks
    the rule is let take (the test's steering: it asks for a larger
    matrix): placing to the bit, the sums to float32 rounding."""
    from kafka_ps_tpu.models import placement_kernel
    with pytest.raises(chip_smoke.SmokeFailure, match="do not take"):
        chip_smoke.phase_placement_products(TINY, "cpu", interpret=True)
    monkeypatch.setattr(placement_kernel, "MIN_MATRIX", 0)
    rec = chip_smoke.phase_placement_products(TINY, "cpu", interpret=True)
    assert rec["live_rows"] == 187 and 0 < rec["visited_share"] < 1
    assert rec["place_gap"] == rec["add_back_t_gap"] == 0.0
    assert rec["add_back_gap"] <= 1e-6 and rec["place_t_gap"] <= 1e-6
    for what in ("add_back", "add_back_t"):
        off, product_off = rec[f"{what}_off_float64"]
        assert 0 < off <= product_off + 1e-6 and off < 1e-3


def test_multichip_phase_on_the_virtual_mesh(data):
    import jax
    n = len(jax.devices())           # 8 virtual CPU devices (conftest)
    assert n > 1
    workdir, train, test = data
    out = chip_smoke.phase_multichip(workdir, train, test, TINY, "cpu", n)
    assert len(out) == 6
    assert all(rec["slab_devices"] == n for rec in out.values())
    assert {rec["solver"] for rec in out.values()} == {"fused-bsp"}


def test_a_failed_check_raises(data):
    workdir, train, test = data
    with pytest.raises(chip_smoke.SmokeFailure, match="on cpu, not tpu"):
        # the platform the caller expects is part of every phase's checks
        chip_smoke.phase_per_node(workdir + "/wrong", train, test, TINY,
                                  "tpu", 0)


def test_stdout_ends_with_the_verdict_and_nothing_else(monkeypatch, capsys):
    """The driver reads the LAST stdout line and takes exactly
    {"ok", "device": {"platform", "kind", "count"}}; the report (with
    `"claim": null`) is the line before."""
    from kafka_ps_tpu.utils import device
    found = dict(device.device_summary(), platform="tpu",
                 kind="TPU v5 lite", count=1)
    monkeypatch.setattr(device, "device_summary", lambda: found)
    monkeypatch.setattr(device, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(chip_smoke, "run_phases",
                        lambda *a: {"per_node_c0": {"solver": "xla"}})
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    report = json.loads(lines[-2])
    assert list(report)[-1] == "claim" and report["claim"] is None
    assert report["phases"] == {"per_node_c0": {"solver": "xla"}}


# -- the compile-cache hook (utils/device.py) --------------------------------

_REPORT = ("import json, jax; from kafka_ps_tpu.cli.run import "
           "apply_platform_env; apply_platform_env(); "
           "print(json.dumps([jax.config.jax_compilation_cache_dir, "
           "jax.config.jax_persistent_cache_min_compile_time_secs]))")


def test_cache_path_is_fixed_inside_the_checkout():
    env = {"JAX_COMPILATION_CACHE_DIR": ""}
    a, b = (json.loads(_run(REPO, env, "-c", _REPORT).stdout)
            for _ in range(2))
    assert a == b == [str(REPO / ".jax_cache"), 0.0]


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "elsewhere")
    r = _run(REPO, {"JAX_COMPILATION_CACHE_DIR": placed}, "-c", _REPORT)
    # JAX read its own variable; the hook set no directory of its own
    assert json.loads(r.stdout) == [placed, 0.0]


def test_suite_does_not_fill_the_checkout_cache():
    """conftest disables the persistent cache for the suite and its
    subprocesses: the chip tool copies the tree as it stands."""
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
