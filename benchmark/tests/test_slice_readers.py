"""The readers that take the tail, the body and the second mode of the
window's slice times from the harness's own samples (clock_ms_tail_p95,
clock_ms_median, slow_slice_share), on hand-made windows and through
the harness."""

import types

import pytest

from helpers import run_cell
from test_span_reduce import metric

# 2-clock slices: a body at 126 ms, three of +10 ms, one stall
WINDOW = [0.126] * 95 + [0.136] * 3 + [0.220, 1.7]


def fake_run(times, clocks=2):
    return types.SimpleNamespace(call_times=list(times), call_clocks=clocks)


def test_tail_is_the_nearest_rank_95th_percentile_per_clock():
    read, spec = metric("clock_ms_tail_p95")
    # 100 samples: the 95th in order is the last of the body ...
    assert read(fake_run(WINDOW), spec) == pytest.approx(63.0)
    # ... and with six slow slices it is the first of the second mode
    assert read(fake_run([0.126] * 94 + [0.136] * 6), spec) \
        == pytest.approx(68.0)
    assert read(fake_run([0.1, 0.3], clocks=1), spec) == pytest.approx(300.0)


def test_median_is_the_body_per_clock_whatever_the_tail():
    read, spec = metric("clock_ms_median")
    assert read(fake_run(WINDOW), spec) == pytest.approx(63.0)
    assert read(fake_run([0.126] * 100), spec) == pytest.approx(63.0)


def test_slow_share_counts_the_slices_over_the_edge():
    read, spec = metric("slow_slice_share")
    assert spec["over_median"] == 1.04
    assert read(fake_run(WINDOW), spec) == pytest.approx(5.0)
    assert read(fake_run([0.126] * 50 + [0.130] * 50), spec) == 0.0


@pytest.mark.parametrize("name", ["clock_ms_tail_p95", "clock_ms_median",
                                  "slow_slice_share"])
def test_a_window_of_one_call_has_nothing_to_read(name):
    read, spec = metric(name)
    assert read(fake_run([20.0], clocks=456), spec) is None


def test_a_traced_pernode_run_reports_all_three(capsys):
    rc, result, out = run_cell(capsys, "mlp-4096.pernode-bsp", "4", trace=1)
    assert rc == 0 and result["correct"] is True, out
    got = result["metrics"]
    assert got["clock_ms_median"]["value"] > 0
    assert got["clock_ms_tail_p95"]["value"] >= got["clock_ms_median"]["value"]
    # what the harness prints of its window is what the reader returns
    printed = float(out.split(" p95 ")[1].split(" max ")[0])
    assert got["clock_ms_tail_p95"]["value"] == pytest.approx(printed, abs=1e-3)
    assert got["clock_ms_median"]["unit"] == "ms"
    assert 0 <= got["slow_slice_share"]["value"] <= 100
    assert "[bench] slow_slice_share:" in out
