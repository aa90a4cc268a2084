"""The classifiers' solver programs compiled for a described TPU v5e
(tests/aot_described.py): the guard against the flat carry's return."""

import functools

import pytest

from aot_described import aot, topo  # noqa: F401 — fixtures

HIDDEN, WORKERS = 512, 8            # reduced: about 3 s a program
W1_BYTES = HIDDEN * 1024 * 4


@pytest.fixture(scope="module")
def program_text(aot, topo):
    """A program of scripts/aot_v5e_hlo.py at the reduced widths,
    compiled once for the file → its scheduled HLO text."""
    @functools.cache
    def text(program):
        return aot.compile_program(program, topo, hidden=HIDDEN,
                                   workers=WORKERS).as_text()
    return text


@pytest.mark.parametrize("program", ["bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_relayout_of_every_workers_parameters(aot, program_text, program):
    """Outside the fused computations no `copy` and no `slice` has a
    result as large as half of W1 times the workers on the chip (a bf16
    W1 of every worker).  With the flat vector carried through the local
    solver each program had three to five: W1 cut out of `[workers, P]`,
    re-laid out as a matrix, and its gradient re-laid out to be
    concatenated back (PERF.md §6, PR 25)."""
    text = program_text(program)
    assert "fusion(" in text            # the reader sees the program
    assert aot.big_relayouts(text, WORKERS * W1_BYTES // 2) == []


@pytest.mark.parametrize("program", ["bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_worker_has_a_w1_before_its_first_gradient(aot, program_text,
                                                      program):
    """In the order the device runs the program, nothing makes a
    float32 `[workers, H, F]` array before the first matrix product of
    the first local step's gradient: that step reads the shared leaves,
    and a worker's first own W1 is what its parameter step writes
    (models/task.py `local_steps`, PR 30).  With all k steps a scan
    from the shared leaves, every program began a clock with a
    `broadcast` of W1 to every worker (`broadcast_in_dim` here, before
    the scan's `while`): 1.08 GB at the cells' size, and a scratch of
    3.9332 GB for `bsp_scan` against 2.8422 GB now (compiled here for
    the described chip at 64 workers x H=4096)."""
    assert aot.made_before(program_text(program), "f32",
                           (WORKERS, HIDDEN, 1024),
                           aot.FIRST_GRAD_PRODUCT) == []
