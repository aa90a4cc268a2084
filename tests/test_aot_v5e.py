"""The solver programs compiled for a TPU v5e that is described, not
attached (scripts/aot_v5e_hlo.py): the guard against the flat carry's
return.  libtpu compiles from shapes alone; nothing runs and no time is
read.  Every compile of the suite for the described chip lives in this
one file, behind a fixture (one process loads libtpu and keeps it), and
skips where libtpu offers no topology."""

import importlib.util
import os

import pytest

HIDDEN, WORKERS = 512, 8            # reduced: about 3 s a program
W1_BYTES = HIDDEN * 1024 * 4


@pytest.fixture(scope="module")
def aot():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "aot_v5e_hlo.py")
    spec = importlib.util.spec_from_file_location("aot_v5e_hlo", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def topo(aot):
    try:
        return aot.describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("program", ["bsp_scan", "bsp_scan_mesh", "gang"])
def test_no_relayout_of_every_workers_parameters(aot, topo, program):
    """Outside the fused computations no `copy` and no `slice` has a
    result as large as half of W1 times the workers on the chip (a bf16
    W1 of every worker).  With the flat vector carried through the local
    solver each program had three to five: W1 cut out of `[workers, P]`,
    re-laid out as a matrix, and its gradient re-laid out to be
    concatenated back (PERF.md §6, PR 25)."""
    compiled = aot.compile_program(program, topo, hidden=HIDDEN,
                                   workers=WORKERS)
    text = compiled.as_text()
    assert "fusion(" in text            # the reader sees the program
    assert aot.big_relayouts(text, WORKERS * W1_BYTES // 2) == []
