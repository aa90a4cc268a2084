"""The reader PR 43 brought (attn_norm_rope_self_share) on the hand-made
trace and HLO text of tests/fixtures/self_time_tiny.json, whose table
tests/test_self_time.py knows by hand: a share known by hand, nothing
(`None`, never a raise) on an untraced run and on a program without the
scope, and the counters printed beside it where the program has them."""

import pytest

from test_self_time_readers import HLO, texts, traced_run  # noqa: F401
from test_span_reduce import metric

# the fixture's norm (fusion.8, 0.25 s of an update's 10, under
# kps.lm.norm) as a head's norm and RoPE inside the attention block
NORM_ROPE = HLO.replace("/kps.lm.norm/", "/kps.attn/kps.attn.proj/"
                        "kps.attn.norm_rope/")


def test_the_share_is_known_by_hand(texts, capsys):
    read, spec = metric("attn_norm_rope_self_share")
    assert "/kps.lm.norm/" in HLO and spec["scope"] in NORM_ROPE
    texts["jit_scanned"] = [NORM_ROPE]
    run = traced_run()
    run.app.last_run["counters"] = {"attn.norm_rope_rows": 480,
                                    "attn.norm_rope_kernel_rows": 480}
    assert read(run, spec) == pytest.approx(2.5)
    out = capsys.readouterr().out
    assert "attn.norm_rope_rows 480, attn.norm_rope_kernel_rows 480" in out
    # the parent's program has the scope and not the counters
    run = traced_run()
    assert read(run, spec) == pytest.approx(2.5)
    assert "attn.norm_rope_rows" not in capsys.readouterr().out


def test_nothing_without_a_trace_or_the_scope(texts):
    read, spec = metric("attn_norm_rope_self_share")
    assert read(traced_run(), spec) is None         # no such scope
    texts["jit_scanned"] = [NORM_ROPE]
    run = traced_run()
    run.trace_dir = run.span_trace_data = None      # --trace 0
    assert read(run, spec) is None
    del texts["jit_scanned"]                        # no executable alive
    assert read(traced_run(), spec) is None
