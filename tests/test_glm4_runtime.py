"""The `glm4_moe_lite` family through the runtime: its record for the
contract every language-model family is held to
(tests/lm_family_contract.py), and the fused loop's second call and
published snapshots, which the frame does for any folded task and this
file shows on this one.  tests/test_glm4_moe_lite.py holds the model
against its reference."""

import numpy as np

from kafka_ps_tpu.models import glm4_moe_lite as glm
from kafka_ps_tpu.models import lm_common as lm
from lm_family_contract import *  # noqa: F401,F403 — the contract's cases
from lm_family_contract import Family


def reads(c):
    assert (c.num_hidden_layers, c.first_k_dense_replace,
            c.num_nextn_predict_layers) == (2, 1, 1)
    assert (c.n_routed_experts, c.num_experts_per_tok) == (8, 2)


def counted(task, counters):
    c = task.arch
    # every token chooses 2 of 8 experts in the expert layer and in the
    # MTP module's; 32 updates x (k + 1) passes x 2 rows
    slots = 32 * 3 * 2 * c.sequence_length * task.slots_a_token
    assert 0 < counters["moe.assignments_here"] <= slots
    assert counters["moe.assignments_here"] \
        + counters["moe.assignments_away"] == slots


FAMILY = Family(
    name="glm4_moe_lite", module=glm,
    tiny="benchmark/families/glm4-moe-lite/tiny.model.json",
    digests="glm4_tiny_stablehlo.json", reads=reads, counted=counted,
    counter_names=lm.COUNTERS,
    slots_a_token=2 * 2,        # 2 experts in the expert layer and in MTP's
    own=("load_config",))       # and no `num_params` of its own


def test_a_second_fused_call_takes_up_where_the_first_ended(folded_app):
    app = folded_app()
    app.run_fused_bsp(max_server_iterations=16 * 2)
    first = np.asarray(app.server.theta).copy()
    assert app.server.iterations == 32
    app.run_fused_bsp(max_server_iterations=24 * 2)
    assert app.server.iterations == 48
    assert np.any(np.asarray(app.server.theta) != first)
    assert app.server.last_metrics is not None


def test_a_snapshot_inside_a_fused_call_is_of_its_clock(folded_app):
    """With the serving plane attached, every chunk boundary publishes
    the parameters that hold every delta up to the clock it is stamped
    with, not the ones the call began with."""
    app = folded_app()
    app.enable_serving()
    published = []
    publish = app.server.serving.publish

    def recording(theta, clock, **kw):
        published.append((int(clock), np.asarray(theta).copy()))
        return publish(theta, clock, **kw)
    app.server.serving.publish = recording
    app.run_fused_bsp(max_server_iterations=16 * 2)
    assert [c for c, _ in published] == [8, 16, 16]
    until8 = folded_app()
    until8.run_fused_bsp(max_server_iterations=8 * 2)
    np.testing.assert_array_equal(published[0][1],
                                  np.asarray(until8.server.theta))
    np.testing.assert_array_equal(published[1][1],
                                  np.asarray(app.server.theta))
    app.close_serving()
