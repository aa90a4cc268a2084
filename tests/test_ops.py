"""Pallas fused local-update kernel vs the XLA reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.data.synth import generate
from kafka_ps_tpu.models import logreg
from kafka_ps_tpu.ops import fused_update
from kafka_ps_tpu.utils.config import ModelConfig

CFG = ModelConfig(num_features=64, num_classes=5)


def _batch(n=48, seed=0, cfg=CFG):
    x, y = generate(n, cfg.num_features, cfg.num_classes, noise=1.0,
                    sparsity=0.5, seed=seed)
    mask = (np.arange(n) < n - 5).astype(np.float32)   # some masked rows
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask)


def _theta(cfg=CFG, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(scale=0.1, size=(cfg.num_params,)),
                       dtype=jnp.float32)


def test_kernel_matches_xla_path():
    x, y, mask = _batch()
    theta = _theta()
    d_ref, loss_ref = logreg.local_update(theta, x, y, mask, cfg=CFG)
    d_pl, loss_pl = fused_update.local_update(theta, x, y, mask, cfg=CFG,
                                              interpret=True)
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)
    assert float(loss_pl) == pytest.approx(float(loss_ref), rel=2e-4)


def test_kernel_batch_padding():
    # batch not a multiple of 8 exercises the pad-with-zero-mask path
    x, y, mask = _batch(n=37)
    theta = _theta()
    d_ref, _ = logreg.local_update(theta, x, y, mask, cfg=CFG)
    d_pl, _ = fused_update.local_update(theta, x, y, mask, cfg=CFG,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)


def test_kernel_all_masked_rows_no_nan():
    x, y, _ = _batch(n=16)
    mask = jnp.zeros((16,), jnp.float32)
    d_pl, loss = fused_update.local_update(_theta(), x, y, mask, cfg=CFG,
                                           interpret=True)
    assert np.isfinite(np.asarray(d_pl)).all()
    assert np.isfinite(float(loss))


def test_oversize_batch_streams_through_vmem():
    """A batch too big for whole-slab VMEM residency STREAMS through
    the tiled double-buffered kernel (docs/PERFORMANCE.md) — the trace
    counter proves which program was built."""
    cfg = ModelConfig(num_features=512, num_classes=5)
    big = fused_update._VMEM_BYTE_BUDGET // (4 * cfg.num_features) + 8
    big += (-big) % 8
    x, y, mask = _batch(n=big, cfg=cfg)
    assert not fused_update.fits_in_vmem(big, cfg.num_features)
    assert fused_update.select_program("logreg", cfg, big, "f32")[0] \
        == "streaming"
    streamed = fused_update.TRACE_COUNTS["streaming"]
    d, loss = fused_update.local_update(_theta(cfg), x, y, mask, cfg=cfg,
                                        interpret=True)
    assert fused_update.TRACE_COUNTS["streaming"] == streamed + 1
    d_ref, loss_ref = logreg.local_update(_theta(cfg), x, y, mask, cfg=cfg)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)
    assert float(loss) == pytest.approx(float(loss_ref), rel=2e-4)


def test_unstreamable_problem_refuses_with_shape_and_reason():
    # features so wide the weight set alone blows the VMEM budget —
    # neither the resident kernel nor a streaming tile can fit, and
    # there is no XLA fallback to hide behind
    assert not fused_update.fits_in_vmem(16, 150_000)
    assert fused_update.stream_tile(16, 150_000, "f32") is None
    cfg = ModelConfig(num_features=1024 * 256, num_classes=5)
    x = jnp.zeros((8, cfg.num_features), jnp.float32)
    y = jnp.ones((8,), jnp.int32)
    mask = jnp.ones((8,), jnp.float32)
    with pytest.raises(fused_update.PallasUnavailable,
                       match=r"features=262144.*VMEM budget"):
        fused_update.local_update(jnp.zeros((cfg.num_params,)), x, y, mask,
                                  cfg=cfg, interpret=True)
    # a feature axis the streaming tiles cannot lay out says so
    with pytest.raises(fused_update.PallasUnavailable,
                       match="multiple of 128"):
        fused_update.select_program(
            "logreg", ModelConfig(num_features=1000, num_classes=5),
            1 << 20, "f32")


# streaming needs a lane-multiple feature axis (stream_tile returns
# None otherwise — Mosaic tiling constraint on the streamed x blocks)
STREAM_CFG = ModelConfig(num_features=128, num_classes=5)


def test_streaming_kernel_multiple_tiles_matches_xla():
    """Several batch tiles per solver step: the per-tile gradient
    accumulation + end-of-step apply must equal the one-shot XLA step
    (tile 32 with batch 200 → 7 tiles, padded rows masked)."""
    x, y, mask = _batch(n=200, cfg=STREAM_CFG)
    theta = _theta(STREAM_CFG)
    d_ref, loss_ref = logreg.local_update(theta, x, y, mask,
                                          cfg=STREAM_CFG)
    d_st, loss_st = fused_update._stream_update(theta, x, y, mask,
                                                cfg=STREAM_CFG, tile=32,
                                                interpret=True)
    np.testing.assert_allclose(np.asarray(d_st), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)
    assert float(loss_st) == pytest.approx(float(loss_ref), rel=2e-4)


def test_streaming_kernel_decodes_slab_storage():
    """bf16 and int8 slab storage route through the streaming kernel
    (never the resident one) and decode in-kernel per batch tile; the
    result must match the XLA path fed the SAME decoded values."""
    from kafka_ps_tpu.compress.slab import decode_x, encode_x

    x, y, mask = _batch(n=96, cfg=STREAM_CFG)
    theta = _theta(STREAM_CFG)
    for kind in ("bf16", "int8"):
        stored = encode_x(kind, x)
        d_ref, loss_ref = logreg.local_update(theta, decode_x(stored),
                                              y, mask, cfg=STREAM_CFG)
        d_st, loss_st = fused_update.local_update(theta, stored, y, mask,
                                                  cfg=STREAM_CFG,
                                                  interpret=True)
        np.testing.assert_allclose(np.asarray(d_st), np.asarray(d_ref),
                                   rtol=2e-4, atol=2e-5, err_msg=kind)
        assert float(loss_st) == pytest.approx(float(loss_ref), rel=2e-4)


def test_mlp_streaming_kernel_matches_xla():
    from kafka_ps_tpu.compress.slab import decode_x, encode_x

    cfg = ModelConfig(num_features=128, num_classes=5, hidden_dim=32)
    task = _mlp_task(cfg)
    theta = task.init_params()
    x, y, mask = _batch(n=200, cfg=cfg)
    for kind in ("f32", "int8"):
        stored = encode_x(kind, x)
        d_ref, loss_ref = task.local_update(theta, decode_x(stored),
                                            y, mask)
        d_st, loss_st = fused_update._mlp_stream_update(
            theta, stored, y, mask, cfg=cfg, tile=32, interpret=True)
        np.testing.assert_allclose(np.asarray(d_st), np.asarray(d_ref),
                                   rtol=2e-4, atol=2e-5, err_msg=kind)
        assert float(loss_st) == pytest.approx(float(loss_ref), rel=2e-4)


def test_compiled_kernel_refuses_a_non_tpu_backend():
    """Without interpret=True the kernels mean "compiled Mosaic"; off
    the chip that is a refusal naming the backend, never the XLA
    solver under a pallas label."""
    assert jax.default_backend() == "cpu"      # tests/conftest.py
    x, y, mask = _batch(n=24)
    xs, ys, ms = x[None], y[None], mask[None]
    for call in (
            lambda: fused_update.local_update(_theta(), x, y, mask, cfg=CFG),
            lambda: fused_update.local_update_batched(
                _theta()[None], xs, ys, ms, cfg=CFG),
            lambda: fused_update.mlp_local_update(
                _mlp_task().init_params(), x, y, mask, cfg=MLP_CFG),
            lambda: fused_update.mlp_local_update_batched(
                _mlp_task().init_params()[None], xs, ys, ms, cfg=MLP_CFG),
            lambda: fused_update.program_name("logreg", CFG, 24, "f32")):
        with pytest.raises(fused_update.PallasUnavailable,
                           match="need a TPU backend, found 'cpu'"):
            call()


def test_out_of_range_label_loss_matches_xla_path():
    """An out-of-range label (y >= num_classes+1) must contribute ZERO
    loss in the kernel, exactly like jax.nn.one_hot's all-zero row in
    the XLA path — not hit a -1e30-masked padded class."""
    x, y, mask = _batch(n=16)
    y = y.at[3].set(CFG.num_classes + 7)     # invalid label, masked-in row
    theta = _theta()
    d_ref, loss_ref = logreg.local_update(theta, x, y, mask, cfg=CFG)
    d_pl, loss_pl = fused_update.local_update(theta, x, y, mask, cfg=CFG,
                                              interpret=True)
    assert float(loss_pl) == pytest.approx(float(loss_ref), rel=2e-4)
    assert abs(float(loss_pl)) < 1e6         # not blown up to ~1e30
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)


# -- MLP family kernel (ops/fused_update.mlp_local_update) -------------------

MLP_CFG = ModelConfig(num_features=64, num_classes=5, hidden_dim=32)


def _mlp_task(cfg=MLP_CFG):
    from kafka_ps_tpu.models.mlp import MLPTask
    return MLPTask(cfg)


def test_mlp_kernel_matches_xla_path():
    x, y, mask = _batch(cfg=MLP_CFG)
    task = _mlp_task()
    theta = task.init_params()
    d_ref, loss_ref = task.local_update(theta, x, y, mask)
    d_pl, loss_pl = fused_update.mlp_local_update(theta, x, y, mask,
                                                  cfg=MLP_CFG,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)
    assert float(loss_pl) == pytest.approx(float(loss_ref), rel=2e-4)


def test_mlp_kernel_hidden_not_lane_multiple():
    # hidden=32 < 128 exercises the H padding; hidden=160 crosses one
    # lane boundary (padded to 256) — padded units must stay exactly 0
    cfg = ModelConfig(num_features=64, num_classes=5, hidden_dim=160)
    x, y, mask = _batch(n=37, cfg=cfg)        # + odd batch padding
    task = _mlp_task(cfg)
    theta = task.init_params()
    d_ref, _ = task.local_update(theta, x, y, mask)
    d_pl, _ = fused_update.mlp_local_update(theta, x, y, mask, cfg=cfg,
                                            interpret=True)
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)


def test_mlp_kernel_all_masked_rows_no_nan():
    x, y, _ = _batch(n=16, cfg=MLP_CFG)
    mask = jnp.zeros((16,), jnp.float32)
    d, loss = fused_update.mlp_local_update(_mlp_task().init_params(),
                                            x, y, mask, cfg=MLP_CFG,
                                            interpret=True)
    assert np.isfinite(np.asarray(d)).all()
    assert np.isfinite(float(loss))


def test_mlp_oversize_hidden_refuses():
    """`--pallas --task mlp --hidden_dim 4096` cannot fit and says so
    instead of training on XLA — at every layer that can be asked."""
    from kafka_ps_tpu.runtime.worker import solver_program
    from kafka_ps_tpu.utils.config import PSConfig

    assert not fused_update.mlp_fits_in_vmem(1024, 1024, 4096)
    cfg = ModelConfig(num_features=1024, num_classes=5, hidden_dim=4096)
    task = _mlp_task(cfg)
    x, y, mask = _batch(n=16, cfg=cfg)
    with pytest.raises(fused_update.PallasUnavailable,
                       match=r"no pallas mlp kernel fits \(batch=16, "
                             r"features=1024, hidden=4096, slab=f32\)"):
        fused_update.mlp_local_update(task.init_params(), x, y, mask,
                                      cfg=cfg, interpret=True)
    with pytest.raises(fused_update.PallasUnavailable, match="hidden=4096"):
        solver_program(PSConfig(task="mlp", model=cfg,
                                use_pallas="interpret"))


def test_solver_program_names():
    from kafka_ps_tpu.runtime.worker import solver_program
    from kafka_ps_tpu.utils.config import PSConfig

    assert solver_program(PSConfig()) == "xla"
    assert solver_program(PSConfig(use_pallas="interpret")) \
        == "pallas-resident+batched"
    assert solver_program(PSConfig(use_pallas="interpret",
                                   use_gang=False)) == "pallas-resident"
    assert solver_program(PSConfig(use_pallas="interpret",
                                   slab_dtype="int8")) == "pallas-streaming"
    with pytest.raises(fused_update.PallasUnavailable, match="found 'cpu'"):
        solver_program(PSConfig(use_pallas=True))


def test_batched_streaming_members_run_the_kernel_per_member():
    """A gang release set over bf16/int8 member slabs has no grid
    kernel; the batched entry runs the streaming kernel once per member
    inside one jit (it used to drop to the vmapped XLA solver)."""
    from kafka_ps_tpu.compress.slab import encode_x

    k = 3
    theta = _theta(STREAM_CFG)
    thetas = jnp.stack([theta * (1 + 0.1 * i) for i in range(k)])
    parts = [_batch(n=96, seed=i, cfg=STREAM_CFG) for i in range(k)]
    ys = jnp.stack([p[1] for p in parts])
    masks = jnp.stack([p[2] for p in parts])
    for kind in ("bf16", "int8"):
        stored = [encode_x(kind, p[0]) for p in parts]
        xs = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stored)
        streamed = fused_update.TRACE_COUNTS["streaming"]
        ds, ls = fused_update.local_update_batched(
            thetas, xs, ys, masks, cfg=STREAM_CFG, interpret=True)
        assert fused_update.TRACE_COUNTS["streaming"] == streamed + k
        for i in range(k):
            d1, l1 = fused_update.local_update(
                thetas[i], stored[i], ys[i], masks[i], cfg=STREAM_CFG,
                interpret=True)
            np.testing.assert_allclose(np.asarray(ds[i]), np.asarray(d1),
                                       rtol=1e-6, atol=1e-7, err_msg=kind)
            assert float(ls[i]) == pytest.approx(float(l1), rel=1e-6)


def test_worker_pallas_dispatch_accepts_both_families():
    """use_pallas dispatches by task family in the per-node worker path
    (runtime/worker._solver_fns); on the CPU the kernels run where the
    config asks for the interpreter by name."""
    from kafka_ps_tpu.data.buffer import SlidingBuffer
    from kafka_ps_tpu.runtime import fabric as fabric_mod
    from kafka_ps_tpu.runtime.messages import KeyRange, WeightsMessage
    from kafka_ps_tpu.runtime.worker import WorkerNode
    from kafka_ps_tpu.utils.config import BufferConfig, PSConfig

    for task_name in ("logreg", "mlp"):
        cfg = PSConfig(
            num_workers=1, task=task_name, use_pallas="interpret",
            model=ModelConfig(num_features=16, num_classes=3,
                              hidden_dim=8),
            buffer=BufferConfig(min_size=4, max_size=32))
        buf = SlidingBuffer(16, cfg.buffer)
        x, y = generate(12, 16, 3, seed=0)
        for i in range(12):
            buf.add(dict(enumerate(x[i])), int(y[i]))
        fab = fabric_mod.Fabric()
        node = WorkerNode(0, cfg, fab, buf)
        node.on_weights(WeightsMessage(
            vector_clock=0,
            key_range=KeyRange(0, node.task.num_params),
            values=jnp.zeros(node.task.num_params)
            if task_name == "logreg" else node.task.init_params()))
        g = fab.poll(fabric_mod.GRADIENTS_TOPIC, 0)
        assert g is not None
        assert np.isfinite(np.asarray(g.values)).all()


def test_mlp_out_of_range_label_matches_jax_grad_semantics():
    """An out-of-range label row must contribute ZERO gradient in the
    MLP kernel — jax.grad of the one-hot CE (the XLA path) differentiates
    through an all-zero one-hot row, unlike logreg's closed form which
    keeps the softmax term (the two families deliberately differ;
    each kernel matches ITS OWN XLA path)."""
    x, y, mask = _batch(n=16, cfg=MLP_CFG)
    y = y.at[3].set(MLP_CFG.num_classes + 7)
    task = _mlp_task()
    theta = task.init_params()
    d_ref, loss_ref = task.local_update(theta, x, y, mask)
    d_pl, loss_pl = fused_update.mlp_local_update(theta, x, y, mask,
                                                  cfg=MLP_CFG,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(d_pl), np.asarray(d_ref),
                               rtol=2e-4, atol=2e-5)
    assert float(loss_pl) == pytest.approx(float(loss_ref), rel=2e-4)
