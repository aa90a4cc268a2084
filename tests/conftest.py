"""Test env: 8 virtual CPU devices so multi-chip sharding paths are exercised
without TPU hardware (mirrors the reference's strategy of simulating N logical
workers in one JVM, BaseKafkaApp.java:25,70 — here N virtual XLA devices in
one process)."""

import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The CLI start-up hook places a persistent compile cache inside the
# checkout (utils/device.py).  The suite — in-process CLI calls and the
# subprocesses that inherit this environment — must not fill it: the
# chip tool copies the tree as it stands.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# What the families' files share lies in two modules that are no test
# files: their assertions are rewritten as a test file's are.
pytest.register_assert_rewrite("lm_family_contract", "aot_described")

# Lock-order detector: records every OrderedLock acquisition across the
# whole session and fails it on acquisition-order cycles (potential
# deadlocks).  Disable for one run with LOCKGRAPH=0.
pytest_plugins = ("kafka_ps_tpu.analysis.pytest_plugin",)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process end-to-end jobs (seconds each)")


@pytest.fixture
def the_tpus_branch(monkeypatch):
    """The attention core, a head's norm and RoPE, the expert layer's
    placement and the state-space scan as the chip runs them, on the
    CPU: every `jax.lax.platform_dependent` takes its `tpu` branch and
    the kernels run in Pallas's interpreter.  The test's own steering; the program
    has no option that does this."""
    import jax

    from kafka_ps_tpu.models import (attention_kernel, norm_rope_kernel,
                                     placement_kernel, ssd_kernel)
    kernel, multiply = attention_kernel.attend, placement_kernel.multiply
    norm_rope, scan = norm_rope_kernel.norm_rope, ssd_kernel.scan
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(
        attention_kernel, "attend",
        lambda q, k, v, window, block: kernel(q, k, v, window, block, True))
    monkeypatch.setattr(
        norm_rope_kernel, "norm_rope",
        lambda x, w, cos, sin, eps, scale=1.0: norm_rope(
            x, w, cos, sin, eps, scale, True))
    monkeypatch.setattr(ssd_kernel, "scan", lambda *args: scan(*args, True))
    monkeypatch.setattr(
        placement_kernel, "multiply",
        lambda x, plan, back, passes, chunk=None: multiply(
            x, plan, back, passes, chunk, True))
