"""The chunked state-space scan as a kernel (models/ssd_kernel.py) in
Pallas's interpreter on the CPU, at sizes the kernel takes — chunks of
128 and 256 tokens, a state of 128 channels, heads of 64 channels two
to a lane vector and of 128 — through `nemotron_h.ssd_chunked`, against
today's einsums and against the recurrence a step a token, and which of
the two `ssd_chunked` traces at which size.

The kernel rounds each product's operands to bfloat16 (the chip's
default precision for a float32 product), so against the float32
einsums it stands at 2e-3 to 8e-3 of the largest value; a changed mask,
a head read from another group or a lost chunk state stands at 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import nemotron_h as nh
from kafka_ps_tpu.models import ssd_kernel

CHUNK, STATE = 128, 128
# (groups, heads a group, channels a head)
FORMS = [
    (1, 8, 64),     # one group read by every head
    (2, 8, 64),     # several groups, a block of heads each
    (1, 64, 64),    # one group over two blocks of heads
    (1, 8, 128),    # heads of a whole lane vector
]


def inputs(rows, chunks, form, seed=0, chunk=CHUNK):
    groups, heads, p = form
    s, h = chunks * chunk, groups * heads
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (rows, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    return (normal(rows, s, h, p), dt, a, normal(rows, s, groups, STATE),
            normal(rows, s, groups, STATE), normal(h))


def scan(x, dt, a, bm, cm, d, chunk=CHUNK):
    return nh.ssd_chunked(x, dt, a, bm, cm, chunk, d)


def a_step_a_token(x, dt, a, bm, cm, d):
    """The definition: `H_t = exp(Δ_t A) H_t−1 + Δ_t x_t ⊗ B_t`, `y_t =
    H_t C_t + D x_t`, from a zero state, float32 at the highest
    precision."""
    r = x.shape[2] // bm.shape[2]
    bm, cm = (jnp.repeat(m, r, axis=2) for m in (bm, cm))

    def step(state, at):
        x_t, dt_t, b_t, c_t = at            # [B, h, p], [B, h], [B, h, n]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision="highest")
    b, _, h, p = x.shape
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, bm.shape[-1])),
                        tuple(jnp.swapaxes(m, 0, 1)
                              for m in (x, dt, bm, cm)))
    return jnp.swapaxes(y, 0, 1) + d[:, None] * x


def values_and_gradients(fn, seen, *args):
    (_, out), grads = jax.value_and_grad(
        lambda *args: (lambda out: (jnp.sum(out * seen), out))(fn(*args)),
        argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(*args)
    return (out, *grads)


@pytest.fixture
def einsums(monkeypatch):
    """`scan` as today's einsums, whatever the shape."""
    def run(*args, **chunk):
        with monkeypatch.context() as m:
            m.setattr(ssd_kernel, "takes", lambda *shape: False)
            return scan(*args, **chunk)
    return run


@pytest.mark.parametrize("form", FORMS, ids=lambda f: "g%d-r%d-p%d" % f)
def test_the_kernel_path_is_the_einsums_and_the_recurrence(
        the_tpus_branch, einsums, form):
    """`ssd_chunked` through the kernels (interpret mode) on a row of
    THREE chunks — the state handed on twice, and its cotangent handed
    back twice — against its einsum path AND the recurrence a step a
    token: the values and the gradients of all six inputs, in both
    forms of (groups, heads a group), over two blocks of one group's
    heads, and at heads of 128 channels."""
    groups, heads, p = form
    args = inputs(1, 3, form, seed=heads + p)
    assert ssd_kernel.takes(args[0].shape, groups, STATE, CHUNK)
    assert ssd_kernel.heads_a_block(heads, p, STATE, CHUNK) == min(heads, 32)
    seen = jnp.asarray(np.random.default_rng(1).standard_normal(
        args[0].shape), jnp.float32)
    got = values_and_gradients(scan, seen, *args)
    for other in (einsums, a_step_a_token):
        want = values_and_gradients(other, seen, *args)
        for name, a, b in zip(("y", "dx", "dΔ", "dA", "dB", "dC", "dD"), got,
                              want):
            assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
            assert float(jnp.max(jnp.abs(a - b))) <= 0.012 * float(
                jnp.max(jnp.abs(b))), (name, other)


def test_the_kernel_path_at_chunks_of_256(the_tpus_branch, einsums):
    """The Granite cell's chunk length (two lane vectors of positions a
    chunk), two chunks, 2 rows: values and gradients against the
    einsums.  (dA sums, over a row, row sums LESS column sums of the
    same `[Q, Q]` array: the operands' rounding, 4e-3 a term, stands at
    2 to 3% of what is left of them at 256 — on the chip the two paths
    round alike and part by 2e-6, chip_smoke.py's `ssd_scan`.)"""
    form = (1, 8, 64)
    args = inputs(2, 2, form, seed=7, chunk=256)
    assert ssd_kernel.takes(args[0].shape, 1, STATE, 256)
    seen = jnp.asarray(np.random.default_rng(2).standard_normal(
        args[0].shape), jnp.float32)
    got = values_and_gradients(lambda *a: scan(*a, chunk=256), seen, *args)
    want = values_and_gradients(lambda *a: einsums(*a, chunk=256), seen,
                                *args)
    for name, a, b in zip(("y", "dx", "dΔ", "dA", "dB", "dC", "dD"), got,
                          want):
        assert float(jnp.max(jnp.abs(a - b))) <= (
            0.04 if name == "dA" else 0.012) * float(
                jnp.max(jnp.abs(b))), name


def test_the_states_kept_for_the_backward_pass_are_the_entering_ones(
        the_tpus_branch):
    """What the custom_vjp keeps beside its inputs: the state that
    ENTERS each chunk (zero before the first), as `chunks_scanned`
    hands them on."""
    form = (2, 8, 64)
    x, dt, a, bm, cm, d = inputs(1, 3, form, seed=3)
    b, s, h, p = x.shape
    g, q = form[0], CHUNK
    xd = x * dt[..., None]
    cum = jnp.cumsum((dt * a).reshape(b, s // q, q, h), axis=2)
    packed = jnp.concatenate([m.reshape(b, s, -1) for m in (x, bm, cm)], -1)
    _, (*_, entering) = ssd_kernel._scan_fwd(
        packed, dt, cum.reshape(b, s, h), d, g, STATE, q, True)
    assert entering.shape == (b, s // q, h * p, STATE)
    assert float(jnp.max(jnp.abs(entering[:, 0]))) == 0.0
    # by the definition: a step a token, the state read at chunk starts
    state, want = jnp.zeros((h, p, STATE)), []
    per_head = lambda m: jnp.repeat(m[0], h // g, axis=1)
    for t in range(s):
        if t % q == 0:
            want.append(state)
        state = (jnp.exp(dt[0, t] * a)[:, None, None] * state
                 + xd[0, t][:, :, None] * per_head(bm)[t][:, None, :])
    want = jnp.stack(want).reshape(1, s // q, h * p, STATE)
    assert float(jnp.max(jnp.abs(entering - want))) <= 0.012 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shape, groups, state, chunk, taken", [
    # the two cells' own
    ((1, 2048, 64, 64), 1, 128, 256, True),
    ((1, 1024, 64, 64), 8, 128, 128, True),
    ((2, 256, 8, 128), 1, 128, 128, True),
    # a chunk of 16 tokens (the tiny model files'), a state of 64, an
    # odd number of 64-channel heads, heads short of a sublane tile a
    # group, heads of 32 channels, a row that is no whole number of
    # chunks, and a chunk too long for a step's VMEM
    ((1, 64, 8, 64), 1, 128, 16, False),
    ((1, 256, 8, 64), 1, 64, 128, False),
    ((1, 256, 7, 64), 1, 128, 128, False),
    ((1, 256, 8, 64), 2, 128, 128, False),
    ((1, 256, 8, 32), 1, 128, 128, False),
    ((1, 320, 8, 64), 1, 128, 128, False),
    ((1, 4096, 8, 64), 1, 128, 2048, False)],
    ids=["granite", "nemotron", "p128", "chunk16", "state64", "odd-heads",
         "four-a-group", "p32", "ragged-row", "chunk2048"])
def test_which_shapes_the_kernel_takes(shape, groups, state, chunk, taken):
    assert ssd_kernel.takes(shape, groups, state, chunk) is taken


@pytest.mark.parametrize("chunk", [16, 128])
def test_which_path_the_scan_traces(chunk):
    """At a shape the kernel takes the traced program holds BOTH ways
    to run the inside of a chunk under one `platform_index` switch (the
    platform picks at lowering: on the CPU the einsums); at a chunk of
    16 there is no switch and no kernel."""
    form = (1, 8, 64)
    x, dt, a, bm, cm, _ = (m[:, :2 * chunk] if m.ndim > 1 else m
                           for m in inputs(1, 2, form))
    text = str(jax.make_jaxpr(
        lambda *args: nh.ssd_chunked(*args, chunk))(x, dt, a, bm, cm))
    assert ("platform_index" in text) == (chunk == 128)
    assert ("kps_ssd_forward" in text) == (chunk == 128)
    lowered = jax.jit(lambda *args: nh.ssd_chunked(*args, chunk)).lower(
        x, dt, a, bm, cm).as_text()
    assert "kps_ssd" not in lowered and "tpu_custom_call" not in lowered


def test_a_second_row_does_not_leak_into_the_first(the_tpus_branch):
    """Two rows at once through the kernels are each row alone, to the
    bit: the grid's first axis is the row."""
    form = (2, 8, 64)
    x, dt, a, bm, cm, d = inputs(1, 2, form, seed=5)
    alone = scan(x, dt, a, bm, cm, d)
    both = scan(jnp.concatenate([x, x[:, ::-1]]),
                jnp.concatenate([dt, 2.0 * dt]), a,
                jnp.concatenate([bm, -bm]), jnp.concatenate([cm, cm]), d)
    np.testing.assert_array_equal(np.asarray(both[0]), np.asarray(alone[0]))
    assert float(jnp.max(jnp.abs(both[1] - alone[0]))) > 1e-2
