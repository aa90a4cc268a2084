"""The attention core as a kernel (models/attention_kernel.py) in
Pallas's interpreter on the CPU, at sizes the kernel takes — head_dim
128, and 64 two heads to a lane vector, tiles of 128 — against the
masked `[S, S]` definition (tests/test_afmoe.py `defined`), and which
of the two cores `lm_common.blocked_attention` traces at which size.

The kernel rounds each product's operands to bfloat16 (the chip's
default precision for a float32 product), so against the float32
definition it stands at 2e-3 to 7e-3 of the largest value; a changed
mask or a missed block stands at 1."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_afmoe import ROOT, defined

from kafka_ps_tpu.models import attention_kernel
from kafka_ps_tpu.models import lm_common as lm

KERNEL_BLOCK, KERNEL_DIM = 128, 128


def kernel_core(q, k, v, window):
    return attention_kernel.attend(q / np.sqrt(q.shape[-1]), k, v, window,
                                   KERNEL_BLOCK, True)


@pytest.mark.parametrize("shape", [
    # (head_dim, KV heads, query heads a KV head)
    (128, 2, 1), (128, 2, 3),
    # half a lane vector: two KV heads' query heads to a vector
    (64, 2, 1), (64, 2, 3), (64, 4, 4)],
    ids=lambda shape: "d%d-g%d-r%d" % shape)
@pytest.mark.parametrize("window", [
    256,            # a whole number of blocks
    200, 72,        # and not: the band's edge cuts a block
    None])          # a full layer
@pytest.mark.parametrize("windows", [1, 1.5, 3])
def test_the_kernel_is_the_masked_definition(windows, window, shape):
    """`attention_kernel.attend` in interpret mode against the
    definition, values and all three gradients, on rows of 1, 1.5 and 3
    times 256 tokens, sliding with a window that is and is not a whole
    number of blocks and full, 1, 3 and 4 query heads a KV head, heads
    of a whole lane vector and of half of one (2 and 4 KV heads: one
    pair and two).  And to the last digit where no rounding is: with
    every score 0 a query's output is the mean of the values it sees,
    and with one channel a key position (mod the head's channels) set
    to 1 that mean COUNTS the keys seen."""
    s = int(windows * 256)
    dim, groups, heads = shape
    assert attention_kernel.takes((1, s, groups, heads, dim), KERNEL_BLOCK)
    rng = np.random.default_rng(s + heads)
    q = jnp.asarray(rng.standard_normal((1, s, groups, heads, dim)),
                    jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, s, groups, dim)),
                        jnp.float32) for _ in range(2))
    seen = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def both(core):
        return jax.value_and_grad(
            lambda q, k, v: (lambda out: (jnp.sum(out * seen), out))(
                core(q, k, v, window)), argnums=(0, 1, 2), has_aux=True)
    (_, got), g_got = both(kernel_core)(q, k, v)
    (_, want), g_want = both(defined)(q, k, v)
    for a, b in zip((got, *g_got), (want, *g_want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 0.015 * float(
            jnp.max(jnp.abs(b)))
    counting = jnp.broadcast_to(jnp.asarray(
        np.arange(s)[:, None] % dim == np.arange(dim),
        jnp.float32)[None, :, None], v.shape)
    np.testing.assert_allclose(
        np.asarray(kernel_core(jnp.zeros_like(q), k, counting, window)),
        np.asarray(defined(jnp.zeros_like(q), k, counting, window)),
        rtol=1e-6, atol=0)


@pytest.mark.parametrize("q_shape,block,taken", [
    ((1, 4096, 4, 8, 128), 512, True),      # the Trinity cell's
    ((1, 4096, 8, 4, 64), 512, True),       # the LFM2 cell's: run as that
    ((1, 256, 2, 3, 64), 128, True),
    ((1, 256, 3, 2, 64), 128, False),       # a head without a neighbour
    ((1, 256, 1, 2, 64), 128, False),
    ((1, 256, 2, 2, 32), 128, False),
    ((1, 256, 2, 2, 96), 128, False),
    ((1, 256, 2, 2, 8), 128, False),
    ((1, 256, 2, 2, 256), 128, True),
    ((1, 256, 2, 2, 128), 64, False),       # half a lane vector of keys
    ((1, 256, 2, 2, 64), 64, False),
    ((1, 384, 2, 2, 64), 256, False),       # the tile does not divide the row
    ((1, 16384, 2, 2, 128), 512, True),     # a head's dk and dv: 2 M elements
    ((1, 32768, 2, 2, 128), 512, False),
    ((1, 16384, 2, 2, 64), 512, True),      # a pair's: a lane vector wide
    ((1, 32768, 2, 2, 64), 512, False)])
def test_what_the_kernel_takes(q_shape, block, taken):
    """Heads of whole lane vectors, and of half of one where the KV
    heads come in pairs — nothing else under 128 channels; tiles of
    whole lanes that divide the row; a row whose resident dk and dv, a
    lane vector wide for a pair, stay within RESIDENT_ELEMENTS."""
    assert attention_kernel.takes(q_shape, block) is taken
    assert int(lm.kernel_attends(q_shape, block)) == 0     # on the CPU


def test_the_kernel_reads_no_key_outside_a_tiles_span():
    """Four tiles of 128 under a window of 128: the third and fourth
    tiles' spans begin at key 128, so NaN in the first block of k and v
    reaches neither their output nor, with the loss on those tiles
    alone, their dq or the later keys' dk and dv — which equal what
    zeros there give.  (The first two tiles see that block, and theirs
    are NaN; the definition, which multiplies every value by its
    weight, reads NaN everywhere.)"""
    s, window, cut = 4 * KERNEL_BLOCK, KERNEL_BLOCK, 2 * KERNEL_BLOCK
    assert lm.key_span(2, KERNEL_BLOCK, window)[0] == KERNEL_BLOCK
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, s, 1, 2, KERNEL_DIM)),
                    jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, s, 1, KERNEL_DIM)),
                        jnp.float32) for _ in range(2))
    seen = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def results(first_block):
        def loss(q, k, v):
            out = kernel_core(q, k.at[:, :KERNEL_BLOCK].set(first_block),
                              v.at[:, :KERNEL_BLOCK].set(first_block), window)
            return jnp.sum((out * seen)[:, cut:]), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return [np.asarray(a)[:, cut:] for a in (out, *grads)]
    for got, want in zip(results(jnp.nan), results(0.0)):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
    poisoned = k.at[:, :KERNEL_BLOCK].set(jnp.nan)
    assert np.isnan(np.asarray(defined(q, poisoned, poisoned,
                                       window))).all()
    assert np.isnan(np.asarray(kernel_core(q, poisoned, poisoned,
                                           window))[:, :cut]).all()


@pytest.mark.parametrize("program", ["window", "window_grad", "full",
                                     "full_grad"])
def test_sizes_the_kernel_does_not_take_trace_the_plain_program(program):
    """A `head_dim` of 8 in tiles of 8: `blocked_attention` and its
    gradient lower, character for character, to the program the commit
    before the kernel traced (tests/fixtures/ holds the digests), with
    no branch on the platform in it."""
    stated = json.load(open(os.path.join(
        ROOT, "tests", "fixtures", "attention_core_tiny_stablehlo.json")))
    if stated["jax"] != jax.__version__:
        pytest.skip(f"the digests were written under jax {stated['jax']}; "
                    f"this is {jax.__version__}, whose printer may differ")
    q = jax.ShapeDtypeStruct((2, 24, 2, 3, 8), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 24, 2, 8), jnp.float32)
    assert not attention_kernel.takes(q.shape, 8)
    assert lm.kernel_attends(q.shape, 8) == 0

    def core(q, k, v):
        return lm.blocked_attention(
            q, k, v, window=12 if program.startswith("window") else None,
            block=8)
    if program.endswith("grad"):
        traced = jax.grad(lambda q, k, v: core(q, k, v).sum(),
                          argnums=(0, 1, 2))
    else:
        traced = core
    text = jax.jit(traced).lower(q, kv, kv).as_text()
    assert "stablehlo.case" not in text and "custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == stated["programs"][program]


@pytest.mark.parametrize("groups,dim", [(1, KERNEL_DIM), (2, 64)])
def test_the_input_says_which_core_runs(request, groups, dim):
    """At a size the kernel takes — heads of 128 channels, and of 64 in
    pairs — the program branches on the platform: lowered for the CPU
    it holds the plain tiles and no kernel; with the TPU's branch taken
    (the test's own steering: no option of the program does this) the
    same call is the kernel, and `kernel_attends` counts it."""
    q = jax.ShapeDtypeStruct((1, 256, groups, 2, dim), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 256, groups, dim), jnp.float32)

    def core(q, k, v):
        return (lm.blocked_attention(q, k, v, window=200,
                                     block=KERNEL_BLOCK),
                lm.kernel_attends(q.shape, KERNEL_BLOCK))
    text = jax.jit(core).lower(q, kv, kv).as_text()
    assert "custom_call" not in text and "dot_general" in text
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal(a.shape), jnp.float32)
               for a in (q, kv, kv))
    plain, engaged = core(q, k, v)
    assert int(engaged) == 0
    request.getfixturevalue("the_tpus_branch")
    out, engaged = core(q, k, v)
    assert int(engaged) == 1
    assert 0 < float(jnp.max(jnp.abs(out - plain))) <= 0.015 * float(
        jnp.max(jnp.abs(plain)))


@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("window", [None, 1, 72, 128, 129, 200, 512, 513,
                                    2048])
def test_the_kernels_band_is_key_spans(window, block):
    """The grid's index maps walk a tile's key blocks from
    `key_span`'s first to the tile's own, and the grid is as deep as
    the widest span, at every window: on, one under and one over a
    whole number of blocks, a single key, none."""
    for tiles in (1, 2, 3, 8):
        spans = [lm.key_span(t, block, window) for t in range(tiles)]
        assert attention_kernel._steps(tiles, block, window) == max(
            (hi - lo) // block for lo, hi in spans)
        for t, (lo, hi) in enumerate(spans):
            assert int(attention_kernel._first_block(t, block, window)) \
                * block == lo and hi == (t + 1) * block
