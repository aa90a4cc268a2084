"""A head's norm and RoPE as a kernel (models/norm_rope_kernel.py) in
Pallas's interpreter on the CPU, at a `head_dim` of 128, against the
lines it stands for — `rope(rms_norm(x, w, eps), …)`, two slices and a
concatenation — and which of the two `lm_common.head_norm_rope` traces
at which size.

Both sides are float32 at every step and differ in the order of a sum
(the norm's mean over 128 channels, the weight's gradient over the
rows), so they stand at a few 1e-7 of the largest value; a wrong sign,
a half rolled the other way or a head stored to another's rows stands
at 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_ps_tpu.models import lm_common as lm
from kafka_ps_tpu.models import mellum, norm_rope_kernel

DIM, EPS, THETA = 128, 1e-6, 10000.0
TILE = 16           # positions a tile, whatever the heads (`tiles`)
YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 8192}


@pytest.fixture
def tiles(monkeypatch):
    """Tiles of TILE positions: the test's own steering (the kernel's
    are 128 positions of 32 heads and 1,024 of 4)."""
    monkeypatch.setattr(norm_rope_kernel, "tile_of",
                        lambda s, width: min(s, TILE))


def rotate_half(y, cos, sin):
    """The definition: `[-y2, y1]` by two slices and a concatenation."""
    y1, y2 = y[..., :DIM // 2], y[..., DIM // 2:]
    return (y * cos[:, None, :]
            + jnp.concatenate([-y2, y1], -1) * sin[:, None, :])


def plain_lines(rule, s):
    """(what the kernel is held to, the tables it is handed) for a
    rule: `lm_common.rope` itself on the normed rows; YaRN's
    frequencies and scale from `mellum.rope_tables` through the
    definition; the norm alone."""
    if rule == "none":
        return (lambda x, w: lm.rms_norm(x, w, EPS)), (None, None)
    if rule == "plain":
        inv = 1.0 / (THETA ** (jnp.arange(0, DIM, 2, dtype=jnp.float32)
                               / DIM))
        return ((lambda x, w: lm.rope(lm.rms_norm(x, w, EPS), THETA)),
                lm.rope_angles(s, inv))
    inv, scale = mellum.rope_tables(YARN, DIM)
    assert scale > 1.2 and inv[-1] < 1e-4
    cos, sin = lm.rope_angles(s, inv, scale)
    return ((lambda x, w: rotate_half(lm.rms_norm(x, w, EPS), cos, sin)),
            (cos, sin))


WHOLE, PARTIAL = 3 * TILE, 2 * TILE + 8   # the last tile half past the row


@pytest.mark.parametrize("rule,heads,positions", [
    ("plain", 1, WHOLE), ("plain", 4, PARTIAL), ("plain", 32, PARTIAL),
    ("yarn", 1, PARTIAL), ("yarn", 4, WHOLE),
    ("none", 1, WHOLE), ("none", 4, PARTIAL), ("none", 32, WHOLE)])
def test_the_kernel_is_the_plain_lines(tiles, rule, heads, positions):
    """`norm_rope_kernel.norm_rope` in interpret mode against
    `rope(rms_norm(x, w, eps))`: the values, dx and the norm weight's
    gradient, over plain tables, YaRN's and none, 1, 4 and 32 heads
    (the last leaves eight heads a tile of memory, `by_head`, and takes
    its cotangent so), rows that are and are not a whole number of the
    kernel's row tiles, two rows a slab, and a scale on the way out.
    (Each rule with each count of rows and 32 heads with and without
    tables, not the whole product: a case of 32 heads compiles for 4 s
    here.)"""
    rng = np.random.default_rng(heads + positions)
    shape = (2, positions, heads, DIM)
    assert norm_rope_kernel.takes(shape)
    assert norm_rope_kernel.by_head(heads) == (heads == 32)
    x, seen = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(2))
    w = jnp.asarray(1.0 + 0.1 * rng.standard_normal(DIM), jnp.float32)
    want_fn, (cos, sin) = plain_lines(rule, positions)
    scale = 0.25 if heads == 32 else 1.0

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w: (lambda out: (jnp.sum(out * seen), out))(fn(x, w)),
            argnums=(0, 1), has_aux=True))(x, w)
    (_, got), g_got = both(lambda x, w: norm_rope_kernel.norm_rope(
        x, w, cos, sin, EPS, scale, True))
    (_, want), g_want = both(lambda x, w: want_fn(x, w) * scale)
    for a, b in zip((got, *g_got), (want, *g_want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(
            jnp.max(jnp.abs(b)))


def test_a_roll_by_half_the_lanes_is_the_rotation():
    """One head, one position, channel j holding j + 1, at an angle of
    a quarter turn in every channel (cos 0, sin 1): the result is
    `[-y2, y1]` to the digit — the second half negated in the first
    half's place."""
    x = jnp.arange(1, DIM + 1, dtype=jnp.float32).reshape(1, 1, 1, DIM)
    x = jnp.broadcast_to(x, (1, 8, 1, DIM))
    rms = float(jnp.sqrt(jnp.mean(x[0, 0, 0] ** 2)))
    out = norm_rope_kernel.norm_rope(
        x, jnp.full((DIM,), rms), jnp.zeros((8, DIM)), jnp.ones((8, DIM)),
        0.0, 1.0, True)
    np.testing.assert_allclose(
        np.asarray(out[0, 3, 0]),
        np.concatenate([-np.arange(65, 129), np.arange(1, 65)]), rtol=1e-6)


def test_sizes_the_kernel_does_not_take_trace_the_plain_lines():
    """A `head_dim` of 16 (the families' tiny sizes), or positions that
    are no whole sublanes: `head_norm_rope` and its gradient hold no
    branch on the platform and no kernel, and the counter says 0."""
    for shape in ((2, 24, 4, 16), (1, 1001, 1, DIM)):
        assert not norm_rope_kernel.takes(shape)
        b, s, heads, d = shape
        tables = lm.rope_angles(s, 1.0 / (THETA ** (jnp.arange(
            0, d, 2, dtype=jnp.float32) / d)))
        text = jax.jit(jax.grad(
            lambda x, w: lm.head_norm_rope(x, w, EPS, *tables,
                                           scale=0.25).sum(),
            argnums=(0, 1))).lower(
                jax.ShapeDtypeStruct(shape, jnp.float32),
                jax.ShapeDtypeStruct((d,), jnp.float32)).as_text()
        assert "stablehlo.case" not in text and "custom_call" not in text
        assert "stablehlo.rsqrt" in text        # the reader sees the norm


class _Arch:
    sequence_length, num_hidden_layers = 64, 3
    num_attention_heads, num_key_value_heads, head_dim = 8, 2, DIM


def test_the_input_says_which_way_the_pass_runs(request):
    """At a size the kernel takes the program branches on the platform:
    lowered for the CPU it holds the plain lines and no kernel; with
    the TPU's branch taken (the test's own steering: no option of the
    program does this) the same call is the kernel, and
    `norm_rope_counts` counts its rows: 2 rows x 64 positions x 3
    layers x (8 + 2) heads, in units of 1,024."""
    c = _Arch()
    shape = (2, c.sequence_length, c.num_attention_heads, DIM)
    assert norm_rope_kernel.takes(shape)
    tables = lm.rope_angles(c.sequence_length, 1.0 / (THETA ** (jnp.arange(
        0, DIM, 2, dtype=jnp.float32) / DIM)))

    def run(x, w):
        return (lm.head_norm_rope(x, w, EPS, *tables, scale=0.5),
                jnp.stack(lm.norm_rope_counts(2, c)))
    text = jax.jit(run).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32),
        jax.ShapeDtypeStruct((DIM,), jnp.float32)).as_text()
    assert "custom_call" not in text and "stablehlo.concatenate" in text
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    w = jnp.asarray(1.0 + 0.1 * rng.standard_normal(DIM), jnp.float32)
    plain, counted = run(x, w)
    assert counted.tolist() == [3, 0]
    np.testing.assert_array_equal(
        np.asarray(plain),
        np.asarray(lm.rope(lm.rms_norm(x, w, EPS), THETA) * 0.5))
    request.getfixturevalue("the_tpus_branch")
    out, counted = run(x, w)
    assert counted.tolist() == [3, 3]
    assert 0 <= float(jnp.max(jnp.abs(out - plain))) <= 2e-6 * float(
        jnp.max(jnp.abs(plain)))
    assert lm.norm_rope_counts(2, type("Tiny", (_Arch,), {
        "head_dim": 16})()) == (3, 0)


def test_a_tile_holds_two_megabytes_of_whole_sublanes():
    """The rows a tile: 128 positions of q's 32 heads and 1,024 of k's 4
    at the cells' sizes, the row at most, never under a sublane tile."""
    assert norm_rope_kernel.tile_of(4096, 32 * DIM) == 128
    assert norm_rope_kernel.tile_of(4096, 4 * DIM) == 1024
    assert norm_rope_kernel.tile_of(256, 4 * DIM) == 256
    assert norm_rope_kernel.tile_of(4096, 1024 * DIM) == 8
