"""``ops/latent_attention.py``: one query token a slot against its latents in
the stacked cache as ONE pipelined pass, its Pallas kernel run in interpret
mode on the CPU against the XLA loop it replaces on a TPU
(``latent_attention_xla``) and against a dense float64 softmax written here,
at the three served head / channel shapes (Mistral-4's ``H`` 32, ``C`` 320;
Kimi-Linear's 32, 576; LongCat's 64, 576) with the positions cut small.
Kernel and loop run the same online softmax over the same blocks, and in
interpret mode the same operations: where they are compared, they are
compared bit for bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.decode_attention import extent_step

STEP = 512
# a bfloat16 product's rounding on a result of magnitude ~1 (the tolerance of
# ``tests/test_decode_live_extent.py``)
TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Shape:
    """``h`` heads over ``c`` channels of which ``rkv`` are weighed, ``b``
    slots of ``t`` positions, ``layers`` attentions in the leaf."""
    h: int
    c: int
    rkv: int
    b: int = 4
    t: int = 2048
    layers: int = 2

    def __str__(self):
        return f"h{self.h}_c{self.c}_b{self.b}_t{self.t}"


SERVED = [Shape(32, 320, 256), Shape(32, 576, 512), Shape(64, 576, 512)]


def draw(s: Shape, seed=0):
    """(leaf ``[layers, b, t, c]``, qc ``[b, h, c]``, latent_self ``[b, c]``)
    in bfloat16, scores of order one."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    return (normal(s.layers, s.b, s.t, s.c), normal(s.b, s.h, s.c),
            normal(s.b, s.c))


def scale_of(s: Shape):
    return float(s.c) ** -0.5


def kernel(s: Shape, leaf, layer, qc, own, pos, slots=None):
    return jax.jit(functools.partial(
        la.latent_attention, rkv=s.rkv, scale=scale_of(s), force_pallas=True,
        slots=slots), static_argnums=1)(
            leaf, layer, qc, own, jnp.asarray(pos, jnp.int32))


def loop(s: Shape, leaf, layer, qc, own, pos):
    return jax.jit(functools.partial(
        la.latent_attention_xla, rkv=s.rkv, scale=scale_of(s)),
        static_argnums=1)(leaf, layer, qc, own, jnp.asarray(pos, jnp.int32))


def dense(s: Shape, leaf, layer, qc, own, pos):
    """softmax over a row's ``[0, pos)`` and its own latent, in float64."""
    leaf, qc, own = (np.asarray(a, np.float64) for a in (leaf, qc, own))
    out = np.zeros((s.b, s.h, s.rkv))
    for b, n in enumerate(pos):
        keys = np.concatenate([leaf[layer, b, :n], own[b:b + 1]])
        scores = qc[b] @ keys.T * scale_of(s)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ keys[:, :s.rkv]
    return out


@pytest.mark.parametrize("slots", [None, 2], ids=["by_shape", "two_a_cell"])
@pytest.mark.parametrize("s", SERVED, ids=str)
def test_kernel_is_the_xla_loop_and_the_dense_softmax(s, slots):
    """Rows at 1, ``step``, ``step + 1`` and ``t - 1`` cached positions, so
    every block is live; then an idle row (``pos`` 0) beside contexts of
    under two blocks, so half the grid's steps are dead."""
    leaf, qc, own = draw(s)
    for pos in ([1, STEP, STEP + 1, s.t - 1], [0, 700, STEP, 3]):
        got = kernel(s, leaf, 1, qc, own, pos, slots)
        assert got.dtype == jnp.float32 and got.shape == (s.b, s.h, s.rkv)
        np.testing.assert_array_equal(got, loop(s, leaf, 1, qc, own, pos))
        np.testing.assert_allclose(got, dense(s, leaf, 1, qc, own, pos),
                                   atol=TOL, rtol=TOL)


@pytest.mark.parametrize("s", SERVED, ids=str)
def test_a_rows_bits_do_not_depend_on_how_far_its_neighbours_make_it_read(s):
    """The live bound is the batch's: one block, two, or all of them, by the
    neighbours' contexts.  Rows 0 and 1 (idle, and 300 positions) must not
    see the difference, whichever cell they share with whom."""
    leaf, qc, own = draw(s, seed=1)
    rows = {}
    for others in (3, STEP + 1, s.t - 1):
        for slots in (None, 2, 1):
            got = kernel(s, leaf, 0, qc, own, [0, 300, others, others], slots)
            rows.setdefault("idle", []).append(np.asarray(got[0]))
            rows.setdefault("short", []).append(np.asarray(got[1]))
    for name, seen in rows.items():
        assert np.isfinite(seen[0]).all(), name
        for other in seen[1:]:
            np.testing.assert_array_equal(seen[0], other, err_msg=name)


@pytest.mark.parametrize("slots", [None, 1], ids=["by_shape", "one_a_cell"])
def test_nothing_beyond_the_live_blocks_or_of_another_layer_is_read(slots):
    """Not-a-number everywhere the pass has no business: the blocks beyond
    the batch's longest context and the other attentions' slices (a block a
    cell fetches AHEAD, the next cell's first, is live by construction)."""
    s = dataclasses.replace(SERVED[1], layers=3)
    leaf, qc, own = draw(s, seed=2)
    pos = [2 * STEP, 1, 40, 0]
    want = kernel(s, leaf, 1, qc, own, pos, slots)
    poisoned = leaf.at[:, :, 2 * STEP:].set(jnp.nan).at[0].set(
        jnp.nan).at[2].set(jnp.nan)
    np.testing.assert_array_equal(
        kernel(s, poisoned, 1, qc, own, pos, slots), want)
    assert np.isfinite(want).all()


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layer_reads_its_own_slice_of_the_stack(layer):
    s = dataclasses.replace(SERVED[0], layers=3, t=1024)
    leaf, qc, own = draw(s, seed=3)
    pos = [1023, 5, 512, 600]
    got = kernel(s, leaf, layer, qc, own, pos)
    np.testing.assert_array_equal(
        got, kernel(s, leaf[layer][None], 0, qc, own, pos))
    np.testing.assert_allclose(got, dense(s, leaf, layer, qc, own, pos),
                               atol=TOL, rtol=TOL)


def test_one_lowered_kernel_serves_every_layer_of_a_step():
    """The layer is a prefetched operand, not a constant of the kernel: the
    attentions of a step are calls of ONE lowered function."""
    s = dataclasses.replace(SERVED[0], layers=3, t=1024)
    leaf, qc, own = draw(s)
    pos = jnp.asarray([1023, 5, 512, 600], jnp.int32)

    def step(leaf, qc, own, pos):
        return sum(la.latent_attention(
            leaf, layer, qc, own, pos, rkv=s.rkv, scale=scale_of(s),
            force_pallas=True) for layer in range(s.layers))

    text = jax.jit(step).lower(leaf, qc, own, pos).as_text()
    assert text.count("call @_call") == s.layers
    assert text.count("func.func private @_call") == 1


@pytest.mark.parametrize("b,h,c,t,want", [
    (32, 32, 320, 16384, 16),  # Mistral-4's cell: a block of 5.2 MB
    (64, 32, 576, 4096, 8),    # Kimi-Linear's: 4.7 MB (all 64: 37.7 MB)
    (32, 64, 576, 2048, 8),    # LongCat's
    (3, 32, 576, 2048, 1),     # no power of two divides three slots
    (4, 32, 320, 2048, 4),     # fewer slots than the memory would hold
])
def test_the_shapes_say_how_many_slots_a_cell_carries(b, h, c, t, want):
    assert la.slots_per_cell(b, c, extent_step(t), 2) == want
    assert want * c * extent_step(t) * 2 <= la._BLOCK_BYTES


@pytest.mark.parametrize("case,error", [
    (dict(t=512), "several extents"),        # one extent: ``mla``'s own way
    (dict(c=328, rkv=256), "whole tiles"),   # channels no sublane tile
    (dict(c=320, rkv=192), "whole tiles"),   # weighed channels no lane tile
    (dict(slots=3), "not a multiple"),
])
def test_what_the_kernel_cannot_tile_it_refuses_by_name(case, error):
    slots = case.pop("slots", None)
    s = dataclasses.replace(SERVED[0], **case)
    leaf, qc, own = draw(s)
    with pytest.raises(ValueError, match=error):
        kernel(s, leaf, 0, qc, own, [1, 2, 3, 4], slots)


@pytest.mark.parametrize("s", [SERVED[0], dataclasses.replace(
    SERVED[0], c=328)], ids=str)
def test_off_a_tpu_the_unforced_way_is_the_xla_loop(s):
    leaf, qc, own = draw(s)
    pos = jnp.asarray([1, 700, 0, s.t - 1], jnp.int32)
    unforced = jax.jit(lambda *a: la.latent_attention(
        a[0], 1, *a[1:], rkv=s.rkv, scale=scale_of(s)))
    assert "pallas" not in unforced.lower(leaf, qc, own, pos).as_text()
    np.testing.assert_array_equal(unforced(leaf, qc, own, pos),
                                  loop(s, leaf, 1, qc, own, pos))
