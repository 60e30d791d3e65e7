"""``ops/conv_update.py``: a causal depthwise convolution's one-token update
on the stacked leaf of windows, against the convolution over the whole
sequence (``numpy``, float64).  Plain XLA on every platform: what the v5e
compiler makes of it is pinned in ``tests/test_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.conv_update import conv_update


def causal_conv(xs, w):
    """xs ``[T, B, C]``, w ``[K, C]`` -> ``[T, B, C]``: position ``t`` sees
    inputs ``t-K+1 .. t``, zeros before the start."""
    k = w.shape[0]
    padded = np.concatenate([np.zeros((k - 1,) + xs.shape[1:]), xs])
    return sum(padded[j:j + len(xs)] * w[j] for j in range(k))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k,c", [(4, 8), (4, 128), (2, 24), (5, 16)],
                         ids=lambda v: str(v))
def test_token_by_token_is_the_convolution_over_the_sequence(k, c, dtype):
    """Seven tokens through layer 1 of three: every step's result is the
    sequence's convolution at that position, the window after it is the last
    ``K-1`` inputs, oldest first (zeros before the start), in the leaf's
    dtype, and the other layers' windows are what they were, to the bit."""
    rng = np.random.default_rng(k * c)
    xs = rng.normal(size=(7, 2, c)).astype(np.float32)
    w = rng.normal(size=(k, c)).astype(np.float32)
    others = rng.normal(size=(3, 2, (k - 1) * c))
    leaf = jnp.asarray(others, dtype).at[1].set(0)
    start = np.asarray(leaf, np.float32)
    held = np.asarray(jnp.asarray(xs, dtype), np.float64)  # as the leaf holds
    step = jax.jit(conv_update, static_argnums=1)
    for t, x in enumerate(xs):
        conv, leaf = step(leaf, 1, x, w)
        seen = np.concatenate([held[:t], xs[t:t + 1]])  # the newest unrounded
        np.testing.assert_allclose(conv, causal_conv(seen, w)[t], atol=1e-5)
        window = np.concatenate([np.zeros((k - 1, 2, c)), held[:t + 1]])[-(
            k - 1):]
        np.testing.assert_array_equal(
            np.asarray(leaf[1], np.float64),
            window.transpose(1, 0, 2).reshape(2, -1))
        for other in (0, 2):
            np.testing.assert_array_equal(
                np.asarray(leaf[other], np.float32), start[other])
    assert leaf.dtype == jnp.dtype(dtype) and conv.dtype == jnp.float32


def test_a_donated_leaf_comes_back_as_the_same_buffer():
    """The engine donates the cache: the update is in place, the result has
    the leaf's shape and dtype and the donated buffer is given up."""
    leaf = jnp.ones((2, 3, 3 * 8), jnp.float32)
    step = jax.jit(conv_update, static_argnums=1, donate_argnums=0)
    conv, new = step(leaf, 0, jnp.full((3, 8), 2.0), jnp.ones((4, 8)))
    assert leaf.is_deleted()
    np.testing.assert_array_equal(conv, np.full((3, 8), 5.0))
    np.testing.assert_array_equal(new[0, :, -8:], np.full((3, 8), 2.0))
    np.testing.assert_array_equal(new[1], np.ones((3, 24)))
