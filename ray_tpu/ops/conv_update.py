"""A causal depthwise convolution's one-token update, on its window where it
lies.

A layer's window is the last ``K-1`` inputs of its ``C`` channels, oldest
first, side by side on the lanes (``[B, (K-1) C]`` float32: ``K-1`` rows of
``C`` as an axis of their own would be padded to the TPU's tile of eight),
and the layers' windows are stacked in one cache leaf ``[layers, B, (K-1)
C]`` that the engine donates.  A decode step needs, a layer, with the
token's input ``x [B, C]`` and the taps ``w [K, C]``::

    conv   = sum_j window_j w_j + x w_{K-1}      (j = 0 .. K-2, in that order)
    window <- window_1 .. window_{K-2}, x

Every element of the window moves every step, so the least a step can do is
read the leaf once and write it once.  ``conv_update`` takes the WHOLE leaf
and the layer and hands the leaf back with that layer shifted: it reads the
layer's window out of the leaf, takes the ``K-1`` taps as lane slices of it
(``window.reshape(B, K, C)`` lays an axis of four on the sublanes: a padded
copy of the window a layer), and writes the shifted window over the layer it
read.  On the v5e that is one slice fusion into the chip's fast memory and
one in-place scatter fusion a layer (``tests/test_tpu_compile.py`` reads the
compiled steps).  While a step took ``leaf[i]`` a layer and stacked the new
windows at its end, the leaf it read was the donated buffer the stack was
written into, and the compiler copied every slice out first and then
rematerialised the copies: 414 slices of 3.3 MB a step in the Granite-4.0-H
cell where 36 are read (PERF.md, PR 63).

The ``optimization_barrier`` on the result is what keeps an update ONE
update.  A layer's scatter has two readers, the next layer's slice and the
next layer's scatter, and at 64 slots the v5e compiler rematerialises layer
0's for the second (``fusion.815.remat``, this PR; PR 60 saw the same on the
``ssm`` leaf, where the clone stepped the state twice).  Here both copies
shifted the same copied-out window to the same place, which is waste and not
a fault, but only while the read is a copy-out, and that is the compiler's
choice: behind the barrier the scatter has one reader and is not cloned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv_update(leaf, at: int, x, w):
    """leaf ``[layers, B, (K-1) C]``, x ``[B, C]`` float32, w ``[K, C]`` ->
    (the convolution ``[B, C]`` float32, before any bias; the leaf with
    layer ``at``'s window shifted by ``x``, in the leaf's dtype: the same
    buffer where the caller donated it)."""
    k, c = w.shape
    window = leaf[at].astype(jnp.float32)  # [B, (K-1) C]
    conv = sum(window[:, j * c:(j + 1) * c] * w[j] for j in range(k - 1))
    new = jnp.concatenate([window[:, c:], x], axis=1).astype(leaf.dtype)
    return (conv + x * w[k - 1],
            jax.lax.optimization_barrier(leaf.at[at].set(new)))
