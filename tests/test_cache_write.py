"""``write_token_to_cache`` (``ray_tpu/ops/decode_attention.py``): the one way
a decode step's token reaches a family's cache.

Against a NumPy loop, for both cache layouts the families have, bit for bit:
the written rows AND everything else.  Then the shape of the program: no
family's decode step may hold a cache-sized ``scatter`` or ``select_n`` (what
the write used to be; each costs at least a pass over the whole cache on the
v5e, ``tests/test_tpu_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (GPT2Config, LlamaConfig, LongcatConfig,
                            model_family)
from ray_tpu.ops.decode_attention import (tile_positions,
                                          write_token_to_cache)

# The families' two layouts.  On the TPU the kv leaf (last axis 128) has its
# positions on a tile's sublanes, 16 of bf16 or 8 of float32 a tile; the
# latent leaf (last axis no multiple of 128) on its 128 lanes.
LAYOUTS = {
    "kv[L,B,Hkv,T,D]": (lambda b, t: (2, b, 2, t, 128), 3),
    "latent[A,B,T,C]": (lambda b, t: (3, b, t, 24), 2),
}

# layout -> {case: (T, each slot's position)}
POSITIONS = {
    "kv[L,B,Hkv,T,D]": {
        # rows around both dtypes' tile edges, the last row, two slots at one
        "T64": (64, [0, 15, 16, 17, 63, 17, 7, 8]),
        # T no multiple of either tile: the last tile starts early
        "T44": (44, [0, 15, 16, 17, 43, 40, 32, 36, 31]),
        # T smaller than either tile: the tile is the whole axis
        "T5": (5, [0, 4, 2, 2]),
        # exactly one bf16 tile, two float32 tiles
        "T16": (16, [15, 0, 8, 7]),
        # a position outside [0, T) writes nothing
        "outside": (32, [32, 40, -1, 31]),
    },
    "latent[A,B,T,C]": {
        "T256": (256, [0, 127, 128, 129, 255, 129]),
        "T300": (300, [0, 127, 128, 255, 256, 299, 172, 171]),
        "T5": (5, [0, 4, 2, 2]),
        "T128": (128, [127, 0, 64]),
        "outside": (256, [256, 300, -1, 255]),
    },
}
CASES = [(layout, case) for layout, cases in POSITIONS.items()
         for case in cases]


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def test_the_cases_cross_the_tiles_the_helper_updates():
    assert tile_positions((2, 8, 2, 64, 128), jnp.bfloat16, 3) == 16
    assert tile_positions((2, 8, 2, 64, 128), jnp.float32, 3) == 8
    assert tile_positions((3, 8, 256, 24), jnp.bfloat16, 2) == 128
    assert tile_positions((12, 32, 12, 1024, 64), jnp.bfloat16, 3) == 128


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout,case", CASES)
def test_writes_each_slots_row_and_nothing_else(layout, case, dtype):
    shape_of, axis = LAYOUTS[layout]
    t, pos = POSITIONS[layout][case]
    shape = shape_of(len(pos), t)
    k_old, k_new = jax.random.split(jax.random.PRNGKey(t))
    old = jax.random.normal(k_old, shape).astype(dtype)
    new = jax.random.normal(
        k_new, shape[:axis] + shape[axis + 1:]).astype(dtype)

    want = np.array(old)
    for b, p in enumerate(pos):
        if 0 <= p < t:
            at = [slice(None)] * len(shape)
            at[1], at[axis] = b, p
            want[tuple(at)] = np.asarray(new)[:, b]

    write = jax.jit(lambda c, n, p: write_token_to_cache(c, n, p, axis),
                    donate_argnums=(0,))
    got = write(old, new, jnp.asarray(pos, jnp.int32))
    assert got.shape == shape and got.dtype == want.dtype
    np.testing.assert_array_equal(bits(got), bits(want))


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (loop bodies,
    closed calls, jitted functions)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from eqns_of(inner)


TINY = {
    "gpt2": lambda: GPT2Config.tiny(),
    "llama": lambda: LlamaConfig.tiny(),
    "longcat": lambda: LongcatConfig.tiny(),
}


@pytest.mark.parametrize("family", TINY)
def test_decode_step_holds_no_cache_sized_scatter_or_select(family):
    cfg = TINY[family]()
    fam = model_family(cfg)
    slots, t = 4, 64
    params = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, slots, t))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c, tok, pos: fam.decode_step(p, tok, pos, c, cfg)
    )(params, cache, rows, rows)
    cache_shapes = {leaf.shape for leaf in jax.tree.leaves(cache)}
    eqns = list(eqns_of(jaxpr.jaxpr))
    # LongCat's expert layer adds its outputs up with a scatter-add over
    # [B, d]: only a cache-sized one is the write.
    cache_sized = [
        eqn.primitive.name for eqn in eqns
        if (eqn.primitive.name.startswith("scatter")
            or eqn.primitive.name == "select_n")
        and any(v.aval.shape in cache_shapes for v in eqn.outvars)]
    assert not cache_sized
    names = {eqn.primitive.name for eqn in eqns}
    # and the write is there: a tile read and written back, inside the loop
    assert {"dynamic_slice", "dynamic_update_slice"} <= names
