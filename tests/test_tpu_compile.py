"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for a
topology that is only *described* (guide ``on-chip-measurement`` §2,
rehearsal 3).  Nothing runs, so these say nothing about results or speed —
they pin what the v5e compiler accepts and refuses at the widths
``chip_smoke.py`` drives, at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under xdist every worker
imports this file.  Dispatch asks ``jax.devices()`` and would take its CPU
branch during such a compile, so the tests steer ``_on_tpu`` themselves.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import attention
from ray_tpu.ops.decode_attention import decode_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


FLASH_SHAPES = [(32, 1024, 12, 64), (4, 2048, 32, 64)]  # (B, S, H, D)


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles_to_pallas(one_chip, as_if_on_tpu, shape):
    fwd = jax.jit(lambda q, k, v: attention.flash_attention(q, k, v))
    text = fwd.lower(*_qkv(shape, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_backward_compiles_to_pallas(
    one_chip, as_if_on_tpu, shape
):
    def loss(q, k, v):
        return attention.flash_attention(q, k, v).astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(*_qkv(shape, one_chip)).compile().as_text()
    # forward + dq + dkv kernels
    assert text.count("tpu_custom_call") >= 3


def _decode_args(L, B, H, Hkv, T, D, sharding):
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding
    )
    return dict(
        q=s((B, H, D)),
        k_cache=s((L, B, Hkv, T, D)),
        v_cache=s((L, B, Hkv, T, D)),
        pos=s((B,), jnp.int32),
        k_self=s((B, Hkv, D)),
        v_self=s((B, Hkv, D)),
    )


def _lower_decode(args):
    def step(q, k_cache, v_cache, pos, k_self, v_self):
        return decode_attention(
            q, k_cache, v_cache, pos, 0, k_self=k_self, v_self=v_self,
            kernel=True,
        )

    return jax.jit(step).lower(**args)


def test_decode_kernel_compiles_at_gpt2_shape(one_chip, as_if_on_tpu):
    args = _decode_args(12, 32, 12, 12, 1024, 64, one_chip)
    assert "tpu_custom_call" in _lower_decode(args).compile().as_text()


def test_decode_kernel_refused_for_vmem_at_tinyllama_shape(
    one_chip, as_if_on_tpu
):
    """Today's truth (ROADMAP D2 starts here): each program copies a whole
    [Hkv, T, D] cache slice per operand, and at Hkv=8, T=2048 the v5e
    compiler runs out of VMEM.  The decode steps default to kernel=False."""
    args = _decode_args(22, 32, 32, 8, 2048, 64, one_chip)
    with pytest.raises(Exception, match="(?i)vmem|RESOURCE_EXHAUSTED"):
        _lower_decode(args).compile()


def test_longcat_decode_step_fits_beside_its_weights(one_chip):
    """The LongCat decode step at the benchmark cell's size (published
    widths, 4 double layers, 16 experts held, 32 slots x 2048) compiles for
    the v5e with temporaries far under the weights it reads: a layer's slice
    of the stacked experts taken outside the expert loop, or a layer's
    weights sliced first and indexed later, is COPIED every step (4.6 GB and
    3.6 GB, PERF.md PR 29) and the compiler then refuses the program beside
    11 GB of arguments."""
    from ray_tpu.models import LongcatConfig, longcat_init, model_family

    cfg = LongcatConfig(n_layer=4, experts_held=16, vocab_size=16384)
    fam = model_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: longcat_init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: fam.init_cache(cfg, 32, 2048)))
    rows = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, pos: fam.decode_step_counted(
        p, t, pos, c, cfg), donate_argnums=(1,))
    memory = step.lower(params, cache, rows, rows).compile().memory_analysis()
    assert memory.argument_size_in_bytes > 10.9e9
    assert memory.temp_size_in_bytes < 1.0e9  # 0.69 GB: one pass of the cache
