"""``ops/mamba_update.py``: Mamba-2's one-token update as ONE pass over the
state, its Pallas kernel run in interpret mode on the CPU against the XLA
formulation it replaces on a TPU (``mamba_update_xla``) and against the plain
reference's recurrence (the ``step`` of ``benchmarks/reference/
granite_h_ref.py`` ``mamba2``, in float64), at toy and at the two families'
published widths.  The kernel sums a row's lanes in another order than XLA's
reduce: equal to float32 rounding, not bit for bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (GraniteHConfig, NemotronHConfig, granite_h_decode,
                            mamba2, model_family, nemotron_h_decode)
from ray_tpu.ops import mamba_update as mu

# float32 sums of 128 products of numbers of order one, in two orders
TOL = 4e-6


@dataclasses.dataclass(frozen=True)
class Widths:
    """``h`` heads of ``[p, n]`` in ``g`` groups."""
    h: int
    p: int
    n: int
    g: int

    def __str__(self):
        return f"h{self.h}_p{self.p}_n{self.n}_g{self.g}"


# a toy; two registers a row of lanes; Granite-4.0-H's 64 heads of one
# group; Nemotron-3's 128 heads in 8 groups of 16
TOY, GRANITE, NEMOTRON = (Widths(8, 8, 128, 1), Widths(64, 64, 128, 1),
                          Widths(128, 64, 128, 8))
WIDTHS = [TOY, Widths(16, 16, 256, 8), GRANITE, NEMOTRON]


def draw(w: Widths, slots=2, layers=1, seed=0, keep=None, dt=None):
    """(leaf ``[layers, slots, h, p, n]``, then x, dt, keep, b, c as
    ``mamba_step`` gives them)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    step = jnp.asarray(rng.uniform(0.001, 0.5, (slots, w.h)), jnp.float32)
    rate = jnp.asarray(rng.uniform(1.0, 16.0, w.h), jnp.float32)
    return (normal(layers, slots, w.h, w.p, w.n), normal(slots, w.h, w.p),
            step if dt is None else jnp.full((slots, w.h), dt, jnp.float32),
            jnp.exp(-step * rate) if keep is None else jnp.full(
                (slots, w.h), keep, jnp.float32),
            normal(slots, w.g, w.n), normal(slots, w.g, w.n))


def kernel(leaf, at, *small, block=None):
    return jax.jit(functools.partial(
        mu.mamba_update, force_pallas=True, block=block),
        static_argnums=1)(leaf, at, *small)


def recurrence(leaf, at, x, dt, keep, b, c):
    """The plain reference's one step from layer ``at``'s state, in
    float64: (y, the layer's new state)."""
    state, x, dt, keep, b, c = (
        np.asarray(a, np.float64) for a in (leaf[at], x, dt, keep, b, c))
    r = x.shape[1] // b.shape[1]
    b, c = (np.repeat(v, r, axis=1) for v in (b, c))  # a head's group
    state = (keep[..., None, None] * state
             + (dt[..., None] * x)[..., None] * b[:, :, None])
    return (state * c[:, :, None]).sum(-1), state


def close(got, want, tol=TOL):
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


@pytest.mark.parametrize("blocks", ["one_block", "several"])
@pytest.mark.parametrize("w", WIDTHS, ids=str)
def test_kernel_is_the_xla_formulation_and_the_references_recurrence(
        w, blocks):
    """One block a layer (every slot's heads at once) and several (a slot of
    eight heads a grid step, so that a block's first head is not its
    slot's and, at eight groups, not its group's)."""
    args = draw(w, seed=w.h + w.n)
    block = (2, w.h) if blocks == "one_block" else (1, 8)
    y, new = kernel(args[0], 0, *args[1:], block=block)
    want_y, want_new = mu.mamba_update_xla(args[0], 0, *args[1:])
    close(y, want_y)
    close(new, want_new)
    ref_y, ref_new = recurrence(args[0], 0, *args[1:])
    close(y, ref_y)
    close(new[0], ref_new)
    assert y.shape == (2, w.h, w.p) and new.dtype == jnp.float32


@pytest.mark.parametrize("w,slots,block", [
    (TOY, 64, (64, 8)), (Widths(64, 16, 128, 1), 6, (3, 64)),
    (GRANITE, 64, (1, 64)), (NEMOTRON, 64, (1, 64)),
    (Widths(256, 64, 128, 8), 4, (1, 64))], ids=str)
def test_a_block_is_chosen_from_bytes_not_from_a_familys_name(
        w, slots, block):
    """Whole slots while they fit in 2 MiB, else the largest run of a
    slot's heads that does: a function of the shape alone."""
    assert mu._block(slots, w.h, w.p * w.n * 4) == block


@pytest.mark.parametrize("at", [0, 1, 2])
def test_a_call_on_one_layer_of_the_stack_leaves_the_others_as_they_were(at):
    w = Widths(16, 8, 128, 2)
    args = draw(w, layers=3, seed=at)
    y, new = kernel(args[0], at, *args[1:])
    want_y, want_new = mu.mamba_update_xla(args[0], at, *args[1:])
    close(y, want_y)
    close(new[at], want_new[at])
    for other in set(range(3)) - {at}:  # bit for bit
        np.testing.assert_array_equal(new[other], args[0][other])


@pytest.mark.parametrize("keep", [0.0, 1e-6, 1.0 - 1e-7, 1.0])
@pytest.mark.parametrize("dt", [0.0, 30.0], ids=["padded", "large"])
def test_decay_near_nothing_and_near_one_at_a_padded_and_a_large_step(
        keep, dt):
    """``keep -> 0`` forgets the state (``S_new = dt x (x) b``); ``dt = 0``
    is a padded position, where ``keep = 1`` and the state passes through
    untouched, bit for bit; ``dt = 30`` is ``softplus`` far out (the
    pieces the kernel splits ``dt x`` into must add up to it exactly)."""
    w = Widths(16, 8, 128, 2)
    args = draw(w, seed=3, keep=keep, dt=dt)
    y, new = kernel(args[0], 0, *args[1:])
    ref_y, ref_new = recurrence(args[0], 0, *args[1:])
    close(y, ref_y)
    close(new[0], ref_new)
    if keep == 1.0 and dt == 0.0:
        np.testing.assert_array_equal(new, args[0])


def test_what_the_matrix_unit_spreads_is_the_float32_not_a_rounding_of_it():
    """``dt x`` reaches a head's lanes as three bfloat16 pieces summed
    against ones: where ``b = 1`` and ``keep = 0`` the new state IS ``dt
    x``, every bit of it, at numbers whose 24 bits are all in use."""
    w = TOY
    leaf, x, dt, _, b, c = draw(w, seed=11)
    x = x * jnp.float32(1.0 + 2.0 ** -23) + jnp.float32(2.0 ** -20)
    _, new = kernel(leaf, 0, x, dt, jnp.zeros_like(dt), jnp.ones_like(b), c)
    want = jnp.broadcast_to((dt[..., None] * x)[..., None], new[0].shape)
    np.testing.assert_array_equal(new[0], want)


def test_an_idle_slots_state_stays_finite_and_bounded_step_after_step():
    """An idle slot decodes the same token at position 0 over and over on
    whatever its last tenant left: the same ``x``, ``b``, ``c`` two hundred
    times and hardly any decay.  ``keep < 1``: the state tends to ``dt x (x)
    b / (1 - keep)``, in the kernel as in the XLA formulation."""
    w = TOY
    leaf, *small = draw(w, seed=5, keep=0.999, dt=0.001)
    leaf = leaf * 30.0  # a tenant's leftovers
    want = leaf + 0.0  # the kernel's is donated
    step = jax.jit(functools.partial(mu.mamba_update, force_pallas=True),
                   static_argnums=1, donate_argnums=0)
    for _ in range(200):
        y, leaf = step(leaf, 0, *small)
        _, want = mu.mamba_update_xla(want, 0, *small)
    assert bool(jnp.isfinite(leaf).all()) and bool(jnp.isfinite(y).all())
    assert float(jnp.abs(leaf).max()) < 200.0
    close(leaf, want, tol=2e-5)


@pytest.mark.parametrize("case,error", [
    (dict(slots=3, block=(2, 8)), "whole number of blocks"),
    (dict(h=16, block=(1, 12)), "whole number of blocks"),
    (dict(p=12), "whole"), (dict(n=80), "whole"), (dict(h=12), "whole"),
    (dict(dtype=jnp.bfloat16), "whole")],
    ids=["slots_not_divided", "heads_not_by_eight", "p_12", "lanes_80",
         "heads_12", "bfloat16"])
def test_what_the_kernel_cannot_tile_it_refuses_by_name(case, error):
    """Forced, the kernel raises where a block does not divide the slots or
    the heads, or a head is not whole float32 tiles; it never falls back."""
    w = Widths(case.get("h", 8), case.get("p", 8), case.get("n", 128), 1)
    leaf, *small = draw(w, slots=case.get("slots", 2))
    leaf = leaf.astype(case.get("dtype", jnp.float32))
    with pytest.raises(ValueError, match=error):
        mu.mamba_update(leaf, 0, *small, force_pallas=True,
                        block=case.get("block"))


@pytest.mark.parametrize("w", [Widths(8, 8, 16, 2), Widths(4, 16, 128, 1),
                               TOY], ids=str)
def test_off_a_tpu_the_unforced_way_is_the_xla_formulation(w):
    """What the CPU suite and the ``--rehearse-cpu`` scripts run (the toy
    configurations' state is 16 wide), and on a TPU a head that is not whole
    tiles: bit for bit ``mamba_update_xla``."""
    args = draw(w)
    for got, want in zip(mu.mamba_update(*args[:1], 0, *args[1:]),
                         mu.mamba_update_xla(*args[:1], 0, *args[1:])):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", [
    NemotronHConfig.tiny(dtype="float32", ssm_state_size=128),
    GraniteHConfig.tiny(dtype="float32", ssm_state_size=128,
                        mamba_head_dim=8)],
    ids=["nemotron_h", "granite_h"])
def test_a_familys_decode_step_through_the_kernel_is_its_step_through_xla(
        cfg, monkeypatch):
    """Both families' decode steps at widths the kernel tiles (a state 128
    wide): ``mamba_step`` with the kernel forced gives the logits and the
    cache of the unforced step, every layer of the stacked leaf through
    ``ops.mamba_update`` and none through anything else."""
    fam = model_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    cache = fam.init_cache(cfg, 3, 16)
    rng = np.random.default_rng(0)
    cache = {name: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
             for name, leaf in cache.items()}
    tokens, pos = jnp.asarray([5, 9, 2]), jnp.asarray([3, 0, 7])
    want_logits, want = fam.decode_step(params, tokens, pos, cache, cfg)
    calls = []

    def forced(leaf, at, *small):
        calls.append((leaf.shape, at))
        return mu.mamba_update(leaf, at, *small, force_pallas=True)

    monkeypatch.setattr(mamba2, "mamba_update", forced)
    logits, new = fam.decode_step(params, tokens, pos, cache, cfg)
    nm = cfg.kinds.count("M")
    assert calls == [(cache["ssm"].shape, i) for i in range(nm)]
    close(logits, want_logits, tol=2e-5)
    for name in cache:
        close(new[name], want[name], tol=2e-5)
    assert granite_h_decode.mamba_step is nemotron_h_decode.mamba_step
    assert granite_h_decode.mamba_step is mamba2.mamba_step
