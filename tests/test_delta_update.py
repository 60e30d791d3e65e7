"""``ops/delta_update.py``: the gated delta rule's one-token update as ONE
pass over the packed state, its Pallas kernel run in interpret mode on the
CPU against the XLA formulation it replaces on a TPU (``delta_update_xla``)
and against the plain reference's recurrence
(``benchmarks/reference/olmo_hybrid_ref.py`` ``gated_delta_rule``), at tiny
widths.  The kernel sums a matrix's rows in another order than XLA's reduce:
equal to float32 rounding, not bit for bit.  The same with the decay a
VECTOR a head (``alpha [B, H, dk]``: Kimi Delta Attention's gate, a column
beside ``q`` and ``k``), against
``benchmarks/reference/kimi_linear_ref.py`` ``kda_recurrence``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear_ref as kimi_ref
from benchmarks.reference import olmo_hybrid_ref as ref
from ray_tpu.models import OlmoHybridConfig, olmo_hybrid, olmo_hybrid_decode
from ray_tpu.ops import delta_update as du

# float32 sums of 8-16 products of numbers of order one, in two orders
TOL = 2e-6


@dataclasses.dataclass(frozen=True)
class Widths:
    """``p`` heads of ``[dk, dv]`` a packed row, ``h`` heads in all."""
    p: int
    h: int
    dk: int
    dv: int

    def __str__(self):
        return f"p{self.p}_h{self.h}_dk{self.dk}_dv{self.dv}"


# two heads in one tile of 128 lanes (the tiny configuration's); one head a
# tile; two heads of 192 over three tiles, the middle one half of each (the
# published widths' case); one head over two tiles
WIDTHS = [Widths(2, 4, 8, 64), Widths(1, 3, 8, 128), Widths(2, 4, 16, 192),
          Widths(1, 2, 8, 256)]


def draw(w: Widths, slots=4, layers=1, seed=0, alpha=None, beta=None):
    """(leaf ``[layers, slots, h / p, dk, p dv]``, then q, k, v, alpha, beta
    as ``split_heads`` and the decode step give them)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def uniform(low, high):
        return jnp.asarray(rng.uniform(low, high, (slots, w.h, 1)),
                           jnp.float32)

    k = normal(slots, w.h, w.dk)
    return (normal(layers, slots, w.h // w.p, w.dk, w.p * w.dv),
            normal(slots, w.h, w.dk) * w.dk ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            normal(slots, w.h, w.dv),
            uniform(0.5, 1.0) if alpha is None else jnp.full(
                (slots, w.h, 1), alpha, jnp.float32),
            uniform(0.0, 2.0) if beta is None else jnp.full(
                (slots, w.h, 1), beta, jnp.float32))


def kernel(leaf, at, *small, slots=None):
    return jax.jit(functools.partial(
        du.delta_update, force_pallas=True, slots=slots),
        static_argnums=1)(leaf, at, *small)


def pack_of(w: Widths):
    cfg = OlmoHybridConfig.tiny(
        linear_num_heads=w.h, linear_key_head_dim=w.dk,
        linear_value_head_dim=w.dv)
    assert cfg.state_pack == w.p
    return cfg


def recurrence(w: Widths, leaf, at, q, k, v, alpha, beta):
    """The plain reference's one step from layer ``at``'s state: (o, the
    layer's new state, packed)."""
    cfg = pack_of(w)
    o, last = ref.gated_delta_rule(
        q[:, None], k[:, None], v[:, None], jnp.log(alpha[:, None, :, 0]),
        beta[:, None, :, 0],
        olmo_hybrid.unpack_state(leaf[at], cfg.state_pack))
    return o[:, 0], olmo_hybrid.pack_state(last, cfg.state_pack)


def close(got, want, tol=TOL):
    scale = max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


@pytest.mark.parametrize("slots", [1, 2], ids=["one_slot_a_step", "two"])
@pytest.mark.parametrize("w", WIDTHS, ids=str)
def test_kernel_is_the_xla_formulation_and_the_references_recurrence(
        w, slots):
    args = draw(w, seed=w.dv + slots)
    o, new = kernel(args[0], 0, *args[1:], slots=slots)
    want_o, want_new = du.delta_update_xla(args[0], 0, *args[1:])
    close(o, want_o)
    close(new, want_new)
    ref_o, ref_new = recurrence(w, args[0], 0, *args[1:])
    close(o, ref_o)
    close(new[0], ref_new)
    assert o.shape == (4, w.h, w.dv) and new.dtype == jnp.float32


@pytest.mark.parametrize("at", [0, 1, 2])
def test_a_call_on_one_layer_of_the_stack_leaves_the_others_as_they_were(at):
    w = WIDTHS[0]
    args = draw(w, layers=3, seed=at)
    o, new = kernel(args[0], at, *args[1:])
    want_o, want_new = du.delta_update_xla(args[0], at, *args[1:])
    close(o, want_o)
    close(new[at], want_new[at])
    for other in set(range(3)) - {at}:  # bit for bit
        np.testing.assert_array_equal(new[other], args[0][other])


@pytest.mark.parametrize("alpha", [0.0, 1e-6, 1.0 - 1e-7, 1.0])
@pytest.mark.parametrize("beta", [0.0, 2.0])
def test_decay_near_nothing_and_near_one_at_both_ends_of_beta(alpha, beta):
    """``alpha -> 0`` forgets the state (``S_new = k (x) beta v``), ``alpha =
    1`` with ``beta = 0`` passes it through untouched; ``beta = 2`` is the
    far end of ``allow_neg_eigval``."""
    w = WIDTHS[2]
    args = draw(w, seed=3, alpha=alpha, beta=beta)
    o, new = kernel(args[0], 0, *args[1:])
    ref_o, ref_new = recurrence(w, args[0], 0, *args[1:])
    close(o, ref_o)
    close(new[0], ref_new)
    if alpha == 1.0 and beta == 0.0:
        np.testing.assert_array_equal(new, args[0])


def test_an_idle_slots_state_stays_finite_and_bounded_step_after_step():
    """An idle slot decodes the same token at position 0 over and over on
    whatever its last tenant left: the same ``q``, ``k``, ``v`` two hundred
    times, ``beta`` at its far end and hardly any decay.  ``|k| = 1`` and
    ``beta <= 2``: the step's map on ``S`` never expands, in the kernel as
    in the XLA formulation."""
    w = WIDTHS[0]
    leaf, *small = draw(w, slots=2, seed=5, alpha=0.999, beta=2.0)
    leaf = leaf * 30.0  # a tenant's leftovers
    want = leaf + 0.0  # the kernel's is donated
    step = jax.jit(functools.partial(du.delta_update, force_pallas=True),
                   static_argnums=1, donate_argnums=0)
    for _ in range(200):
        o, leaf = step(leaf, 0, *small)
        _, want = du.delta_update_xla(want, 0, *small)
    assert bool(jnp.isfinite(leaf).all()) and bool(jnp.isfinite(o).all())
    assert float(jnp.abs(leaf).max()) < 200.0
    close(leaf, want, tol=2e-5)


@pytest.mark.parametrize("case,error", [
    (dict(slots=3, block=2), "not a multiple"),
    (dict(dk=12), "whole"), (dict(dv=80), "whole"),
    (dict(dtype=jnp.bfloat16), "whole")],
    ids=["slots_not_divided", "dk_12", "lanes_80", "bfloat16"])
def test_what_the_kernel_cannot_tile_it_refuses_by_name(case, error):
    """Forced, the kernel raises where a grid step's slots do not divide the
    batch or a packed row is not whole float32 tiles; it never falls back."""
    w = Widths(1, 2, case.get("dk", 8), case.get("dv", 128))
    leaf, *small = draw(w, slots=case.get("slots", 2))
    leaf = leaf.astype(case.get("dtype", jnp.float32))
    with pytest.raises(ValueError, match=error):
        du.delta_update(leaf, 0, *small, force_pallas=True,
                        slots=case.get("block"))


@pytest.mark.parametrize("w", [Widths(1, 2, 12, 80), WIDTHS[0]], ids=str)
def test_off_a_tpu_the_unforced_way_is_the_xla_formulation(w):
    """What the CPU suite and the ``--rehearse-cpu`` scripts run, and on a
    TPU a row that is not whole tiles: bit for bit ``delta_update_xla``."""
    args = draw(w)
    for got, want in zip(du.delta_update(*args[:1], 0, *args[1:]),
                         du.delta_update_xla(*args[:1], 0, *args[1:])):
        np.testing.assert_array_equal(got, want)


def test_one_layers_state_goes_through_the_same_kernel_as_the_stack(
        monkeypatch):
    """``delta_step`` (one layer's ``[B, H / p, dk, p dv]``, what
    ``benchmarks/olmo_hybrid_all_layers.py --time-delta`` donates) is
    ``delta_step_at`` on a stack of one; both through the forced kernel
    equal the XLA formulation."""
    cfg = OlmoHybridConfig.tiny(dtype="float32", layer_pattern="LL",
                                n_layer=2)
    m = olmo_hybrid.olmo_hybrid_init(
        jax.random.PRNGKey(0), cfg)["blocks"]["linear"]
    cache = olmo_hybrid_decode.olmo_hybrid_init_cache(cfg, 3, 8)
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.normal(size=cache["state"].shape), jnp.float32)
    conv = jnp.asarray(rng.normal(size=cache["conv"].shape), jnp.float32)
    y = jnp.asarray(rng.normal(size=(3, cfg.d_model)), jnp.float32)
    want = olmo_hybrid_decode.delta_step(y, conv[1], state[1], m, 1, cfg)
    calls = []

    def forced(*args):
        calls.append(args[0].shape)
        return du.delta_update(*args, force_pallas=True)

    monkeypatch.setattr(olmo_hybrid_decode, "delta_update", forced)
    alone = olmo_hybrid_decode.delta_step(y, conv[1], state[1], m, 1, cfg)
    out, new_conv, leaf = olmo_hybrid_decode.delta_step_at(
        y, conv, state, 1, m, 1, cfg)
    assert calls == [(1,) + state.shape[1:], state.shape]
    for got, other, ideal in zip(alone, (out, new_conv[1], leaf[1]), want):
        np.testing.assert_array_equal(got, other)
        close(got, ideal)
    np.testing.assert_array_equal(leaf[0], state[0])
    np.testing.assert_array_equal(new_conv[0], conv[0])


# ------------------------------------------------------- a vector gate
def vector_gate(w: Widths, slots=4, seed=0, low=1e-3, high=1.0 - 1e-6):
    """``alpha [slots, h, dk]``: every head holds channels that forget in a
    token (``low``) beside channels that never do (``high``)."""
    rng = np.random.default_rng(seed)
    alpha = np.exp(rng.uniform(np.log(low), 0.0, (slots, w.h, w.dk)))
    alpha[..., 0], alpha[..., 1] = low, high
    return jnp.asarray(alpha, jnp.float32)


def vector_recurrence(w: Widths, leaf, at, q, k, v, alpha, beta):
    """The Kimi reference's one step from layer ``at``'s state."""
    o, last = kimi_ref.kda_recurrence(
        q[:, None], k[:, None], v[:, None], jnp.log(alpha)[:, None],
        beta[:, None, :, 0], olmo_hybrid.unpack_state(leaf[at], w.p))
    return o[:, 0], olmo_hybrid.pack_state(last, w.p)


# one head a tile (Kimi-Linear's shape at toy widths), two heads a tile,
# two heads of 192 over three tiles, one head over two tiles
@pytest.mark.parametrize("slots", [1, 2], ids=["one_slot_a_step", "two"])
@pytest.mark.parametrize("w", [Widths(1, 3, 16, 128), *WIDTHS], ids=str)
def test_vector_gate_kernel_is_the_xla_formulation_and_the_recurrence(
        w, slots):
    leaf, q, k, v, _, beta = draw(w, seed=w.dk + slots)
    alpha = vector_gate(w, seed=slots)
    o, new = kernel(leaf, 0, q, k, v, alpha, beta, slots=slots)
    want_o, want_new = du.delta_update_xla(leaf, 0, q, k, v, alpha, beta)
    close(o, want_o)
    close(new, want_new)
    ref_o, ref_new = vector_recurrence(w, leaf, 0, q, k, v, alpha, beta)
    close(o, ref_o)
    close(new[0], ref_new)
    assert o.shape == (4, w.h, w.dv) and new.dtype == jnp.float32
    # a scalar gate is another update: the head's mean decay is not it
    mean = alpha.mean(-1, keepdims=True)
    scalar_o, _ = du.delta_update_xla(leaf, 0, q, k, v, mean, beta)
    assert float(jnp.abs(scalar_o - ref_o).max()) > 1e-2
    # and a vector gate whose channels agree IS the scalar gate's update
    same = jnp.broadcast_to(mean, alpha.shape)
    for got, want in zip(kernel(leaf, 0, q, k, v, same, beta, slots=slots),
                         kernel(leaf, 0, q, k, v, mean, beta, slots=slots)):
        close(got, want)


@pytest.mark.parametrize("at", [0, 1, 2])
def test_a_vector_gate_call_on_one_layer_leaves_the_others_bit_identical(at):
    w = Widths(1, 3, 16, 128)
    leaf, q, k, v, _, beta = draw(w, layers=3, seed=at)
    alpha = vector_gate(w, seed=at)
    o, new = kernel(leaf, at, q, k, v, alpha, beta)
    want_o, want_new = du.delta_update_xla(leaf, at, q, k, v, alpha, beta)
    close(o, want_o)
    close(new[at], want_new[at])
    for other in set(range(3)) - {at}:  # bit for bit
        np.testing.assert_array_equal(new[other], leaf[other])
    # off a TPU the unforced way is the XLA formulation, bit for bit
    for got, want in zip(du.delta_update(leaf, at, q, k, v, alpha, beta),
                         (want_o, want_new)):
        np.testing.assert_array_equal(got, want)
