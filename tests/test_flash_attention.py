"""The flash kernels (``ray_tpu/ops/attention.py``) in interpret mode on the
CPU: forward and the three gradients against ``reference_attention`` over
the tile geometries the loops meet (tiles under the diagonal, on it, and
none above it) and at the callers' head sizes, the backward's one kernel
against the parent's two, what a tile pair costs and how its operands lie
(``[B*H, D, S]``: the sequence along the lanes), the work the loops'
bounds leave (``flash_tile_work``), and the packed entry
(``flash_attention_packed``: the same kernels over the ONE projected array
``[B, S, 3, H, D]``, addressed where it lies) against the unpacked one, bit
for bit, and from ``models/gpt2.py``'s block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.models import gpt2
from ray_tpu.ops.attention import (
    flash_attention,
    flash_attention_packed,
    flash_tile_work,
    reference_attention,
)


def _qkv(sq, sk, dtype=jnp.float32, b=2, h=2, d=8, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, sq, h, d), dtype),
            jax.random.normal(kk, (b, sk, h, d), dtype),
            jax.random.normal(kv, (b, sk, h, d), dtype))


# (Sq, Sk, block_q, block_k, causal)
GEOMETRIES = {
    "one diagonal tile": (32, 32, 32, 32, True),
    "interior and diagonal tiles": (64, 64, 16, 16, True),
    "block_q over block_k": (64, 64, 32, 16, True),
    "block_k over block_q": (64, 64, 16, 32, True),
    "no mask": (64, 64, 16, 16, False),
    "no mask, one tile": (48, 48, 64, 64, False),
    "more keys than queries": (32, 64, 16, 16, True),
    "more queries than keys": (64, 32, 16, 16, True),
}
# float32 operands round nowhere but in the sums; bf16 operands are rounded
# where the kernel feeds the MXU (p, ds) and the reference is not.
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 6e-2}


def _check_against_the_reference(q, k, v, bq, bk, causal):
    """Forward and dq, dk, dv of the kernels against the float32
    reference's, to the operands' type's tolerance."""
    dtype = q.dtype.type
    weight = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                               force_pallas=True)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                                * weight).sum()

    tol = TOLERANCE[dtype]
    as32 = [x.astype(jnp.float32) for x in (q, k, v)]
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32), np.asarray(ref(*as32)),
        rtol=tol, atol=tol)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(*as32)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("dtype", list(TOLERANCE), ids=lambda d: d.__name__)
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_forward_and_gradients_match_the_reference(geometry, dtype):
    sq, sk, bq, bk, causal = GEOMETRIES[geometry]
    _check_against_the_reference(*_qkv(sq, sk, dtype), bq, bk, causal)


@pytest.mark.parametrize("dtype", list(TOLERANCE), ids=lambda d: d.__name__)
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "no mask"])
@pytest.mark.parametrize("d", [64, 128])
def test_the_callers_heads_with_the_sequence_along_the_lanes(d, causal, sq,
                                                             sk, dtype):
    """Heads of 64 (``models/gpt2.py``, the training cells) and of 128
    (``models/llama.py``) through the same kernels: every operand crosses as
    ``[B*H, D, S]``, whole ``[D, S]`` tiles for either head, tiles of 64 so
    that both loops run several pairs and ``Sq != Sk`` is met too."""
    _check_against_the_reference(*_qkv(sq, sk, dtype, b=1, d=d), 64, 64,
                                 causal)


def _planes(qkv):
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _check_packed_against_unpacked(qkv, bq, bk, causal):
    """``out`` and d(qkv) of the packed entry EQUAL the unpacked entry's on
    the planes (stacked back by the slices' own gradient): the same kernels
    on the same numbers.  The unpacked entry's against the reference, to the
    type's tolerance, and so the packed one's."""
    weight = jax.random.normal(
        jax.random.PRNGKey(7), qkv.shape[:2] + qkv.shape[3:], jnp.float32)
    blocks = dict(causal=causal, block_q=bq, block_k=bk, force_pallas=True)

    def packed(qkv):
        return flash_attention_packed(qkv, **blocks)

    def unpacked(qkv):
        return flash_attention(*_planes(qkv), **blocks)

    def loss(attn):
        return lambda qkv: (attn(qkv).astype(jnp.float32) * weight).sum()

    np.testing.assert_array_equal(np.asarray(packed(qkv), np.float32),
                                  np.asarray(unpacked(qkv), np.float32))
    got, want = jax.grad(loss(packed))(qkv), jax.grad(loss(unpacked))(qkv)
    assert got.shape == qkv.shape and got.dtype == qkv.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    _check_against_the_reference(*_planes(qkv), bq, bk, causal)


def _packed(s, dtype, b=2, h=2, d=8, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, 3, h, d), dtype)


# one projected array has as many keys as queries
PACKED_GEOMETRIES = [name for name, (sq, sk, *_) in GEOMETRIES.items()
                     if sq == sk]


@pytest.mark.parametrize("dtype", list(TOLERANCE), ids=lambda d: d.__name__)
@pytest.mark.parametrize("geometry", PACKED_GEOMETRIES)
def test_the_packed_entry_gives_the_unpacked_ones_bits(geometry, dtype):
    s, _sk, bq, bk, causal = GEOMETRIES[geometry]
    _check_packed_against_unpacked(_packed(s, dtype), bq, bk, causal)


@pytest.mark.parametrize("dtype", list(TOLERANCE), ids=lambda d: d.__name__)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "no mask"])
@pytest.mark.parametrize("d", [64, 128])
def test_the_packed_entry_at_the_callers_heads(d, causal, dtype):
    """Heads of 64 (``models/gpt2.py``, the packed entry's caller) and of
    128, planes addressed inside ``[B, 3, H, D, S]`` under the grid ``(B, H,
    tiles)`` (2 rows of 3 heads, so that a row for a head shows), tiles of
    64: several key tiles write their columns of planes 1 and 2 of the one
    result, and ``out`` / dO / the statistics are head ``b * H + h``'s."""
    _check_packed_against_unpacked(_packed(128, dtype, b=2, h=3, d=d), 64, 64,
                                   causal)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "no mask"])
def test_the_packed_entry_off_a_tpu_is_the_reference_on_the_planes(causal):
    qkv = _packed(48, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(flash_attention_packed(qkv, causal=causal)),
        np.asarray(reference_attention(*_planes(qkv), causal=causal)))


@pytest.mark.parametrize("shape,message", [
    ((2, 48, 3, 2, 8), "not multiples of the blocks"),  # as flash_attention
    ((2, 32, 2, 2, 8), "a packed projection is"),
    ((2, 32, 2, 8), "a packed projection is"),
])
def test_the_packed_entry_refuses_what_the_kernels_cannot_tile(shape, message):
    with pytest.raises(ValueError, match=message):
        flash_attention_packed(jnp.zeros(shape), block_q=32, block_k=32,
                               force_pallas=True)


def _parents_two_kernels(q, k, v, do, block_q, block_k):
    """dq, dk, dv of ONE head ([S, D] operands) by the arithmetic of the two
    backward kernels this repo had until PR 49: dQ's loop rebuilt s and p for
    every key tile of a query tile and scaled dq after it; dK/dV's loop
    rebuilt them again for every query tile of a key tile, took ``p.T`` and
    ``ds.T``, and scaled dk after it.  ``scale`` on every score tile, the
    mask on every tile."""
    s_len, d = q.shape
    scale = d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s_len, s_len), bool)),
                       (q @ k.T) * scale, attention.NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    out = jnp.exp(scores - lse) @ v
    delta = (do * out).sum(-1, keepdims=True)

    def tile(qb, kb):
        rows = slice(qb * block_q, (qb + 1) * block_q)
        cols = slice(kb * block_k, (kb + 1) * block_k)
        s = (q[rows] @ k[cols].T) * scale
        q_pos = jnp.arange(rows.start, rows.stop)[:, None]
        k_pos = jnp.arange(cols.start, cols.stop)[None, :]
        s = jnp.where(k_pos <= q_pos, s, attention.NEG_INF)
        p = jnp.exp(s - lse[rows])
        ds = p * (do[rows] @ v[cols].T - delta[rows])
        return rows, cols, p, ds

    nq, nk = s_len // block_q, s_len // block_k
    dq, dk, dv = jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
    for qb in range(nq):  # the dQ kernel
        acc = jnp.zeros((block_q, d))
        for kb in range(min(((qb + 1) * block_q + block_k - 1) // block_k,
                            nk)):
            rows, cols, _p, ds = tile(qb, kb)
            acc = acc + ds @ k[cols]
        dq = dq.at[rows].set(acc * scale)
    for kb in range(nk):  # the dK/dV kernel
        acc_k, acc_v = jnp.zeros((block_k, d)), jnp.zeros((block_k, d))
        for qb in range(kb * block_k // block_q, nq):
            rows, cols, p, ds = tile(qb, kb)
            acc_v = acc_v + p.T @ do[rows]
            acc_k = acc_k + ds.T @ q[rows]
        dk = dk.at[cols].set(acc_k * scale)
        dv = dv.at[cols].set(acc_v)
    return dq, dk, dv


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 16), (64, 16)])
def test_one_backward_kernel_gives_what_the_parents_two_gave(block_q,
                                                             block_k):
    """dq gathered in VMEM across FOUR key tiles (64 keys in tiles of 16: the
    accumulator is zeroed at the first, cast after the last), dk and dv
    across the query tiles from the diagonal on: the parent's numbers to
    float32 rounding."""
    q, k, v = _qkv(64, 64, b=1, h=2)
    do = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)
    _out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, block_q=block_q,
                                        block_k=block_k, force_pallas=True),
        q, k, v)
    got = vjp(do)
    for head in range(q.shape[2]):
        want = _parents_two_kernels(
            q[0, :, head], k[0, :, head], v[0, :, head], do[0, :, head],
            block_q, block_k)
        for name, g, w in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(g[0, :, head]), np.asarray(w), rtol=1e-5,
                atol=1e-5, err_msg=name)


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _kernels(fn, *args):
    """The ``pallas_call`` equations in ``fn``'s jaxpr, by their outputs."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[len(eqn.outvars)] = eqn
            else:
                for sub in _subjaxprs(eqn):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _count(jaxpr, primitive):
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive) for sub in _subjaxprs(eqn))
               for eqn in jaxpr.eqns)


def _per_loop(kernel, primitive):
    """How many ``primitive`` each loop of the kernel holds a turn."""
    body = {"while": "body_jaxpr", "scan": "jaxpr"}  # dynamic, static bounds
    return [_count(eqn.params[body[eqn.primitive.name]].jaxpr, primitive)
            for eqn in kernel.eqns if eqn.primitive.name in body]


def _products(jaxpr):
    """The contracted axes (lhs, rhs) of every product in ``jaxpr``, its
    sub-programs included, sorted."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs, rhs), batch = eqn.params["dimension_numbers"]
            assert batch == ((), ())
            found.append((*lhs, *rhs))
        for sub in _subjaxprs(eqn):
            found += _products(sub)
    return sorted(found)


def _gradients(entry, **blocks):
    """``arguments -> gradients`` of the summed output through ``entry``:
    q, k, v (``[B, S, H, D]`` each) through ``flash_attention``, or the one
    ``[B, S, 3, H, D]`` through ``flash_attention_packed``."""
    call = {"three arrays": flash_attention,
            "one packed array": flash_attention_packed}[entry]

    def grads(*arrays):
        return jax.grad(
            lambda *a: call(*a, force_pallas=True, **blocks).sum(),
            argnums=tuple(range(len(arrays))))(*arrays)

    return grads


ENTRIES = ["three arrays", "one packed array"]


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("causal", [True, False])
def test_a_tile_pair_costs_what_the_mathematics_has(causal, entry):
    """Forward: 2 products (k q^T, v^T p^T) and 2 exponentials (p, and the
    accumulator's rescale, one a QUERY) a tile pair; backward: the 5 products
    the mathematics has (s, dv, dp, dk, dq) and 1 exponential, in ONE kernel
    (three outputs).  Until PR 49 dQ ran 3 + 1 and dK/dV 4 + 1 (7 and 2 a
    pair) and dK/dV transposed two [block_q, block_k] tiles a pair; now no
    loop transposes anything (the scores are BUILT transposed).

    How the products take operands that all come as ``[D, S]`` (PR 50),
    (lhs axis, rhs axis) contracted: the forward's two are plain, ``(1, 0)``:
    acc^T = v^T p^T as it lies, s^T = k q^T against K turned ONCE A HEAD
    into VMEM (the one ``transpose`` of the kernel, under the ``cond`` of
    the head's first query tile: none a tile pair, none a later grid cell).
    The backward's five: dq^T = k^T ds^T plain; dv^T = dO^T p and dk^T =
    q^T ds over the lanes of both sides, ``(1, 1)``; s^T and dp^T = v dO^T
    over the sublanes of both, ``(0, 0)``, which leaves the turn of the
    [D, block_k] side to the compiler: no ``transpose`` in that kernel (the
    chip preferred it to two tiles turned a grid cell: PERF.md, PR 50).

    The packed entry runs the same two bodies: its backward's three results
    are views of ONE (d(qkv)), so that kernel has one output."""
    arrays = {"three arrays": _qkv(64, 64),
              "one packed array": [_packed(64, jnp.float32)]}[entry]
    kernels = _kernels(
        _gradients(entry, causal=causal, block_q=16, block_k=16), *arrays)
    results = {"three arrays": 3, "one packed array": 1}[entry]
    # (out, lse); (dq, dk, dv) or d(qkv): no other
    assert sorted(kernels) == sorted([2, results])
    forward, backward = (kernels[n].params["jaxpr"] for n in (2, results))
    assert _per_loop(forward, "dot_general") == [2]
    assert _per_loop(forward, "exp") == [2]
    assert _per_loop(backward, "dot_general") == [5]
    assert _per_loop(backward, "exp") == [1]
    for kernel in (forward, backward):
        assert _per_loop(kernel, "select_n") == [int(causal)]
        assert _per_loop(kernel, "transpose") == [0]
    assert _products(forward) == [(1, 0), (1, 0)]
    assert _products(backward) == [(0, 0), (0, 0), (1, 0), (1, 1), (1, 1)]
    assert _count(forward, "transpose") == _count(forward, "cond") == 1
    assert _count(backward, "transpose") == 0


def test_every_operand_crosses_as_heads_by_depth_by_sequence():
    """q, k, v, dO in and out, dq, dk, dv back: ``[B*H, D, S]`` each (the
    statistics ``[B*H, 1, S]``), so that the compiled step, which keeps the
    sequence along the lanes, hands them over and takes them back without a
    ``copy`` (``tests/test_tpu_compile.py`` holds that on the cells' step)."""
    b, h, d, sq, sk = 2, 2, 8, 64, 32
    q, k, v = _qkv(sq, sk, b=b, h=h, d=d)
    calls = _kernels(_gradients("three arrays", block_q=16, block_k=16),
                     q, k, v)
    shapes = lambda variables: [tuple(x.aval.shape) for x in variables]
    query, key, row = (b * h, d, sq), (b * h, d, sk), (b * h, 1, sq)
    assert shapes(calls[2].invars) == [query, key, key]
    assert shapes(calls[2].outvars) == [query, row]
    assert shapes(calls[3].invars) == [query, key, key, query, row, row]
    assert shapes(calls[3].outvars) == [query, key, key]
    x = jnp.arange(b * sq * h * d, dtype=jnp.float32).reshape(b, sq, h, d)
    folded = attention._fold(x)
    assert folded.shape == query
    assert float(folded[1 * h + 1, 3, 5]) == float(x[1, 5, 1, 3])
    np.testing.assert_array_equal(attention._unfold(folded, b), x)


def test_the_packed_projection_crosses_once_as_it_lies():
    """The packed entry hands the kernels ONE array, ``[B, 3, H, D, S]`` (the
    projection's ``[B, S, 3, H, D]`` with the sequence along the lanes: what
    the compiled step keeps), three times in, and takes ONE of that shape
    back from the backward; ``out``, dO and the statistics as the unpacked
    entry's.  Nothing slices, concatenates or updates a slice on either side
    of a kernel: between the caller's array and the kernels stand two
    ``transpose``s of the packed array (in; d(qkv) out), which the compiled
    step makes bitcasts, and the folds of ``out`` and dO."""
    b, h, d, s = 2, 3, 8, 64
    qkv = _packed(s, jnp.float32, b=b, h=h, d=d)
    grads = _gradients("one packed array", block_q=16, block_k=16)
    calls = _kernels(grads, qkv)
    shapes = lambda variables: [tuple(x.aval.shape) for x in variables]
    packed, head, row = (b, 3, h, d, s), (b * h, d, s), (b * h, 1, s)
    assert shapes(calls[2].invars) == [packed] * 3
    assert len(set(calls[2].invars)) == 1  # the same array, not three
    assert shapes(calls[2].outvars) == [head, row]
    assert shapes(calls[1].invars) == [packed] * 3 + [head, row, row]
    assert len(set(calls[1].invars[:3])) == 1
    assert shapes(calls[1].outvars) == [packed]
    jaxpr = jax.make_jaxpr(grads)(qkv).jaxpr
    for moved in ("slice", "dynamic_slice", "gather", "concatenate",
                  "dynamic_update_slice", "scatter", "pad"):
        assert _count(jaxpr, moved) == _count(
            calls[1].params["jaxpr"], moved) + _count(
            calls[2].params["jaxpr"], moved), moved
    # a head's plane where the BlockSpecs say it is
    seen = qkv.transpose(0, 2, 3, 4, 1)
    assert float(seen[1, 2, 1, 3, 5]) == float(qkv[1, 5, 2, 1, 3])


TOKENS = jax.random.randint(jax.random.PRNGKey(5), (2, 33), 0, 512)


@pytest.mark.parametrize("mode", ["flash", "dense", "dense_remat", "ring",
                                  "ulysses"])
def test_gpt2s_block_hands_over_what_it_has(mode, monkeypatch):
    """``attention="flash"`` reaches the packed entry with the projection's
    one ``[B, S, 3, H, D]`` and never ``flash_attention`` nor three arrays;
    every other mode still gets q, k, v, ``[B, S, H, D]`` each."""
    cfg = gpt2.GPT2Config.tiny(dtype="float32", attention=mode)
    seen = []

    def spy(name, shapes_of, result):
        def call(*args, **kwargs):
            seen.append((name, shapes_of(args)))
            return result(*args)
        return call

    three = lambda args: [a.shape for a in args[:3]]
    monkeypatch.setattr(attention, "flash_attention_packed", spy(
        "packed", lambda args: args[0].shape,
        lambda qkv: reference_attention(*_planes(qkv))))
    monkeypatch.setattr(attention, "flash_attention", spy(
        "flash_attention", three, reference_attention))
    monkeypatch.setattr(gpt2, "_attention", spy(
        "three arrays", three,
        lambda q, k, v, *_: reference_attention(q, k, v)))
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), cfg)
    gpt2.gpt2_loss(params, TOKENS, cfg)
    b, s, h, d = 2, 32, cfg.n_head, cfg.head_dim
    want = (("packed", (b, s, 3, h, d)) if mode == "flash"
            else ("three arrays", [(b, s, h, d)] * 3))
    assert seen == [want]  # the layers are one scan: traced once


def test_gpt2s_loss_through_the_packed_entry_is_the_dense_ones():
    """``gpt2_loss`` and every gradient at the ``tiny`` widths on the CPU,
    where the packed entry takes its reference path: ``attention="dense"``'s
    numbers (the tolerance of ``tests/test_models.py``'s remat check)."""
    dense = gpt2.GPT2Config.tiny(dtype="float32")
    flash = gpt2.GPT2Config.tiny(dtype="float32", attention="flash")
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), dense)
    got, want = (jax.value_and_grad(
        lambda p: gpt2.gpt2_loss(p, TOKENS, cfg))(params)
        for cfg in (flash, dense))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def _brute_force(sq, sk, bq, bk, causal):
    """Tile pairs with at least one live score, and those with a dead one."""
    live = np.tril(np.ones((sq, sk), bool)) if causal else np.ones(
        (sq, sk), bool)
    tiles = live.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
    some, every = tiles.any((2, 3)), tiles.all((2, 3))
    return {"pairs": int(some.sum()), "diagonal": int((some & ~every).sum()),
            "executed_over_needed": some.sum() * bq * bk / live.sum()}


@pytest.mark.parametrize("geometry", list(GEOMETRIES) + ["the cell's"])
def test_tile_work_is_what_the_mask_leaves(geometry):
    """The loops' bounds visit every tile pair with a live score and no
    other; ``diagonal`` of them hold a dead one."""
    sq, sk, bq, bk, causal = GEOMETRIES.get(
        geometry, (1024, 1024, 256, 256, True))
    bq, bk = min(bq, sq), min(bk, sk)
    assert flash_tile_work(sq, sk, bq, bk, causal) == pytest.approx(
        _brute_force(sq, sk, bq, bk, causal))


def test_the_work_of_the_chosen_tiles_at_the_cells_shape():
    """At S = 1024 (both training cells) the 512 x 512 the chip chose runs 3
    tile pairs a head, 1.5 x the score elements the mask leaves live.  Tiles
    of 256 would run 10 pairs, 1.25 x, and ISSUE 49 expected them to win;
    on the chip they take 1.8 x the forward's time and 1.6 x the
    backward's (PERF.md, PR 49: the sweep), so the pin is on what runs."""
    assert attention._TILE == 512
    assert flash_tile_work(1024, 1024, 512, 512, True) == pytest.approx(
        {"pairs": 3, "diagonal": 2, "executed_over_needed": 1.5}, abs=2e-3)
    assert flash_tile_work(1024, 1024, 256, 256, True) == pytest.approx(
        {"pairs": 10, "diagonal": 4, "executed_over_needed": 1.25}, abs=2e-3)
    assert flash_tile_work(1024, 1024, 512, 512, False) == {
        "pairs": 4, "diagonal": 0, "executed_over_needed": 1.0}
