"""The flash kernels (``ray_tpu/ops/attention.py``) in interpret mode on the
CPU: forward and the three gradients against ``reference_attention`` over
the tile geometries the loops meet (tiles under the diagonal, on it, and
none above it) and at the callers' head sizes, the backward's one kernel
against the parent's two, what a tile pair costs and how its operands lie
(``[B*H, D, S]``: the sequence along the lanes), and the work the loops'
bounds leave (``flash_tile_work``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (
    flash_attention,
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


@pytest.mark.parametrize("causal", [True, False])
def test_a_tile_pair_costs_what_the_mathematics_has(causal):
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
    chip preferred it to two tiles turned a grid cell: PERF.md, PR 50)."""
    q, k, v = _qkv(64, 64)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16,
            force_pallas=True).sum(), argnums=(0, 1, 2))(q, k, v)

    kernels = _kernels(grads, q, k, v)
    assert sorted(kernels) == [2, 3]  # (out, lse); (dq, dk, dv): no other
    forward, backward = (kernels[n].params["jaxpr"] for n in (2, 3))
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

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=16, block_k=16, force_pallas=True).sum(),
            argnums=(0, 1, 2))(q, k, v)

    calls = _kernels(grads, q, k, v)
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
