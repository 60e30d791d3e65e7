"""``expert_share.held_experts`` against a plain float32 sum over one-hot
choices: dropless under every routing, a row's result its own whatever the
other rows choose, and the loop's counts against a count by hand.  Tiny
widths on the CPU; the five callers' shapes (rows, held experts, the
expert's kind), and the decode steps' one-chunk form."""

import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from ray_tpu.models import expert_share
from ray_tpu.models.expert_share import (EXPERT_CHUNK, chunk_rows,
                                         held_experts, loop_counts,
                                         sigmoid_route)
from ray_tpu.models.layers import ffn
from ray_tpu.models.nemotron_h import relu2

# name: rows, width, held experts, expert's hidden width, its kind, the
# share of the (row, held expert) pairs that are chosen when balanced
CALLERS = {
    "longcat_decode": (32, 24, 4, 16, "swiglu", 12 / 768),   # below a chunk
    "mistral4_decode": (32, 32, 4, 16, "swiglu", 4 / 128),  # a choice each
    "longcat_prefill": (256, 24, 4, 16, "swiglu", 12 / 768),
    "nemotron_h": (300, 16, 16, 24, "relu2", 22 / 512),  # no multiple
    "mimo_v2": (EXPERT_CHUNK, 32, 4, 16, "swiglu", 8 / 256),  # one chunk
    "mistral4": (520, 32, 4, 16, "swiglu", 4 / 128),
    "laguna": (1030, 24, 4, 16, "swiglu", 10 / 256),
}
ROUTINGS = ["balanced", "one_expert", "all_on_all", "nobody", "padded"]


def weights_of(kind, d, held, f, key):
    ks = jax.random.split(key, 3)
    shapes = {"swiglu": [(held, d, f), (held, d, f), (held, f, d)],
              "relu2": [(held, d, f), (held, f, d)]}[kind]
    return [jax.random.normal(k, s, jnp.bfloat16) * 0.3
            for k, s in zip(ks, shapes)]


def expert_of(kind, stack):
    """``expert(x, e)`` as the families hand it over: the expert's matrices
    taken as ``stack[e]`` inside the loop."""
    if kind == "swiglu":
        return lambda x, e: ffn(x, stack[0][e], stack[1][e], stack[2][e])
    return lambda x, e: relu2(x, stack[0][e], stack[1][e])


def routing_of(name, n, held, share, key):
    """``hit [n, held]`` bool and ``w_held`` float32 (0 where not hit)."""
    k1, k2 = jax.random.split(key)
    if name == "balanced":
        hit = jax.random.uniform(k1, (n, held)) < max(share, 0.1)
    elif name == "one_expert":
        hit = jnp.zeros((n, held), bool).at[:, 1].set(True)
    elif name == "all_on_all":  # every expert runs every row's chunk
        hit = jnp.ones((n, held), bool)
    elif name == "nobody":
        hit = jnp.zeros((n, held), bool)
    else:  # the rows past two thirds are padding: not live, choose nothing
        hit = (jax.random.uniform(k1, (n, held)) < 0.3) & (
            jnp.arange(n) < 2 * n // 3)[:, None]
    w = jax.random.uniform(k2, (n, held), jnp.float32, 0.1, 1.0)
    return hit, jnp.where(hit, w, 0.0)


def plain_sum(u, hit, w_held, expert):
    """Every held expert on every row, weighed by its one-hot choice, summed
    in float32 in ascending expert order."""
    out = jnp.zeros(u.shape, jnp.float32)
    for e in range(hit.shape[1]):
        out = out + jnp.where(hit[:, e, None],
                              expert(u, e) * w_held[:, e, None], 0.0)
    return out


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("caller", CALLERS)
def test_held_experts_is_the_plain_sum_over_the_chosen(caller, routing):
    n, d, held, f, kind, share = CALLERS[caller]
    key = jax.random.PRNGKey(len(caller) + 31 * ROUTINGS.index(routing))
    u = jax.random.normal(key, (n, d), jnp.bfloat16)
    expert = expert_of(kind, weights_of(kind, d, held, f, key))
    hit, w_held = routing_of(routing, n, held, share, key)
    got = jax.jit(lambda u, hit, w: held_experts(u, hit, w, expert))(
        u, hit, w_held)
    want = plain_sum(u, hit, w_held, expert)
    assert got.shape == (n, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # a row that chose nothing (padding, or nobody) gets exactly nothing
    assert not np.asarray(got)[~np.asarray(hit.any(1))].any()


def test_a_share_with_no_expert_adds_nothing():
    u = jnp.ones((8, 4), jnp.bfloat16)
    got = held_experts(u, jnp.zeros((8, 0), bool),
                       jnp.zeros((8, 0), jnp.float32), None)
    assert got.shape == (8, 4) and not np.asarray(got).any()
    assert {k: int(v) for k, v in loop_counts(jnp.zeros((8, 0), bool)).items(
        )} == {"held_chunks": 0, "held_chunk_rows": 0}


OTHERS = ["nobody", "balanced", "one_expert", "all_on_all", "padded"]


@pytest.mark.parametrize("others", OTHERS)
@pytest.mark.parametrize("caller", ["longcat_decode", "mistral4_decode",
                                    "nemotron_h", "mistral4", "laguna"])
def test_a_rows_result_does_not_depend_on_what_the_other_rows_choose(
        caller, others):
    """The engine's promise that a request's greedy ids do not depend on its
    neighbours: the first rows keep their tokens and their choices (three
    held experts each, so the order of their float32 sum shows), the other
    rows choose nothing, a balanced load, one expert, every held expert
    (so the first rows sit in other chunks, beside other rows) or are in
    part not live: the first rows' results are the same to the bit, and are
    the sum in ascending expert order.  In a decode step (all the rows one
    chunk) the others' choices set the loop's trip count, one turn a
    touched expert, and nothing else."""
    n, d, held, f, kind, share = CALLERS[caller]
    mine = 7
    key = jax.random.PRNGKey(5)
    u = jax.random.normal(key, (n, d), jnp.bfloat16)
    expert = expert_of(kind, weights_of(kind, d, held, f, key))
    own_hit = jnp.zeros((mine, held), bool).at[:, jnp.array([0, 2, 3])].set(
        True).at[3, :].set(True).at[5, 0].set(False)
    own_w = jax.random.uniform(key, (mine, held), jnp.float32, 0.1, 1.0)
    run = jax.jit(lambda u, hit, w: held_experts(u, hit, w, expert))

    def with_others(name):
        hit, w = routing_of(name, n, held, share, jax.random.PRNGKey(9))
        hit = hit.at[:mine].set(own_hit)
        w = jnp.where(hit, w.at[:mine].set(own_w), 0.0)
        return np.asarray(run(u, hit, w))[:mine]

    alone = with_others("nobody")
    np.testing.assert_array_equal(with_others(others), alone)
    want = plain_sum(u[:mine], own_hit, jnp.where(own_hit, own_w, 0.0),
                     expert)
    np.testing.assert_allclose(alone, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("caller", CALLERS)
def test_the_loops_counts_are_a_count_by_hand(caller, routing):
    """``loop_counts``: the chunks each expert's rows need, summed, and the
    rows those chunks hold; the dense products run no chunk."""
    n, _, held, _, _, share = CALLERS[caller]
    hit, _ = routing_of(routing, n, held, share, jax.random.PRNGKey(2))
    chunk = chunk_rows(n)
    assert chunk <= n and (n <= EXPERT_CHUNK) == (chunk == n)
    by_hand = sum(-(-int(c) // chunk) for c in np.asarray(hit).sum(0))
    assert {k: int(v) for k, v in jax.jit(loop_counts)(hit).items()} == {
        "held_chunks": by_hand, "held_chunk_rows": by_hand * chunk}
    assert {k: int(v) for k, v in loop_counts(hit, looped=False).items()} == {
        "held_chunks": 0, "held_chunk_rows": 0}
    assert tuple(loop_counts(hit)) == expert_share.LOOP_COUNT_NAMES
    if n <= EXPERT_CHUNK:  # one chunk: a turn is a touched expert
        assert by_hand == int(np.asarray(hit).any(0).sum())


# ------------------------------------------------ a decode step's one chunk
@pytest.mark.parametrize("others", OTHERS)
@pytest.mark.parametrize("caller", ["longcat_decode", "mistral4_decode"])
def test_a_decode_steps_row_is_its_own_whoever_sits_in_the_other_slots(
        caller, others):
    """All the rows are one chunk: a turn runs ONE touched expert on the
    whole batch and adds it where it was chosen, so the neighbours set how
    many turns there are (``held_chunks`` = the touched experts, by
    ``loop_counts``) and nothing of a row's value: the same to the bit
    whatever tokens the other slots hold, whatever they choose, and whether
    they are live, and the float32 sum over its own choices in ascending
    expert order.  No expert's product is run for nobody: with a weight
    that is not finite where no row chose it the result stays finite."""
    n, d, held, f, kind, share = CALLERS[caller]
    assert n <= chunk_rows(n)
    mine = 5
    key = jax.random.PRNGKey(11)
    u = jax.random.normal(key, (n, d), jnp.bfloat16)
    stack = weights_of(kind, d, held, f, key)
    own_hit = jnp.zeros((mine, held), bool).at[:, jnp.array([0, 2])].set(
        True).at[1, :].set(True).at[4, :].set(False)
    own_w = jax.random.uniform(key, (mine, held), jnp.float32, 0.1, 1.0)

    def step(name, tokens_key, stack=stack):
        hit, w = routing_of(name, n, held, share, jax.random.PRNGKey(9))
        hit = hit.at[:mine].set(own_hit)
        w = jnp.where(hit, w.at[:mine].set(own_w), 0.0)
        tokens = jax.random.normal(tokens_key, (n, d), jnp.bfloat16)
        out = jax.jit(lambda u, hit, w: held_experts(
            u, hit, w, expert_of(kind, stack)))(
                tokens.at[:mine].set(u[:mine]), hit, w)
        return np.asarray(out)[:mine], hit

    alone, hit = step("nobody", key)
    assert int(loop_counts(hit)["held_chunks"]) == 4  # 0 and 2; row 1: all
    got, hit = step(others, jax.random.PRNGKey(13))
    np.testing.assert_array_equal(got, alone)
    assert int(loop_counts(hit)["held_chunks"]) == int(hit.any(0).sum())
    want = plain_sum(u[:mine], own_hit, jnp.where(own_hit, own_w, 0.0),
                     expert_of(kind, stack))
    np.testing.assert_allclose(alone, want, rtol=2e-6, atol=2e-6)
    assert not alone[4].any()  # chose nothing: exactly nothing
    # an expert nobody chose is not run: rows 0, 2, 3 choose 0 and 2 only
    broken = [a.at[1].set(jnp.nan) for a in stack]
    hit = jnp.zeros((n, held), bool).at[jnp.array([0, 2, 3])].set(
        own_hit[0])
    out = jax.jit(lambda u, hit, w: held_experts(
        u, hit, w, expert_of(kind, broken)))(u, hit, jnp.where(hit, 0.5, 0.0))
    assert np.isfinite(np.asarray(out)).all()


# --------------------------------------------------- the way, by the shapes
# cell's configuration: every held expert in batched products in its decode
# step?  (independent rows would touch 64, 72, 87, 94 and 39 % of the held)
STEP_WAYS = {"mistral_small4_l9_ep8": False, "laguna_s21_l9_ep16": False,
             "mimo_v25_l7_ep16": True, "nemotron3_super_l11_ep4": True,
             "longcat_flash_l4_ep32": False}


@pytest.mark.parametrize("name", STEP_WAYS)
def test_a_cells_step_shapes_choose_its_way(name):
    """``runs_every_held_expert`` at the five serving cells' own shapes
    (slots, choices a token, experts routed over): Mistral-4's and Laguna's
    decode steps take the loop, a turn a touched expert; MiMo-V2's and
    Nemotron-H's run every held expert (PERF.md, PR 54, has the timing that
    drew the line); LongCat's step, which never asks, would be told the
    loop it takes.  No prefill rung runs them all, at any cell's shapes; and
    the way is a function of these three numbers alone."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", name + ".json")) as f:
        cell = json.load(f)
    model, slots = cell["model"], cell["engine"]["max_batch_size"]
    routed = model["n_routed_experts"] + model.get("zero_expert_num", 0)
    rule = expert_share.runs_every_held_expert
    assert rule(slots, model["top_k"], routed) is STEP_WAYS[name]
    assert slots <= EXPERT_CHUNK == chunk_rows(EXPERT_CHUNK)
    for rung in (256, 1024, 16384):
        assert rule(rung, model["top_k"], routed) is False
    assert rule.__code__.co_varnames[:rule.__code__.co_argcount] == (
        "rows", "top_k", "n_routed")


# ------------------------------------------------------- the metric's file
def metric_ctx(counts):
    """Steps as the engine writes its counts on zero-length spans."""
    host = [[span for at, attrs in enumerate(counts) for span in (
        ["engine.step", 200 * at, 100, {"seq": at}],
        ["engine.counts", 200 * at + 90, 0, attrs])]]
    device = {"/device:TPU:0": {tr.OPS_LINE: [["fusion.1", 0, 50]],
                                tr.MODULES_LINE: [["jit__lambda(1)", 0, 50]]}}
    return types.SimpleNamespace(
        host_spans=[hs.from_planes(host, device)],
        trace=tr.Trace.from_planes(device), config={}, mix={}, stats={},
        peaks={})


@pytest.mark.parametrize("cell", ["laguna_ep16_code_closed32",
                                  "mistral4_ep8_longdoc_closed32"])
def test_prefill_chunk_fill_pct_reads_the_prefills_counts(cell):
    """``prefill_chunk_fill_pct.serve``: held choices over the rows the
    loop's chunks ran, over the steps that folded a prefill; a decode step's
    own counts (same names, no prefix) are not read, and a program that
    writes no such counts (the parent commit's) gives nothing, no raise."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "prefill_chunk_fill_pct.serve"]
    assert cell in entry["workloads"] and entry["layer"] == "model step"
    assert (entry["moves"], entry["source"], entry["unit"]) == (
        "serve_tokens_per_s", "program_counter", "%")
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           entry["name"] + ".json")) as f:
        spec = json.load(f)
    read = importlib.import_module("benchmarks.readers." + spec["reader"]).read
    decode = {"occupied": 32, "routed_held": 40, "held_chunk_rows": 0,
              "held_chunks": 0}
    steps = [decode,
             dict(decode, prefill_routed_held=5000, prefill_held_chunks=52,
                  prefill_held_chunk_rows=6656),
             dict(decode, prefill_routed_held=3000, prefill_held_chunks=28,
                  prefill_held_chunk_rows=3584)]
    assert read(metric_ctx(steps), **spec["args"]) == pytest.approx(
        100 * 8000 / 10240)
    assert read(metric_ctx([decode, decode]), **spec["args"]) is None


@pytest.mark.parametrize("cell", ["mistral4_ep8_longdoc_closed32",
                                  "longcat_ep32_agent_closed32",
                                  "laguna_ep16_code_closed32"])
def test_held_loop_turns_reads_the_decode_steps_turns(cell):
    """``held_loop_turns.serve``: the mean of a decode step's ``held_chunks``
    over the traced window's steps: the touched experts a step where the
    step takes the loop (the Mistral-4, LongCat and Laguna cells), 0 where
    it runs every held expert in batched products (the parent commit's
    Mistral-4 and Laguna steps); a prefill's turns, on the
    same spans under another name, are not read; no such count, nothing."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "held_loop_turns.serve"]
    assert cell in entry["workloads"] and entry["layer"] == "model step"
    assert (entry["moves"], entry["source"], entry["better"]) == (
        "serve_tokens_per_s", "program_counter", "lower")
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           entry["name"] + ".json")) as f:
        spec = json.load(f)
    read = importlib.import_module("benchmarks.readers." + spec["reader"]).read
    steps = [{"occupied": 32, "experts_touched": 70, "held_chunks": 70,
              "held_chunk_rows": 70 * 32},
             {"occupied": 32, "experts_touched": 62, "held_chunks": 62,
              "held_chunk_rows": 62 * 32, "prefill_held_chunks": 52,
              "prefill_held_chunk_rows": 6656},
             {"occupied": 31, "experts_touched": 66, "held_chunks": 66,
              "held_chunk_rows": 66 * 32}]
    assert read(metric_ctx(steps), **spec["args"]) == pytest.approx(66.0)
    dense = [dict(step, held_chunks=0, held_chunk_rows=0) for step in steps]
    assert read(metric_ctx(dense), **spec["args"]) == 0.0
    assert read(metric_ctx([{"occupied": 32}]), **spec["args"]) is None


# ---------------------------------------------------------------- the router
@pytest.mark.parametrize("rows,experts,top_k", [
    (64, 256, 8), (300, 512, 22), (1030, 256, 10)],
    ids=["mimo_v2", "nemotron_h", "laguna"])
def test_sigmoid_route_gives_the_bits_a_gather_of_the_scores_gives(
        rows, experts, top_k):
    """``chosen_scores`` picks ``p`` at the chosen experts by a select where
    ``take_along_axis`` gathered ``N k`` scalars: the same experts and the
    same combine weights to the bit, under jit (where a pass could reorder
    a sum it may merge), with a bias that moves the choice off the largest
    scores."""
    key = jax.random.PRNGKey(rows)
    u = jax.random.normal(key, (rows, 48), jnp.float32)
    router = jax.random.normal(key, (48, experts), jnp.float32) * 0.2
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (experts,))

    def with_a_gather(u, router, bias):
        p = jax.nn.sigmoid(
            jnp.dot(u, router, precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(p + bias, top_k)
        chosen = jnp.take_along_axis(p, sel, axis=-1)
        return sel, 2.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    sel, w = jax.jit(lambda *a: sigmoid_route(*a, top_k, 2.5))(
        u, router, bias)
    want_sel, want_w = jax.jit(with_a_gather)(u, router, bias)
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(w, want_w)
    assert float(jnp.abs(w.sum(-1) - 2.5).max()) < 1e-5
