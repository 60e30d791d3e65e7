"""Where the engine's weights lie: the decode step's choice, made at build.

The decode step is jitted with its params' device layouts left to the
compiler (``jit_decode_step``); the build compiles it first, from shapes,
moves every leaf that lies otherwise into the layout the program reports
(``lay_out``, once), and compiles the prefill rungs against the weights as
they then lie.  On the CPU the compiler asks for the default layout of every
leaf, so nothing moves; the CPU backend does hold an array in a second
layout, so a stand-in for ``jit_decode_step`` that ASKS for one drives the
whole path here.  What the v5e's compiler asks for, and what it then copies,
is in ``tests/test_tpu_compile.py``.  Nothing here times anything.
"""

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import ray_tpu.llm.engine as engine_module
from ray_tpu.llm.engine import (EngineConfig, JaxLLMEngine, SamplingParams,
                                lay_out)
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import (GPT2Config, LagunaConfig, LlamaConfig,
                            LongcatConfig, MimoV2Config, model_family)
from ray_tpu.util import tracing

SEQ = 512  # rungs 256, 512
FAMILIES = {
    "gpt2": lambda **kw: GPT2Config.tiny(vocab_size=384, max_seq=SEQ, **kw),
    "llama": lambda **kw: LlamaConfig.tiny(vocab_size=384, max_seq=SEQ, **kw),
    "longcat": lambda **kw: LongcatConfig.tiny(vocab_size=384, **kw),
    "mimo_v2": lambda **kw: MimoV2Config.tiny(vocab_size=384, **kw),
    "laguna": lambda **kw: LagunaConfig.tiny(vocab_size=384, **kw),
}
PROMPTS = ["where do the weights lie", "b" * 300, "as the decode step reads"]
GREEDY = SamplingParams(max_tokens=6, stop_token=-1)


class IdTokenizer(ByteTokenizer):
    """Every id one visible character: a streamed text shows its ids."""

    def decode(self, ids):
        return "".join(chr(0x100 + i) for i in ids)


def make_engine(family: str, loader=None, **model) -> JaxLLMEngine:
    return JaxLLMEngine(EngineConfig(
        model=FAMILIES[family](**model), max_batch_size=2, max_seq_len=SEQ,
        param_loader=loader), tokenizer=IdTokenizer())


def formats_of(tree):
    return jax.tree.map(lambda leaf: leaf.format, tree)


def shapes_of(tree):
    return jax.tree.map(lambda leaf: (leaf.shape, leaf.dtype), tree)


def minor_axes_swapped(leaf) -> Format:
    """A second layout for a leaf of three axes or more (its last two
    change places in memory), the default for the others."""
    order = tuple(range(leaf.ndim))
    if leaf.ndim >= 3:
        order = order[:-2] + (order[-1], order[-2])
    return Format(Layout(major_to_minor=order), leaf.sharding)


def ask_for_a_second_layout(monkeypatch) -> None:
    """From here on ``jit_decode_step`` asks for ``minor_axes_swapped`` where
    the real one leaves the layout to the compiler: the same program, the
    same name."""
    def jit_decode_step(family, model, params):
        decode_step = family.decode_step_counted or (
            engine_module._without_counts(family.decode_step))
        return jax.jit(
            lambda params, cache, tokens, pos: decode_step(
                params, tokens, pos, cache, model),
            in_shardings=(jax.tree.map(minor_axes_swapped, params),
                          None, None, None),
            donate_argnums=(1,))

    monkeypatch.setattr(engine_module, "jit_decode_step", jit_decode_step)


@pytest.fixture
def a_compiler_that_asks(monkeypatch):
    ask_for_a_second_layout(monkeypatch)


def all_three_ways(engine) -> list:
    """The prompts' greedy ids: batched = one by one = streamed."""
    batched = [r["token_ids"] for r in engine.generate(PROMPTS, GREEDY)]
    unary = [engine.generate([p], GREEDY)[0]["token_ids"] for p in PROMPTS]
    assert unary == batched
    streamed = ["".join(engine.generate_stream(p, GREEDY)) for p in PROMPTS]
    assert streamed == [engine.tokenizer.decode(ids) for ids in batched]
    return batched


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_on_the_cpu_nothing_moves_and_every_program_agrees(family):
    engine = make_engine(family)
    model = engine.cfg.model
    assert shapes_of(engine.params) == shapes_of(jax.eval_shape(
        lambda: model_family(model).init(jax.random.PRNGKey(0), model)))
    assert engine.stats()["relaid_param_bytes"] == 0
    lying = formats_of(engine.params)
    assert engine._decode.input_formats[0][0] == lying
    assert sorted(engine._prefill_one) == [256, 512]
    for rung in engine._prefill_one.values():
        assert rung.input_formats[0][0] == lying
    all_three_ways(engine)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_loaders_weights_end_in_the_decode_steps_formats(
    family, a_compiler_that_asks
):
    """Arrays that arrive in the default layout end where the decode
    program wants them, once, with the shapes, dtypes and values the loader
    gave; every rung is compiled for them there, so no call relays one; and
    the tokens are those of an engine whose weights never moved."""
    model = FAMILIES[family]()
    given = model_family(model).init(jax.random.PRNGKey(7), model)
    arrived = formats_of(given)
    engine = make_engine(family, loader=lambda: given)

    asked = engine._decode.input_formats[0][0]
    assert formats_of(engine.params) == asked
    moved = [leaf for leaf, was, now in zip(*map(
        jax.tree.leaves, (given, arrived, asked))) if was != now]
    assert {leaf.ndim >= 3 for leaf in moved} == {True}
    assert len(moved) == sum(
        leaf.ndim >= 3 for leaf in jax.tree.leaves(given))
    assert engine.stats()["relaid_param_bytes"] == sum(
        leaf.nbytes for leaf in moved) > 0
    # a leaf that lay right already is the loader's own array
    assert all(now is was or was.ndim >= 3 for was, now in zip(
        jax.tree.leaves(given), jax.tree.leaves(engine.params)))
    assert shapes_of(engine.params) == shapes_of(given)
    jax.tree.map(np.testing.assert_array_equal, engine.params, given)
    for rung in engine._prefill_one.values():
        assert rung.input_formats[0][0] == asked

    monolithic = all_three_ways(engine)
    # the by-hand step() runs the same two executables
    first = engine.add_request(PROMPTS[0], GREEDY)
    while engine.has_unfinished():
        engine.step()
    assert engine.wait([first])[0]["token_ids"] == monolithic[0]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tokens_are_those_of_weights_that_never_moved(family, monkeypatch):
    plain = all_three_ways(make_engine(family))
    ask_for_a_second_layout(monkeypatch)
    relaid = make_engine(family)
    assert relaid.stats()["relaid_param_bytes"] > 0
    assert all_three_ways(relaid) == plain


def test_a_decode_replica_with_relaid_weights_matches_monolithic(monkeypatch):
    """``llm/disagg.py``: the decode replica is this engine, so its weights
    lie as its decode step asks; the prefill replica builds its own params
    and its row arrives through ``add_request_from_kv``, whose one program
    (``splice_row``) takes no weight."""
    from ray_tpu.llm.disagg import DecodeReplica, DisaggRouter, PrefillReplica

    cfg = EngineConfig(max_batch_size=2, max_seq_len=64, seed=3)
    greedy = SamplingParams(max_tokens=8, temperature=0.0)
    mono = JaxLLMEngine(cfg).generate(PROMPTS[::2], greedy)
    ask_for_a_second_layout(monkeypatch)
    decode = DecodeReplica(cfg)
    assert decode.stats()["relaid_param_bytes"] > 0
    router = DisaggRouter([PrefillReplica(cfg)], [decode])
    assert [router.generate(p, greedy)["token_ids"] for p in PROMPTS[::2]] == [
        m["token_ids"] for m in mono]


def test_lay_out_moves_only_what_lies_otherwise():
    a = jax.numpy.arange(24.0).reshape(2, 3, 4)
    b = jax.numpy.arange(6.0).reshape(2, 3)
    params = {"a": a, "b": b}
    same, moved = lay_out(params, formats_of(params))
    assert moved == 0 and same["a"] is a and same["b"] is b
    # formats as a compiled program reports them: the backend's own
    asked = formats_of(jax.tree.map(
        lambda leaf: jax.device_put(leaf, minor_axes_swapped(leaf)), params))
    laid, moved = lay_out(params, asked)
    assert moved == a.nbytes and laid["b"] is b
    assert laid["a"].format == asked["a"] != a.format
    assert laid["a"].shape == a.shape
    np.testing.assert_array_equal(laid["a"], a)
    again, moved = lay_out(laid, formats_of(laid))
    assert moved == 0 and again["a"] is laid["a"]


@pytest.mark.parametrize("stubborn", [False, True])
def test_lay_out_holds_a_moved_leaf_to_the_layout_asked_for(
    monkeypatch, stubborn
):
    """``device_put`` into a layout has come back in the OLD layout in
    silence (v5e, an uncommitted leaf, every program out of the compile
    cache: PERF.md, PR 45), and the first prefill refused the weight.
    ``lay_out`` looks at what came back: a leaf that did not move is put
    again (the copy it came back as moves), and one that still lies
    otherwise is an error at build, not in the first request."""
    a = jax.numpy.arange(24.0).reshape(2, 3, 4)
    asked = formats_of({"a": jax.device_put(a, minor_axes_swapped(a))})
    real, calls = jax.device_put, []

    def forgetful(x, fmt):
        calls.append(x)
        if stubborn or len(calls) == 1:  # comes back as a copy, unmoved
            return real(x, x.format)
        return real(x, fmt)

    monkeypatch.setattr(jax, "device_put", forgetful)
    if stubborn:
        with pytest.raises(RuntimeError, match="stays in layout"):
            lay_out({"a": a}, asked)
        return
    laid, moved = lay_out({"a": a}, asked)
    assert len(calls) == 2 and calls[0] is a and calls[1] is not a
    assert laid["a"].format == asked["a"] and moved == a.nbytes
    np.testing.assert_array_equal(laid["a"], a)


def test_a_loader_of_another_dtype_gets_a_decode_step_of_its_own(monkeypatch):
    """The decode step is compiled before the weights are there, from the
    shapes the family's ``init`` gives.  A loader that hands over other
    dtypes (a float32 checkpoint for a bfloat16 config) costs a second
    compilation, from what arrived; one that hands over ``init``'s costs
    none."""
    compiled_for = []
    real = engine_module.jit_decode_step

    def spy(family, model, params):
        compiled_for.append({leaf.dtype.name
                             for leaf in jax.tree.leaves(params)})
        return real(family, model, params)

    monkeypatch.setattr(engine_module, "jit_decode_step", spy)
    model = FAMILIES["llama"](dtype="float32")
    given = model_family(model).init(jax.random.PRNGKey(3), model)
    make_engine("llama", loader=lambda: given, dtype="float32")
    assert compiled_for == [{"float32"}]
    del compiled_for[:]
    engine = make_engine("llama", loader=lambda: given, dtype="bfloat16")
    assert compiled_for == [{"bfloat16"}, {"float32"}]
    assert shapes_of(engine.params) == shapes_of(given)
    assert len(engine.generate(PROMPTS[:1], GREEDY)[0]["token_ids"]) == 6


def test_a_loader_of_host_arrays_ends_on_the_device():
    model = FAMILIES["gpt2"]()
    given = model_family(model).init(jax.random.PRNGKey(5), model)
    on_device = make_engine("gpt2", loader=lambda: given)
    from_host = make_engine(
        "gpt2", loader=lambda: jax.tree.map(np.asarray, given))
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves(from_host.params))
    assert formats_of(from_host.params) == formats_of(on_device.params)
    assert (from_host.generate(PROMPTS, GREEDY)
            == on_device.generate(PROMPTS, GREEDY))


@pytest.mark.parametrize("family", ["llama", "mimo_v2"])
def test_the_decode_step_compiles_beside_the_load_and_the_rungs_after_it(
    monkeypatch, a_compiler_that_asks, family
):
    """Build order, as the build GUARANTEES it by the order of its own
    statements (not by who wins a race between a tiny model's load and a
    thread's start): the decode step's compilation is handed to the pool
    before the loader is called (it needs shapes alone, so the two run side
    by side); no rung is handed over before the decode step's compilation
    has ended (a rung is compiled for the weights as they will lie, which
    needs the decode step's answer and no weight); the relayout follows
    both the decode step and the load and says what it moved.  One list
    takes the pool's submissions, the loader's call and every span as it
    ends, in the order they happen."""
    events, rows = [], []

    def record(row):
        rows.append(row)
        events.append(("span", row.name, row.attributes.get("program")))

    class RecordingPool(engine_module.ThreadPoolExecutor):
        def submit(self, fn, *args, **kwargs):
            events.append(("submit", fn.__name__, None))
            return super().submit(fn, *args, **kwargs)

    monkeypatch.setattr(tracing, "_record", record)
    monkeypatch.setattr(engine_module, "ThreadPoolExecutor", RecordingPool)
    model = FAMILIES[family]()

    def loader():
        events.append(("load", None, None))
        return model_family(model).init(jax.random.PRNGKey(0), model)

    engine = make_engine(family, loader=loader)
    by_name = {}
    for row in rows:
        by_name.setdefault(row.name, []).append(row)
    [weights] = by_name["llm.engine.weights"]
    [relayout] = by_name["llm.engine.relayout"]
    [decode] = [r for r in by_name["llm.engine.compile"]
                if r.attributes["program"] == "decode_step"]
    rungs = [r for r in by_name["llm.engine.compile"]
             if r.attributes["program"] == "prefill_one"]
    assert sorted(r.attributes["rung"] for r in rungs) == [256, 512]
    # submitted, then loaded: nothing of the decode step waits for a weight
    assert events.index(("submit", "compile_all", None)) < events.index(
        ("load", None, None))
    # every rung is handed over after the decode step's span has ended
    decoded = events.index(("span", "llm.engine.compile", "decode_step"))
    handed = [i for i, e in enumerate(events)
              if e == ("submit", "compile_rung", None)]
    assert len(handed) == 2 and decoded < min(handed)
    assert all(decode.end <= r.start for r in rungs)
    assert max(decode.end, weights.end) <= relayout.start
    assert relayout.attributes == {
        "relaid_param_bytes": engine.stats()["relaid_param_bytes"]}
    assert relayout.attributes["relaid_param_bytes"] > 0
