"""LLM engine, OpenAI-compatible serving, and batch inference tests."""

import asyncio
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (
    ByteTokenizer,
    EngineConfig,
    JaxLLMEngine,
    SamplingParams,
    build_llm_processor,
    build_openai_app,
)
from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine
from ray_tpu.llm.serve_app import LLMServer
from ray_tpu.models import LlamaConfig
from ray_tpu.models.gpt2 import GPT2Config

TINY_MODELS = {
    "gpt2": lambda seq: GPT2Config.tiny(
        vocab_size=384, max_seq=seq, dtype="float32"),
    "llama": lambda seq: LlamaConfig.tiny(
        vocab_size=384, max_seq=seq, dtype="float32"),
}


def _tiny_cfg(family="gpt2", **kw):
    defaults = dict(max_batch_size=4, max_seq_len=64, seed=0)
    defaults.update(kw)
    return EngineConfig(
        model=TINY_MODELS[family](defaults["max_seq_len"]), **defaults)


class TestEngine:
    def test_greedy_deterministic(self):
        engine = JaxLLMEngine(_tiny_cfg())
        p = SamplingParams(max_tokens=8, temperature=0.0)
        [a] = engine.generate(["hello"], p)
        [b] = engine.generate(["hello"], p)
        assert a["token_ids"] == b["token_ids"]
        assert a["num_generated"] <= 8

    def test_kv_cache_matches_full_forward(self):
        """Greedy decode through the KV cache must match naive re-forward
        with gpt2_apply at every step (cache correctness)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt2 import gpt2_apply

        cfg = _tiny_cfg()
        engine = JaxLLMEngine(cfg)
        tok = engine.tokenizer
        prompt_ids = tok.encode("abc")
        [out] = engine.generate(
            ["abc"], SamplingParams(max_tokens=6, temperature=0.0)
        )
        # Naive: argmax over full forward, re-running the whole prefix.
        ids = list(prompt_ids)
        naive = []
        for _ in range(6):
            logits = gpt2_apply(
                engine.params, jnp.asarray([ids]), cfg.model
            )
            nxt = int(jnp.argmax(logits[0, -1]))
            naive.append(nxt)
            ids.append(nxt)
            if nxt == tok.EOS:
                break
        assert out["num_generated"] == len(naive)
        got = out["token_ids"] + (
            [tok.EOS] if out["num_generated"] > len(out["token_ids"]) else []
        )
        assert got == naive

    def test_continuous_batching_overflow(self):
        """More requests than slots stream through the pool."""
        engine = JaxLLMEngine(_tiny_cfg(max_batch_size=2))
        prompts = [f"prompt {i}" for i in range(5)]
        outs = engine.generate(
            prompts, SamplingParams(max_tokens=4, temperature=0.0)
        )
        assert len(outs) == 5
        assert all(o["num_generated"] >= 1 for o in outs)

    def test_ragged_joining(self):
        """Requests of different lengths decode in one batch correctly:
        results match the same prompts run alone."""
        p = SamplingParams(max_tokens=5, temperature=0.0)
        together = JaxLLMEngine(_tiny_cfg()).generate(["a", "longer prompt"], p)
        solo_a = JaxLLMEngine(_tiny_cfg()).generate(["a"], p)
        solo_b = JaxLLMEngine(_tiny_cfg()).generate(["longer prompt"], p)
        assert together[0]["token_ids"] == solo_a[0]["token_ids"]
        assert together[1]["token_ids"] == solo_b[0]["token_ids"]

    @pytest.mark.parametrize("family", sorted(TINY_MODELS))
    def test_admission_mid_decode_matches_solo(self, family):
        """A request added after the others have decoded a few steps joins
        the running batch at a token boundary and gets the ids it gets
        alone (greedy: parity is of the sampled tokens)."""
        cfg = _tiny_cfg(family)
        p = SamplingParams(max_tokens=10, temperature=0.0)
        prompts = ["hello world", "jax on tpu", "disaggregate me", "z"]
        alone = JaxLLMEngine(cfg)
        expected = [alone.generate([q], p)[0]["token_ids"] for q in prompts]

        engine = JaxLLMEngine(cfg)
        ids = []
        for q in prompts:  # staggered: each joins a RUNNING batch
            ids.append(engine.add_request(q, p))
            engine.step()
            engine.step()
        got = engine.wait(ids)
        assert [r["token_ids"] for r in got] == expected
        st = engine.stats()
        assert st["admitted"] == st["retired"] == len(prompts)
        # They really shared decode steps.
        assert st["occupied_slot_steps"] > st["steps"]

    def test_temperature_sampling_runs(self):
        engine = JaxLLMEngine(_tiny_cfg())
        outs = engine.generate(
            ["x"], SamplingParams(max_tokens=8, temperature=1.0, top_p=0.9)
        )
        assert outs[0]["num_generated"] >= 1

    def test_byte_tokenizer_roundtrip(self):
        tok = ByteTokenizer()
        ids = tok.encode("héllo wörld")
        assert ids[0] == tok.BOS
        assert tok.decode(ids[1:]) == "héllo wörld"


# The three callers of ``JaxLLMEngine.wait``, which blocks until the engine's
# loop has finished a set of requests: each builds its engine from ``cfg`` and
# returns (engine, run(prompts, params) -> results, run_out(prompt, params),
# which waits for the prompt with no time left).
def _waiter_generate(cfg):
    engine = JaxLLMEngine(cfg)
    return (engine, engine.generate,
            lambda prompt, p: engine.generate([prompt], p, timeout_s=0.0))


def _waiter_decode_replica(cfg):
    replica, pre = DecodeReplica(cfg), PrefillEngine(cfg)

    def admit(prompt, p):
        return replica.add_from_kv(pre.prefill(prompt, p))

    return (replica.engine,
            lambda prompts, p: [replica.run(admit(q, p)) for q in prompts],
            lambda prompt, p: replica.run(admit(prompt, p), timeout_s=0.0))


def _waiter_llm_server(cfg):
    server = LLMServer.func_or_class(cfg)

    # The body under @serve.batch, handed one flush's requests (the batcher
    # itself keeps a task of the loop it ran on: not for a test process
    # that pickles the class later).
    body = LLMServer.func_or_class._generate_batch.__wrapped__

    # The batched body takes no timeout: its wait, with none left.
    return (server.engine,
            lambda prompts, p: asyncio.run(
                body(server, [(q, p) for q in prompts])),
            lambda prompt, p: server.engine.wait(
                [server.engine.add_request(prompt, p)], timeout_s=0.0))


@pytest.mark.parametrize("make", [
    _waiter_generate, _waiter_decode_replica, _waiter_llm_server],
    ids=["generate", "decode_replica_run", "llm_server_batch"])
def test_every_waiter_gets_its_own_results_beside_a_stream(make):
    """While another thread streams a long request through the same engine,
    a waiter returns the ids it returns alone, the stream its own text; a
    wait that runs out of time cancels its request and frees the slot."""
    cfg = _tiny_cfg(max_batch_size=4, max_seq_len=512)
    engine, run, run_out = make(cfg)
    p = SamplingParams(max_tokens=6, temperature=0.0)
    long = SamplingParams(max_tokens=480, temperature=0.0, stop_token=-1)
    prompts = ["hello world", "jax on tpu", "one more"]
    alone = [r["token_ids"] for r in run(prompts, p)]
    streamed_alone = "".join(engine.generate_stream("stream me", long))

    deltas = []
    streamer = threading.Thread(
        target=lambda: deltas.extend(engine.generate_stream("stream me", long)),
        daemon=True)
    streamer.start()
    deadline = time.monotonic() + 60
    while engine.occupied() == 0:
        assert time.monotonic() < deadline
        time.sleep(0.001)
    beside = [r["token_ids"] for r in run(prompts, p)]
    still_streaming = streamer.is_alive()
    streamer.join(timeout=120)
    assert not streamer.is_alive()
    assert beside == alone
    assert "".join(deltas) == streamed_alone
    assert still_streaming  # 480 steps of stream beside ~20 of waiting

    before = engine.stats()
    with pytest.raises(TimeoutError):
        run_out("never finished", long)
    after = engine.stats()
    assert after["cancelled"] == before["cancelled"] + 1
    assert after["occupied"] == 0 and not engine.has_unfinished()
    assert after["retired"] == before["retired"]


# ------------------------------------------------------------------ the loop
# One thread steps the engine; callers wait on their request's mailbox.  Every
# wait below has a deadline that holds beside five other xdist workers.
LONG_WAIT_S = 120


class IdTokenizer(ByteTokenizer):
    """Every id one visible character: a streamed text shows its ids."""

    def decode(self, ids):
        return "".join(chr(0x100 + i) for i in ids)


def _ids(text):
    return [ord(c) - 0x100 for c in text]


def _loop_engine(**kw):
    return JaxLLMEngine(_tiny_cfg(**kw), tokenizer=IdTokenizer())


def _gated(engine, monkeypatch):
    """Make every step wait for a permit: the test decides how many steps
    run before a consumer looks.  Returns the semaphore."""
    permits = threading.Semaphore(0)
    step_locked = engine._step_locked

    def gated(jnp):
        assert permits.acquire(timeout=LONG_WAIT_S)
        return step_locked(jnp)

    monkeypatch.setattr(engine, "_step_locked", gated)
    return permits


def _until(condition):
    deadline = time.monotonic() + LONG_WAIT_S
    while not condition():
        assert time.monotonic() < deadline
        time.sleep(0.001)


def _in_threads(bodies):
    """Run the callables at once; their results, or the exception each
    raised, in order."""
    out = [None] * len(bodies)

    def run(i, body):
        try:
            out[i] = body()
        except BaseException as e:  # noqa: BLE001 - the test looks at it
            out[i] = e

    threads = [threading.Thread(target=run, args=(i, b), daemon=True)
               for i, b in enumerate(bodies)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=LONG_WAIT_S)
    assert not any(t.is_alive() for t in threads)
    return out


def test_sixteen_streams_at_once_get_the_ids_each_gets_alone():
    p = SamplingParams(max_tokens=12, temperature=0.0, stop_token=-1)
    prompts = [f"prompt number {i} " * (1 + i % 3) for i in range(16)]
    alone = _loop_engine(max_batch_size=16)
    expected = [alone.generate([q], p)[0]["token_ids"] for q in prompts]

    engine = _loop_engine(max_batch_size=16)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # threads change places at every chance
    try:
        texts = _in_threads([
            lambda q=q: "".join(engine.generate_stream(q, p))
            for q in prompts])
    finally:
        sys.setswitchinterval(interval)
    assert [_ids(t) for t in texts] == expected  # no token lost or doubled
    stats = engine.stats()
    assert stats["loop_steps"] == stats["steps"] > 0
    assert stats["admitted"] == stats["retired"] == 16
    assert stats["occupied_slot_steps"] > stats["steps"]  # they shared steps
    assert not engine._mailboxes  # every stream collected its own


def test_a_delta_a_token_while_the_consumer_keeps_up(monkeypatch):
    """With a consumer that takes each delta before the next step runs, a
    stream of n tokens is n deltas of one: the prefill's token leaves in the
    step that admitted it, once that step's decode is dispatched behind the
    prefill; each later one in the step after the one that sampled it, once
    that step's decode is dispatched; the last with the result."""
    n = 9
    p = SamplingParams(max_tokens=n, temperature=0.0, stop_token=-1)
    engine = _loop_engine()
    [want] = engine.generate(["keep up"], p)
    before = engine.stats()
    permits = _gated(engine, monkeypatch)
    deltas = []
    permits.release()
    for delta in engine.generate_stream("keep up", p):
        deltas.append(_ids(delta))
        permits.release()
    assert [len(d) for d in deltas] == [1] * n
    assert sum(deltas, []) == want["token_ids"]
    after = engine.stats()
    # n - 1 decode steps, and one step more that reads the last one's token.
    assert after["decode_steps"] - before["decode_steps"] == n - 1
    assert after["steps"] - before["steps"] == n
    assert after["loop_steps"] == after["steps"]


def test_a_consumer_that_sleeps_gets_one_delta_with_all_it_missed(monkeypatch):
    p = SamplingParams(max_tokens=20, temperature=0.0, stop_token=-1)
    engine = _loop_engine()
    [want] = engine.generate(["sleepy consumer"], p)
    permits = _gated(engine, monkeypatch)
    steps = engine._counts["steps"]  # not stats(): a gated step holds the lock
    stream = engine.generate_stream("sleepy consumer", p)
    permits.release()
    got = [_ids(next(stream))]
    _until(lambda: engine._counts["steps"] == steps + 1)
    assert len(got[0]) == 1  # the step's decode's token is still the device's
    for _ in range(5):  # five steps while nobody looks
        permits.release()
    _until(lambda: engine._counts["steps"] == steps + 6)
    got.append(_ids(next(stream)))
    assert len(got[1]) == 5
    for _ in range(40):
        permits.release()
    got.extend(_ids(delta) for delta in stream)
    assert sum(got, []) == want["token_ids"]  # nothing lost, nothing doubled


def test_a_freed_slot_is_refilled_at_the_next_step(monkeypatch):
    from ray_tpu.util import flight_recorder

    rows = []
    monkeypatch.setattr(flight_recorder, "record_llm_step",
                        lambda *a: rows.append(a))
    slots = 2
    engine = _loop_engine(max_batch_size=slots)
    outs = engine.generate(
        [f"request {i}" for i in range(7)],
        SamplingParams(max_tokens=4, temperature=0.0, stop_token=-1))
    assert len(outs) == 7 and engine.stats()["loop_steps"] == len(rows)
    # (occupied, waiting, admitted, retired, bucket) a step: where a step
    # ends with a free slot and a queue, the next one admits.
    freed = [i for i, (occupied, waiting, *_rest) in enumerate(rows)
             if occupied < slots and waiting]
    assert freed and all(rows[i + 1][2] >= 1 for i in freed)


def test_an_idle_loop_makes_no_steps_and_a_hand_stepped_engine_has_no_thread():
    p = SamplingParams(max_tokens=3, temperature=0.0)
    engine = _loop_engine()
    engine.generate(["wake the loop"], p)
    assert engine._loop.is_alive() and engine._loop.name == "engine.loop"
    steps = engine.stats()["steps"]
    time.sleep(0.5)
    assert engine.stats()["steps"] == steps and not engine.has_unfinished()
    assert engine.generate(["and again"], p)[0]["num_generated"] >= 1

    by_hand = _loop_engine()
    rid = by_hand.add_request("stepped by hand", p)
    done = []
    while by_hand.has_unfinished():
        done.extend(by_hand.step())
    assert [r["request_id"] for r in done] == [rid]
    assert by_hand._loop is None and by_hand.stats()["loop_steps"] == 0


def test_shutdown_joins_the_loop_and_fails_blocked_and_later_callers():
    long = SamplingParams(max_tokens=400, temperature=0.0, stop_token=-1)
    engine = _loop_engine(max_batch_size=1, max_seq_len=512)
    results = []
    blocked = threading.Thread(daemon=True, target=lambda: results.extend(
        _in_threads([lambda: engine.generate(["in the slot"], long),
                     lambda: list(engine.generate_stream("queued", long))])))
    blocked.start()
    _until(lambda: engine.occupied() == 1 and engine._n_waiting() == 1)
    loop = engine._loop
    engine.shutdown()
    assert not loop.is_alive()
    blocked.join(timeout=LONG_WAIT_S)
    assert [type(r) for r in results] == [RuntimeError, RuntimeError]
    assert not engine.has_unfinished() and engine.occupied() == 0
    with pytest.raises(RuntimeError, match="shut down"):
        engine.generate(["too late"], long)
    with pytest.raises(RuntimeError, match="shut down"):
        list(engine.generate_stream("too late", long))
    engine.shutdown()  # again: nothing to do


def test_a_step_that_raises_fails_every_waiter_and_the_loop_goes_on(
        monkeypatch):
    p = SamplingParams(max_tokens=30, temperature=0.0, stop_token=-1)
    engine = _loop_engine(max_batch_size=2)
    [want] = engine.generate(["after the fault"], p)
    decode = engine._decode

    def boom(*_args):
        raise FloatingPointError("boom")

    monkeypatch.setattr(engine, "_decode", boom)
    got = _in_threads([
        lambda: engine.generate(["one"], p),
        lambda: list(engine.generate_stream("two", p)),
        lambda: engine.generate(["three, in the queue"], p)])
    assert [type(r) for r in got] == [FloatingPointError] * 3
    assert not engine.has_unfinished() and not engine._mailboxes
    monkeypatch.setattr(engine, "_decode", decode)
    assert engine._loop.is_alive()
    [again] = engine.generate(["after the fault"], p)
    assert again["token_ids"] == want["token_ids"]


def test_a_timeout_and_an_abandoned_stream_free_slot_and_queue_entry():
    long = SamplingParams(max_tokens=400, temperature=0.0, stop_token=-1)
    engine = _loop_engine(max_batch_size=1, max_seq_len=512)
    holder = engine.generate_stream("holds the slot", long)
    assert next(holder)
    # Queued behind it, out of time: the queue entry goes.
    with pytest.raises(TimeoutError):
        list(engine.generate_stream("queued", long, timeout_s=0.05))
    with pytest.raises(TimeoutError):
        engine.generate(["queued too"], long, timeout_s=0.05)
    stats = engine.stats()
    assert stats["cancelled"] == 2 and stats["waiting"] == 0
    assert stats["occupied"] == 1
    holder.close()  # the consumer walks away: the slot goes
    stats = engine.stats()
    assert stats["cancelled"] == 3 and stats["occupied"] == 0
    assert not engine._mailboxes
    _until(lambda: not engine.has_unfinished())  # the step in flight is read
    time.sleep(0.1)  # a step that began before the cancel may end after it
    steps = engine.stats()["steps"]
    time.sleep(0.1)
    assert engine.stats()["steps"] == steps  # nothing left to step for


class TestSampling:
    def test_top_k_restricts(self):
        import jax

        from ray_tpu.models.sampling import sample_logits

        logits = np.full((1, 10), -10.0, np.float32)
        logits[0, 3] = 5.0
        logits[0, 7] = 4.0
        key = jax.random.PRNGKey(0)
        for i in range(5):
            t = sample_logits(
                jax.numpy.asarray(logits),
                jax.random.fold_in(key, i),
                temperature=1.0,
                top_k=2,
            )
            assert int(t[0]) in (3, 7)

    def test_greedy(self):
        import jax

        from ray_tpu.models.sampling import sample_logits

        logits = np.zeros((2, 5), np.float32)
        logits[0, 2] = 3.0
        logits[1, 4] = 3.0
        t = sample_logits(
            jax.numpy.asarray(logits), jax.random.PRNGKey(0), temperature=0.0
        )
        assert t.tolist() == [2, 4]


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=8)
    yield ctx
    import ray_tpu.serve as serve

    serve.shutdown()
    ray_tpu.shutdown()


class TestServing:
    def test_openai_completions_and_chat(self, cluster):
        import ray_tpu.serve as serve

        app = build_openai_app(_tiny_cfg())
        handle = serve.run(app)
        resp = handle.remote(
            {"prompt": "hi", "max_tokens": 4}
        ).result(timeout=120)
        assert resp["object"] == "text_completion"
        assert isinstance(resp["choices"][0]["text"], str)
        assert resp["usage"]["completion_tokens"] >= 1

        resp = handle.remote(
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 4}
        ).result(timeout=120)
        assert resp["object"] == "chat.completion"
        assert resp["choices"][0]["message"]["role"] == "assistant"
        serve.delete("LLMServer")

    def test_a_unary_request_outlasts_a_metrics_flush(self, cluster):
        """The unary route blocks the replica's event loop until the
        engine's loop has finished the request, and the engine's loop
        records metrics every step: a flush that falls due meanwhile (every
        2 s) must not wait for the event loop, or neither ever returns."""
        import ray_tpu.serve as serve

        handle = serve.run(build_openai_app(_tiny_cfg(max_seq_len=2048)))
        asked = 1900  # several seconds of steps
        resp = handle.remote(
            {"prompt": "hi", "max_tokens": asked}).result(timeout=240)
        assert resp["usage"]["completion_tokens"] == asked
        serve.delete("LLMServer")

    def test_http_prefix_routing(self, cluster):
        import json
        import urllib.request

        import ray_tpu.serve as serve

        app = build_openai_app(_tiny_cfg())
        serve.run(app)
        url = serve.start_http_proxy(port=8161)
        req = urllib.request.Request(
            f"{url}/v1/completions",
            data=json.dumps({"prompt": "q", "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"},
        )
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert body["result"]["object"] == "text_completion"
        req = urllib.request.Request(
            f"{url}/v1/chat/completions",
            data=json.dumps(
                {"messages": [{"role": "user", "content": "q"}],
                 "max_tokens": 3}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert body["result"]["object"] == "chat.completion"
        serve.stop_http_proxy()
        serve.delete("LLMServer")


class TestBatchInference:
    def test_processor_over_dataset(self, cluster):
        import ray_tpu.data as rdata

        ds = rdata.from_items(
            [{"prompt": f"p{i}"} for i in range(6)], parallelism=2
        )
        processor = build_llm_processor(
            _tiny_cfg(),
            SamplingParams(max_tokens=3, temperature=0.0),
            concurrency=1,
        )
        rows = processor(ds).take_all()
        assert len(rows) == 6
        assert all(isinstance(r["generated"], str) for r in rows)


class TestTokenStreaming:
    def test_engine_generate_stream(self):
        engine = JaxLLMEngine(_tiny_cfg())
        p = SamplingParams(max_tokens=6, temperature=0.0)
        deltas = list(engine.generate_stream("hello", p))
        assert len(deltas) >= 1
        # Streamed deltas concatenate to the one-shot result.
        full = JaxLLMEngine(_tiny_cfg()).generate(["hello"], p)[0]["text"]
        assert "".join(deltas) == full

    def test_openai_sse_streaming(self, cluster):
        import json
        import time
        import urllib.error
        import urllib.request

        import ray_tpu.serve as serve

        serve.run(build_openai_app(_tiny_cfg()))
        url = serve.start_http_proxy(port=8173)
        req = urllib.request.Request(
            f"{url}/v1/completions",
            data=json.dumps(
                {"prompt": "hi", "max_tokens": 5, "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        # Bounded retry on the connect: the proxy's listening socket comes
        # up asynchronously, so the first request can race the bind — a
        # refused connection within the deadline is retried, never slept
        # through blindly.
        deadline = time.monotonic() + 60.0
        while True:
            try:
                raw = urllib.request.urlopen(req, timeout=180).read().decode()
                break
            except urllib.error.HTTPError:
                raise  # the proxy answered: a real 4xx/5xx, never retried
            except (urllib.error.URLError, ConnectionError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        frames = [
            l[len("data: "):]
            for l in raw.splitlines()
            if l.startswith("data: ")
        ]
        assert frames[-1] == "[DONE]"
        chunks = [json.loads(f) for f in frames[:-1]]
        # The stream always carries at least the terminal finish_reason
        # chunk — even when every sampled token decodes to empty text
        # (tiny-vocab models can greedily emit undecodable ids).
        assert len(chunks) >= 1
        assert chunks[0]["object"] == "text_completion"
        assert all("text" in c["choices"][0] for c in chunks)
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
        serve.stop_http_proxy()
        serve.delete("LLMServer")
