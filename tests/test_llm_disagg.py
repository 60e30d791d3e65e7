"""Prefill/decode disaggregated serving: KV pages move prefill->decode
over the device-object plane and outputs match the monolithic engine
token for token (reference: llm/_internal/serve/serving_patterns/
prefill_decode/ + engines/vllm/kv_transfer/)."""

import time

import pytest

import ray_tpu
from ray_tpu.llm.disagg import DecodeReplica, DisaggRouter, PrefillReplica
from ray_tpu.llm.engine import EngineConfig, JaxLLMEngine, SamplingParams

PROMPTS = ["hello world", "jax on tpu", "disaggregate me", "one more prompt"]


def _cfg():
    return EngineConfig(max_batch_size=4, max_seq_len=64, seed=3)


def _greedy():
    return SamplingParams(max_tokens=12, temperature=0.0)


def _mono_outputs():
    engine = JaxLLMEngine(_cfg())
    return engine.generate(PROMPTS, _greedy())


def test_local_disagg_matches_monolithic():
    mono = _mono_outputs()
    router = DisaggRouter(
        [PrefillReplica(_cfg())], [DecodeReplica(_cfg())]
    )
    for prompt, expect in zip(PROMPTS, mono):
        got = router.generate(prompt, _greedy())
        assert got["token_ids"] == expect["token_ids"], prompt
        assert got["text"] == expect["text"]


def test_router_spreads_requests_over_both_pools():
    """Round robin over the prefill pool and over the decode pool, whatever
    the prompt (no replica holds anything a repeat could hit)."""
    prefills = []

    class CountingPrefill(PrefillReplica):
        def prefill(self, prompt, params=None):
            prefills.append(self)
            return super().prefill(prompt, params)

    pre = [CountingPrefill(_cfg()) for _ in range(2)]
    dec = [DecodeReplica(_cfg()) for _ in range(2)]
    router = DisaggRouter(pre, dec)
    prompts = PROMPTS + [PROMPTS[0], PROMPTS[0]]  # repeats spread too
    outs = router.generate_many(prompts, _greedy())
    mono = _mono_outputs()
    assert [o["token_ids"] for o in outs] == [
        m["token_ids"] for m in mono + [mono[0], mono[0]]]
    assert [prefills.count(p) for p in pre] == [3, 3]
    assert [d.stats()["admitted"] for d in dec] == [3, 3]
    assert all(d.stats()["occupied"] == 0 for d in dec)


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8)
    yield
    import ray_tpu.serve as serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_actor_disagg_2p2d_matches_monolithic(cluster):
    mono = _mono_outputs()

    Pre = ray_tpu.remote(num_cpus=1)(PrefillReplica)
    Dec = ray_tpu.remote(num_cpus=1)(DecodeReplica)
    prefill = [Pre.remote(_cfg()) for _ in range(2)]
    decode = [Dec.remote(_cfg()) for _ in range(2)]
    router = DisaggRouter(prefill, decode)

    outs = router.generate_many(PROMPTS, _greedy(), timeout_s=240)
    assert [o["token_ids"] for o in outs] == [m["token_ids"] for m in mono]
    assert [o["text"] for o in outs] == [m["text"] for m in mono]
    for a in prefill + decode:
        ray_tpu.kill(a)


def test_disagg_run_stream_matches_run(cluster):
    """run_stream yields the same text run() returns, token-incremental,
    and concurrent admissions share the decode batch (max_concurrency)."""
    mono = _mono_outputs()

    Pre = ray_tpu.remote(num_cpus=1)(PrefillReplica)
    Dec = ray_tpu.remote(num_cpus=1, max_concurrency=4)(DecodeReplica)
    pre = Pre.remote(_cfg())
    dec = Dec.remote(_cfg())
    try:
        meta = ray_tpu.get(
            pre.prefill.remote(PROMPTS[0], _greedy()), timeout=240
        )
        rid = ray_tpu.get(dec.add_from_kv.remote(meta), timeout=240)
        gen = dec.run_stream.options(num_returns="streaming").remote(rid)
        deltas = [ray_tpu.get(d, timeout=240) for d in gen]
        assert len(deltas) >= 2  # incremental, not one final blob
        assert "".join(deltas) == mono[0]["text"]
    finally:
        for a in (pre, dec):
            ray_tpu.kill(a)


class TestDisaggServeApp:
    def test_sse_stream_stitched_trace(self, cluster):
        """One streaming request exports ONE stitched trace:
        proxy span -> replica serve.request.stream -> prefill task ->
        decode stream, with the trace id in x-ray-tpu-trace-id."""
        import json
        import urllib.error
        import urllib.request

        import ray_tpu.serve as serve
        from ray_tpu.llm import build_disagg_openai_app
        from ray_tpu.util import obs, tracing

        serve.run(build_disagg_openai_app(_cfg()))
        url = serve.start_http_proxy(port=8179)
        req = urllib.request.Request(
            f"{url}/v1/completions",
            data=json.dumps(
                {"prompt": "trace me", "max_tokens": 4, "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        deadline = time.monotonic() + 90.0
        while True:
            try:
                resp = urllib.request.urlopen(req, timeout=240)
                break
            except urllib.error.HTTPError:
                raise
            except (urllib.error.URLError, ConnectionError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        trace_id = resp.headers["x-ray-tpu-trace-id"]
        raw = resp.read().decode()
        frames = [
            line[len("data: "):]
            for line in raw.splitlines() if line.startswith("data: ")
        ]
        assert frames[-1] == "[DONE]"
        chunks = [json.loads(f) for f in frames[:-1]]
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
        assert trace_id
        # serve.request.stream is recorded at stream END and flushes
        # asynchronously — poll the trace until every required hop
        # appears instead of trusting the first >=3 spans.
        required = {"serve.http.stream", "serve.request.stream"}
        deadline = time.monotonic() + 120
        while True:
            spans = tracing.get_trace(trace_id, min_spans=3, timeout=30)
            names = {s["name"] for s in spans}
            if required <= names and len(obs.trace_processes(trace_id)) >= 3:
                break
            assert time.monotonic() < deadline, sorted(names)
            time.sleep(0.5)
        serve.stop_http_proxy()
        serve.delete("LLMDisaggServer")

    def test_unary_completions_via_router(self, cluster):
        import ray_tpu.serve as serve
        from ray_tpu.llm import build_disagg_openai_app

        handle = serve.run(build_disagg_openai_app(_cfg()))
        out = handle.remote(
            {"prompt": "hi", "max_tokens": 4}
        ).result(timeout=240)
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] >= 1
        serve.delete("LLMDisaggServer")
