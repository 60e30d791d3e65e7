"""OpenAI-compatible LLM serving on top of ``ray_tpu.serve``.

Reference: ray ``python/ray/llm/_internal/serve/core/server/`` (the
OpenAI-compatible router over vLLM deployments) and ``serve/llm``'s
``build_openai_app``.  The deployment holds one ``JaxLLMEngine`` per
replica (one chip each via ``num_tpus=1``); ``@serve.batch`` coalesces
concurrent single-prompt calls so they enter the engine's slot pool
together.  Endpoints: ``/v1/completions`` and ``/v1/chat/completions``
via the serve HTTP proxy (the raw JSON body arrives as the call's single
argument).
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Dict, List, Optional

from .. import serve
from ..util import tracing
from .engine import EngineConfig, JaxLLMEngine, SamplingParams


def _sampling_from_request(body: Dict[str, Any]) -> SamplingParams:
    return SamplingParams(
        max_tokens=int(body.get("max_tokens", 64)),
        temperature=float(body.get("temperature", 0.0)),
        top_p=float(body.get("top_p", 1.0)),
    )


@serve.deployment(name="LLMServer", ray_actor_options={"num_cpus": 1})
class LLMServer:
    """One engine per replica; requests batch dynamically."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None,
                 model_name: str = "ray-tpu-gpt2"):
        self.engine = JaxLLMEngine(engine_cfg or EngineConfig())
        self.model_name = model_name

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
    async def _generate_batch(self, requests: List[tuple]):
        """requests: [(prompt, SamplingParams)] — one engine pass serves
        them all (the engine's slot pool IS the batch), beside whatever SSE
        streams wait on the same engine's loop from replica threads.  No
        deadline of its own: the handle's caller has one, and a request ends
        at ``max_tokens``."""
        return self.engine.wait(
            [self.engine.add_request(prompt, params)
             for prompt, params in requests],
            timeout_s=float("inf"))

    async def __call__(self, body: Dict[str, Any]):
        """OpenAI completions-ish: dispatch on request shape.  With
        ``"stream": true`` the proxy calls this through the streaming path
        and SSE-frames each yielded chunk (OpenAI ``stream`` semantics)."""
        if body.get("stream") is True:
            return self.stream_chunks(body)
        if "messages" in body:
            return await self.chat(body)
        return await self.completions(body)

    def stream_chunks(self, body: Dict[str, Any]):
        """Sync generator of OpenAI-style streaming chunks (one per engine
        step while the consumer keeps up).  Runs on a replica thread via
        handle_request_streaming."""
        yield from _stream_openai_chunks(
            self.engine.generate_stream(
                _prompt_from_body(body), _sampling_from_request(body)
            ),
            body, self.model_name,
        )

    def engine_stats(self) -> Dict[str, Any]:
        """The engine's counters (``JaxLLMEngine.stats``): steps, admitted,
        retired, tokens, slot occupancy, queue and lock wait."""
        return self.engine.stats()

    def start_profile(self, path: str) -> str:
        """Trace THIS replica (only the process that holds the chip can):
        device operations and the engine's spans into ``path``, on one
        clock, until ``stop_profile``.  See docs/llm_serving.md."""
        tracing.start_profile(path)
        return path

    def stop_profile(self) -> None:
        """End the session; ``<path>/programs.jsonl`` (this replica's
        programs' instruction -> ``op_name`` tables) is written after it."""
        tracing.stop_profile()

    def device_info(self) -> Dict[str, Any]:
        """The devices this replica's engine runs on, as jax reports them."""
        import jax

        devices = jax.devices()
        stats = devices[0].memory_stats() or {}
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }

    async def completions(self, body: Dict[str, Any]) -> Dict[str, Any]:
        prompt = body.get("prompt", "")
        out = await self._generate_batch((prompt, _sampling_from_request(body)))
        return _unary_response(
            body, out, self.model_name, chat=False,
            prompt_tokens=len(self.engine.tokenizer.encode(prompt)),
        )

    async def chat(self, body: Dict[str, Any]) -> Dict[str, Any]:
        prompt = _prompt_from_body(body)
        out = await self._generate_batch((prompt, _sampling_from_request(body)))
        return _unary_response(
            body, out, self.model_name, chat=True,
            prompt_tokens=len(self.engine.tokenizer.encode(prompt)),
        )


@serve.deployment(name="LLMDisaggServer", ray_actor_options={"num_cpus": 0})
class LLMDisaggServer:
    """OpenAI endpoints over the disaggregated path.

    One replica of this deployment owns a prefill pool + a decode pool
    (``llm.disagg.PrefillReplica`` / ``DecodeReplica`` actors) and routes
    through ``DisaggRouter``.  Streaming requests flow proxy → this replica
    (``serve.request.stream`` span) → prefill actor → decode actor, each
    hop inheriting the request's trace context, so one stitched cluster
    trace (returned in ``x-ray-tpu-trace-id``) covers the whole streaming
    request."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None,
                 model_name: str = "ray-tpu-gpt2",
                 num_prefill: int = 1, num_decode: int = 1,
                 num_cpus_per_replica: float = 0.0,
                 num_tpus_per_replica: float = 0):
        import ray_tpu
        from .disagg import DecodeReplica, DisaggRouter, PrefillReplica

        from .tokenizer import ByteTokenizer

        engine_cfg = engine_cfg or EngineConfig()
        self.model_name = model_name
        # Same default tokenizer the replica engines use — usage token
        # accounting must match the monolithic server's.
        self._tokenizer = ByteTokenizer()
        # Each prefill / decode actor is its own process: with
        # num_tpus_per_replica they each lease that many chips, the way
        # build_openai_app's num_tpus gives LLMServer its chip (so this
        # path needs two chips at least; one chip serves through LLMServer).
        opts: Dict[str, Any] = {"num_cpus": num_cpus_per_replica}
        if num_tpus_per_replica:
            opts["num_tpus"] = num_tpus_per_replica
        Pre = ray_tpu.remote(**opts)(PrefillReplica)
        # max_concurrency is load-bearing: concurrent run()/run_stream()
        # calls wait on one shared engine's loop, so their requests share
        # its decode batch; on an exclusive actor each would decode alone.
        Dec = ray_tpu.remote(max_concurrency=64, **opts)(DecodeReplica)
        self._prefill = [Pre.remote(engine_cfg) for _ in range(num_prefill)]
        self._decode = [Dec.remote(engine_cfg) for _ in range(num_decode)]
        self.router = DisaggRouter(self._prefill, self._decode)

    def __call__(self, body: Dict[str, Any]):
        # Deliberately sync: the router blocks on actor round trips, so
        # the replica runs this on an executor thread (RTL005 — blocking
        # work must stay off the replica event loop); the streaming case
        # returns a sync generator the streaming path pulls on a thread.
        if body.get("stream") is True:
            return self.stream_chunks(body)
        prompt = _prompt_from_body(body)
        out = self.router.generate(prompt, _sampling_from_request(body))
        return _unary_response(
            body, out, self.model_name, chat="messages" in body,
            prompt_tokens=len(self._tokenizer.encode(prompt)),
        )

    def stream_chunks(self, body: Dict[str, Any]):
        """Sync generator of OpenAI streaming chunks over the router's
        disaggregated stream (runs on a replica thread; actor hops inside
        inherit the serve.request.stream trace context)."""
        yield from _stream_openai_chunks(
            self.router.stream(
                _prompt_from_body(body), _sampling_from_request(body)
            ),
            body, self.model_name,
        )

    def stats(self) -> Dict[str, Any]:
        """Each decode replica's ``JaxLLMEngine.stats()``."""
        import ray_tpu

        return {"decode": ray_tpu.get(
            [d.stats.remote() for d in self._decode], timeout=30)}

    def check_health(self):
        # Deliberately does NOT round-trip to the child actors: one still
        # building its engine (weights, compiles) would turn "starting"
        # into health strikes against THIS replica (the reconciler would
        # kill it and orphan the children).  Child failures surface as
        # request errors instead.
        return True


def _prompt_from_body(body: Dict[str, Any]) -> str:
    if "messages" in body:
        return "\n".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}"
            for m in body.get("messages", [])
        ) + "\nassistant:"
    return body.get("prompt", "")


def _chunk_framer(body: Dict[str, Any], model_name: str, chat: bool):
    cid = f"{'chatcmpl' if chat else 'cmpl'}-{uuid.uuid4().hex[:12]}"
    created = int(time.time())
    obj = "chat.completion.chunk" if chat else "text_completion"

    def frame(choice):
        return {
            "id": cid,
            "object": obj,
            "created": created,
            "model": body.get("model", model_name),
            "choices": [choice],
        }

    return frame


def _stream_openai_chunks(deltas, body: Dict[str, Any], model_name: str):
    """Frame an engine/router delta stream as OpenAI streaming chunks —
    the ONE chunk shape both serve deployments emit.  The terminal
    finish_reason chunk is always yielded (OpenAI semantics), which also
    keeps the stream observable when every generated token decodes to
    empty text (the byte tokenizer drops ids outside its range) — SSE
    consumers never see a bare [DONE] with zero chunks."""
    chat = "messages" in body
    frame = _chunk_framer(body, model_name, chat)
    for delta in deltas:
        if chat:
            yield frame({"index": 0, "delta": {"content": delta},
                         "finish_reason": None})
        else:
            yield frame({"index": 0, "text": delta, "finish_reason": None})
    if chat:
        yield frame({"index": 0, "delta": {}, "finish_reason": "stop"})
    else:
        yield frame({"index": 0, "text": "", "finish_reason": "stop"})


def _unary_response(body: Dict[str, Any], out: Dict[str, Any],
                    model_name: str, chat: bool,
                    prompt_tokens: int = 0) -> Dict[str, Any]:
    usage = {
        "completion_tokens": out["num_generated"],
        "prompt_tokens": prompt_tokens,
        "total_tokens": prompt_tokens + out["num_generated"],
    }
    if chat:
        return {
            "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": body.get("model", model_name),
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": out["text"]},
                    "finish_reason": "stop",
                }
            ],
            "usage": usage,
        }
    return {
        "id": f"cmpl-{uuid.uuid4().hex[:12]}",
        "object": "text_completion",
        "created": int(time.time()),
        "model": body.get("model", model_name),
        "choices": [
            {"index": 0, "text": out["text"], "finish_reason": "stop"}
        ],
        "usage": usage,
    }


def build_disagg_openai_app(
    engine_cfg: Optional[EngineConfig] = None,
    model_name: str = "ray-tpu-gpt2",
    num_prefill: int = 1,
    num_decode: int = 1,
    num_tpus: float = 0,
):
    """OpenAI app over the prefill/decode disaggregated path; expose via
    ``serve.run`` + ``serve.start_http_proxy`` like ``build_openai_app``
    (same ``/v1`` endpoints, ``stream: true`` SSE included).  ``num_tpus``
    chips go to EACH prefill and decode actor (the router replica itself
    stays off the chip)."""
    d = LLMDisaggServer.options(route_prefix="/v1")
    return d.bind(
        engine_cfg, model_name, num_prefill, num_decode,
        num_tpus_per_replica=num_tpus,
    )


def build_openai_app(
    engine_cfg: Optional[EngineConfig] = None,
    model_name: str = "ray-tpu-gpt2",
    num_replicas: int = 1,
    num_tpus: float = 0,
):
    """Build the Serve application; run with ``serve.run(app)`` and expose
    via ``serve.start_http_proxy()`` — then POST to ``/v1/completions`` or
    ``/v1/chat/completions``."""
    opts: Dict[str, Any] = {"num_cpus": 1}
    if num_tpus:
        opts = {"num_cpus": 0, "num_tpus": num_tpus}
    d = LLMServer.options(
        num_replicas=num_replicas,
        ray_actor_options=opts,
        route_prefix="/v1",
    )
    return d.bind(engine_cfg, model_name)
