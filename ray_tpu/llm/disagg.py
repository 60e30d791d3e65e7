"""Prefill/decode disaggregated serving.

Reference: ray ``llm/_internal/serve/serving_patterns/prefill_decode/`` +
``engines/vllm/kv_transfer/`` — prefill replicas compute the prompt's KV
cache, decode replicas continue token generation, and the KV pages move
replica-to-replica without re-running the prompt.

TPU-native shape: the KV transfer rides the device-object plane
(``ray_tpu.collective.device_objects``) — the prefill replica keeps the
[L, 1, H, S, D] KV blocks resident and returns ``DeviceRef`` metadata;
the decode replica fetches point-to-point from the owner (ICI/DCN-safe:
same-process hits HBM directly, cross-process streams over the owner's
RPC channel) and splices the pages into its batch cache with one jitted
``dynamic_update_slice``.  The cross-process hop is zero-copy end to
end: ``device_fetch`` replies frame the KV block's host view as an
out-of-band buffer segment (no ``tobytes()`` flat copy — see
``core_worker.handle_device_fetch`` / docs/performance.md) and the
decode side rebuilds with ``np.frombuffer`` straight from the receive
buffer, so a KV handoff costs exactly one D2H and one H2D.  A prefill
replica runs one prefill program and nothing else; a decode replica is a
``JaxLLMEngine`` whose admissions all arrive as KV pages, so its chip runs
the decode step and the sampler (the engine's own prefill programs are
compiled with it and never called).

Why disaggregate (same motivation as the reference): prefill is
compute-bound and bursty, decode is HBM-bound and steady; separating them
lets each pool scale independently and keeps long prompts from stalling
token streams of in-flight requests.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..models import model_family
from ..models.sampling import sample_logits
from .engine import EngineConfig, JaxLLMEngine, SamplingParams
from .tokenizer import ByteTokenizer


def require_kv_cache(family, cache) -> None:
    """The hand-over between replicas moves a prompt's ``k`` and ``v`` pages
    (``[L, 1, Hkv, T, D]`` each) and nothing else.  A family whose cache is
    anything else (a latent cache, state beside keys and values) is refused
    here by name, before a key error somewhere inside a program."""
    leaves = sorted(cache) if isinstance(cache, dict) else [type(cache).__name__]
    if leaves != ["k", "v"]:
        raise NotImplementedError(
            f"model family {family.name!r} keeps a cache with leaves "
            f"{leaves}: disaggregated serving hands over 'k' and 'v' pages "
            "only; serve it with JaxLLMEngine")


class PrefillEngine:
    """Prefill-only engine: prompt -> (first token, resident KV pages).

    No batch slots, no decode program — one jitted prefill over a
    single-row cache; the row is published to the device-object store and
    ownership transfers to the fetching decode replica.
    """

    def __init__(self, cfg: EngineConfig, tokenizer=None):
        import jax

        self.cfg = cfg
        self.tokenizer = tokenizer or ByteTokenizer()
        mcfg = cfg.model
        fam = model_family(mcfg)
        self.family = fam
        if cfg.param_loader is not None:
            self.params = cfg.param_loader()
        else:
            self.params = fam.init(jax.random.PRNGKey(cfg.seed), mcfg)
        self._key = jax.random.PRNGKey(cfg.seed + 1)
        require_kv_cache(fam, jax.eval_shape(
            lambda: fam.init_cache(mcfg, 1, cfg.max_seq_len)))

        def prefill_row(params, tokens, length):
            import jax.numpy as jnp

            cache = fam.init_cache(mcfg, 1, cfg.max_seq_len)
            logits, cache = fam.prefill(
                params, tokens[None], jnp.asarray([length]), cache, mcfg
            )
            return logits[0], cache

        self._prefill_row = jax.jit(prefill_row)
        self._sample = jax.jit(
            sample_logits, static_argnames=("temperature", "top_k", "top_p")
        )

    def prefill(
        self, prompt: str, params: Optional[SamplingParams] = None
    ) -> Dict[str, Any]:
        """Run the prompt; return picklable metadata + KV DeviceRefs.

        The caller (router) hands the dict to a decode replica, which
        fetches and frees the refs — the KV pages live on this replica
        only until that single consumer collects them.
        """
        import jax

        from ..collective.device_objects import device_object_store

        from .engine import encode_prompt

        params = params or SamplingParams()
        token_ids = encode_prompt(self.tokenizer, prompt, self.cfg.max_seq_len)
        tokens = np.zeros(self.cfg.max_seq_len, np.int32)
        tokens[: len(token_ids)] = token_ids
        import jax.numpy as jnp

        logits, cache = self._prefill_row(
            self.params, jnp.asarray(tokens), len(token_ids)
        )
        self._key, sub = jax.random.split(self._key)
        first = int(
            np.asarray(
                self._sample(
                    logits[None], sub,
                    temperature=params.temperature,
                    top_k=params.top_k,
                    top_p=params.top_p,
                )
            )[0]
        )
        store = device_object_store()
        return {
            "prompt_len": len(token_ids),
            "first_token": first,
            "sampling": params,
            "k_ref": store.put(cache["k"]),
            "v_ref": store.put(cache["v"]),
        }


class DecodeReplica:
    """Decode-role replica: adopts prefilled KV, streams decode steps.

    Wraps the standard engine (whose ``add_request_from_kv`` owns the
    disaggregated admission path); its prefill programs are compiled with
    the engine and never run here: all admissions arrive as KV pages.  A
    family whose cache cannot be handed over is refused when the replica is
    BUILT, before any page has been fetched and freed."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None):
        self.engine = JaxLLMEngine(engine_cfg or EngineConfig())
        require_kv_cache(self.engine.family, self.engine.cache)

    def add_from_kv(self, meta: Dict[str, Any]) -> int:
        """Fetch the KV pages from the prefill owner, free them there (this
        replica is their one consumer) and enqueue."""
        from ..collective.device_objects import device_object_store

        store = device_object_store()
        row = {"k": store.fetch(meta["k_ref"]), "v": store.fetch(meta["v_ref"])}
        store.free(meta["k_ref"])
        store.free(meta["v_ref"])
        return self.engine.add_request_from_kv(meta, row)

    def run(self, request_id: int, timeout_s: float = 300.0) -> dict:
        """Decode until this request finishes; returns its result (a
        timeout cancels the request and raises).

        Deploy decode replicas with ``max_concurrency`` > 1: concurrent
        run() calls wait side by side while the engine's one loop steps
        for all of them, and concurrent add_from_kv admissions (arriving
        on other lanes) join the SAME decode batch — on an exclusive actor
        each request would decode solo, which is the anti-pattern
        disaggregation exists to avoid."""
        return self.engine.wait([request_id], timeout_s)[0]

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def run_stream(self, request_id: int, timeout_s: float = 300.0):
        """Stream an adopted request's text deltas as they decode (the
        disaggregated analog of ``JaxLLMEngine.generate_stream``) — this
        replica's streams are never interrupted by prefill programs, the
        inter-token-latency property the pattern exists for.

        Each stream records its TTFT and inter-token-gap histograms
        (``deployment="llm_decode"``)."""
        from ray_tpu.util import flight_recorder

        tele = flight_recorder.StreamTelemetry("llm_decode", "decode")
        outcome = "ok"
        try:
            for delta in self.engine.stream_request(request_id, timeout_s):
                tele.tick()
                yield delta
        except BaseException:
            outcome = "error"
            raise
        finally:
            tele.done(outcome)


class PrefillReplica:
    """Prefill-role replica (actor-friendly wrapper)."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None):
        self.engine = PrefillEngine(engine_cfg or EngineConfig())

    def prefill(
        self, prompt: str, params: Optional[SamplingParams] = None
    ) -> Dict[str, Any]:
        return self.engine.prefill(prompt, params)


def _call(method, *args, timeout_s: float):
    """A replica's method, whichever the replica is: an actor handle's goes
    through ``.remote`` + ``get``, a local instance's is called."""
    if hasattr(method, "remote"):
        import ray_tpu

        return ray_tpu.get(method.remote(*args), timeout=timeout_s)
    return method(*args)


class DisaggRouter:
    """Routes new requests to prefill replicas and continuations to decode
    replicas (the reference's prefill_decode serving-pattern router): round
    robin over each pool, ``prefill`` -> ``add_from_kv`` -> ``run`` /
    ``run_stream``.  Works with actor handles or plain local instances."""

    def __init__(self, prefill_replicas: List[Any], decode_replicas: List[Any]):
        if not prefill_replicas or not decode_replicas:
            raise ValueError("need at least one prefill and one decode replica")
        self.prefill_replicas = list(prefill_replicas)
        self.decode_replicas = list(decode_replicas)
        self._p_rr = itertools.cycle(self.prefill_replicas)
        self._d_rr = itertools.cycle(self.decode_replicas)

    def _admit(self, prompt: str, params, timeout_s: float):
        """Prefill ``prompt`` on the next prefill replica and hand its KV
        pages to the next decode replica.  Returns that replica and its
        request id."""
        p, d = next(self._p_rr), next(self._d_rr)
        meta = _call(p.prefill, prompt, params, timeout_s=timeout_s)
        return d, _call(d.add_from_kv, meta, timeout_s=timeout_s)

    def generate(
        self,
        prompt: str,
        params: Optional[SamplingParams] = None,
        timeout_s: float = 300.0,
    ) -> dict:
        from ray_tpu.util import flight_recorder, tracing

        # One request-scoped span per generate: the prefill and decode
        # actor calls inside inherit the trace, so the router -> prefill
        # -> decode path exports as a single stitched cluster trace.  A
        # unary request's time to first result is its whole latency.
        t0 = time.perf_counter()
        outcome = "error"
        try:
            with tracing.start_span(
                "llm.disagg.generate", {"deployment": "llm_disagg"}
            ) as span:
                try:
                    d, rid = self._admit(prompt, params, timeout_s)
                    result = _call(d.run, rid, timeout_s, timeout_s=timeout_s)
                    span.set_attribute("ttft_s", time.perf_counter() - t0)
                except BaseException as e:
                    span.set_attribute("error", str(e))
                    raise
            outcome = "ok"
            return result
        finally:
            flight_recorder.record_serve_request(
                "llm_disagg", "router", 0.0, time.perf_counter() - t0,
                outcome=outcome,
            )

    def stream(self, prompt: str,
               params: Optional[SamplingParams] = None,
               timeout_s: float = 300.0):
        """Streaming generate through the disaggregated path: prefill + KV
        handoff, then yield the decode replica's text deltas.  Inside a
        traced caller (e.g. the serve SSE path) the admission and decode
        calls inherit the active span, so one stitched trace covers
        router -> prefill -> decode."""
        d, rid = self._admit(prompt, params, timeout_s)
        if hasattr(d.run_stream, "remote"):
            import ray_tpu

            gen = d.run_stream.options(num_returns="streaming").remote(
                rid, timeout_s)
            for ref in gen:
                yield ray_tpu.get(ref, timeout=timeout_s)
        else:
            yield from d.run_stream(rid, timeout_s)

    def generate_many(
        self,
        prompts: List[str],
        params: Optional[SamplingParams] = None,
        timeout_s: float = 300.0,
    ) -> List[dict]:
        """Pipelined fan-out over actor pools: every prompt's prefill ->
        add_from_kv -> run chain is dispatched at once, each call taking the
        one before it as a ref, so a prompt's decode starts the moment ITS
        prefill completes: no barrier, one slow prefill never delays the
        other prompts.  Local instances run one after the other."""
        if not hasattr(self.prefill_replicas[0].prefill, "remote"):
            return [self.generate(p, params, timeout_s) for p in prompts]
        import ray_tpu

        runs = []
        for prompt in prompts:
            p, d = next(self._p_rr), next(self._d_rr)
            rid = d.add_from_kv.remote(p.prefill.remote(prompt, params))
            runs.append(d.run.remote(rid, timeout_s))
        return ray_tpu.get(runs, timeout=timeout_s)
