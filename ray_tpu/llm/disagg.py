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
buffer, so a KV handoff costs exactly one D2H and one H2D.  Compute stays in exactly two XLA programs per
replica role: prefill compiles only the prefill graph, decode only the
decode-step graph — each role's chip runs one static-shape program at
100% duty instead of interleaving both.

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
from ..models.gpt2_decode import sample_logits
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
            f"{leaves}: disaggregated and continuous-batching serving hand "
            "over 'k' and 'v' pages only; serve it with JaxLLMEngine")


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
            # The prompt's token ids + last-position logits ride along so
            # the decode side can index its prefix KV cache (block-chain
            # hashes) and re-sample the first token exactly on a cache
            # hit (llm.continuous_batching.PrefixKVCache).
            "token_ids": list(token_ids),
            "logits": np.asarray(logits, np.float32),
            "k_ref": store.put(cache["k"]),
            "v_ref": store.put(cache["v"]),
        }


def _missing_method(e: BaseException, name: str) -> bool:
    """True iff a remote error is the executor's missing-method
    AttributeError for ``name`` — matched on its exact signature, NOT a
    bare substring (a real failure RAISED INSIDE the method would also
    carry the method name in its task-error message, and swallowing that
    would silently demote a batched replica to the plain path)."""
    return f"has no attribute '{name}'" in str(e)


def fetch_prefill_kv(meta: Dict[str, Any]):
    """Collect (and free) the KV pages a ``PrefillEngine`` published for
    one prompt — THE consumer side of the zero-copy handoff, shared by
    every decode role and the bench harness so the protocol has exactly
    one implementation."""
    from ..collective.device_objects import device_object_store

    store = device_object_store()
    k = store.fetch(meta["k_ref"])
    v = store.fetch(meta["v_ref"])
    store.free(meta["k_ref"])
    store.free(meta["v_ref"])
    return k, v


class DecodeReplica:
    """Decode-role replica: adopts prefilled KV, streams decode steps.

    Wraps the standard engine (whose ``add_request_from_kv`` owns the
    disaggregated admission path); the prefill program is simply never
    compiled or run on this replica — all admissions arrive as KV pages."""

    def __init__(self, engine_cfg: Optional[EngineConfig] = None):
        self.engine = JaxLLMEngine(engine_cfg or EngineConfig())

    def add_from_kv(self, meta: Dict[str, Any]) -> int:
        """Fetch the KV pages from the prefill owner and enqueue."""
        k, v = fetch_prefill_kv(meta)
        require_kv_cache(self.engine.family, self.engine.cache)
        return self.engine.add_request_from_kv(meta, {"k": k, "v": v})

    def run(self, request_id: int, timeout_s: float = 300.0) -> dict:
        """Decode until this request finishes; returns its result.

        Deploy decode replicas with ``max_concurrency`` > 1: run() loops
        step the shared engine, and concurrent add_from_kv admissions
        (arriving on other lanes) join the SAME decode batch — on an
        exclusive actor each request would decode solo, which is the
        anti-pattern disaggregation exists to avoid."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self.engine.locked(request_id):
                done = self.engine._finished.pop(request_id, None)
                if done is None:
                    self.engine.step()
                    done = self.engine._finished.pop(request_id, None)
            if done is not None:
                return done
            if time.monotonic() > deadline:
                self.engine.cancel_request(request_id)
                raise TimeoutError(f"decode of request {request_id} timed out")

    def run_stream(self, request_id: int, timeout_s: float = 300.0):
        """Stream an adopted request's text deltas as they decode (the
        disaggregated analog of ``JaxLLMEngine.generate_stream``) — this
        replica's streams are never interrupted by prefill programs, the
        inter-token-latency property the pattern exists for.

        Each stream records its TTFT and inter-token-gap histograms
        (``deployment="llm_decode"``) — the exact per-request signals
        the continuous-batching serving gate measures against."""
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


class DisaggRouter:
    """Routes new requests to prefill replicas and continuations to decode
    replicas (the reference's prefill_decode serving-pattern router).

    Works with actor handles (``.remote()``/``ray_tpu.get``) or plain
    local instances (ducks on the presence of ``.prefill.remote``).

    **Prefix-cache-aware decode routing** (on by default when the decode
    pool supports it): the router hashes the prompt into block-chain keys
    (``llm.continuous_batching.prefix_block_keys``) and routes a request
    sharing a prefix with earlier traffic to the decode replica those
    requests landed on — the replica already holding the prefix KV
    blocks.  On a full-coverage hit the decode replica admits straight
    from its prefix cache (``try_add_cached``) and the prefill hop is
    skipped entirely; router affinity decisions and engine reuse are
    accounted separately (``site="router"`` vs ``site="engine"`` on the
    ``ray_tpu_llm_prefix_cache_*`` counters)."""

    def __init__(self, prefill_replicas: List[Any], decode_replicas: List[Any],
                 prefix_routing: Optional[bool] = None,
                 prefix_block_tokens: int = 16,
                 max_affinity_entries: int = 4096,
                 imbalance_factor: float = 2.0):
        if not prefill_replicas or not decode_replicas:
            raise ValueError("need at least one prefill and one decode replica")
        self.prefill_replicas = list(prefill_replicas)
        self.decode_replicas = list(decode_replicas)
        self._p_rr = itertools.cycle(range(len(self.prefill_replicas)))
        self._d_rr = itertools.cycle(range(len(self.decode_replicas)))
        if prefix_routing is None:
            # Actor handles synthesize ANY method name, so capability is
            # probed lazily per replica on first use (_try_cached);
            # affinity routing itself is safe for plain DecodeReplicas.
            prefix_routing = True
        self.prefix_routing = prefix_routing
        # replica id() -> supports try_add_cached (None = not yet probed).
        self._cached_support: Dict[int, Optional[bool]] = {}
        self.prefix_block_tokens = prefix_block_tokens
        self.max_affinity_entries = max_affinity_entries
        import threading

        self._tokenizer = ByteTokenizer()
        # block-chain key -> decode replica index (insertion-ordered LRU).
        # Routers live inside serve replicas where concurrent executor
        # threads route at once: the map (and its eviction iterator) is
        # lock-guarded — lookups/inserts only, never a blocking call.
        self._affinity: Dict[bytes, int] = {}
        self._affinity_lock = threading.Lock()
        # Load guard (same semantics as serve.PrefixAwareRouter): a warm
        # replica whose queue is imbalance_factor deeper than the
        # lightest replica's loses the request — a shared leading block
        # must not collapse the whole pool onto one replica.  Queue
        # loads are TTL-cached so the guard costs O(n) RPCs per interval,
        # not per request.
        self.imbalance_factor = imbalance_factor
        self._loads_ttl_s = 0.1
        self._loads_cache: tuple = (0.0, None)  # (ts, loads | None)
        self.router_hits = 0
        self.router_misses = 0

    @staticmethod
    def _is_actor(h) -> bool:
        return hasattr(getattr(h, "prefill", None), "remote") or hasattr(
            getattr(h, "add_from_kv", None), "remote"
        )

    # ------------------------------------------------- prefix-aware routing
    def _select_decode(self, prompt: str):
        """Pick the decode replica for ``prompt``: deepest block-chain
        affinity match wins (the replica already holding those KV
        blocks), round-robin otherwise.  Returns (replica, affinity_hit)
        and re-homes the prompt's chain onto the choice."""
        if not self.prefix_routing:
            return self.decode_replicas[next(self._d_rr)], False
        from .continuous_batching import full_prompt_key, prefix_block_keys

        token_ids = self._tokenizer.encode(prompt)
        # Block chain + the exact-prompt key: short prompts (< one block)
        # produce no chain keys at all, and exact repeats are the single
        # most common serving pattern — the full key gives both affinity.
        keys = prefix_block_keys(token_ids, self.prefix_block_tokens)
        keys.append(full_prompt_key(token_ids, self.prefix_block_tokens))
        with self._affinity_lock:
            idx = None
            exact = False
            for j in range(len(keys) - 1, -1, -1):  # deepest first
                idx = self._affinity.get(keys[j])
                if idx is not None and idx < len(self.decode_replicas):
                    exact = j == len(keys) - 1  # the exact-prompt key
                    break
                idx = None
        if idx is not None and not exact and len(self.decode_replicas) > 1:
            # Imbalance guard (queue probes happen OUTSIDE the affinity
            # lock): a block-level match is locality ADVICE — distinct
            # prompts sharing one leading block must not collapse the
            # pool onto one replica, so an overloaded advisory target
            # loses the request.  An EXACT-prompt match is exempt: that
            # replica holds this prompt's full KV, and re-homing it
            # trades a cache hit for a prefill.
            loads = self._decode_loads()
            if loads is not None:
                warm, lightest = loads[idx], min(loads)
                if warm > self.imbalance_factor * max(lightest, 1):
                    idx = None
        hit = idx is not None
        with self._affinity_lock:
            if idx is None:
                idx = next(self._d_rr)
            if hit:
                self.router_hits += 1
            else:
                self.router_misses += 1
            for key in keys:
                self._affinity[key] = idx
            while len(self._affinity) > self.max_affinity_entries:
                self._affinity.pop(next(iter(self._affinity)))
        from ray_tpu.util import flight_recorder

        flight_recorder.record_llm_prefix_lookup("router", hit)
        return self.decode_replicas[idx], hit

    def _decode_loads(self) -> Optional[List[int]]:
        """Per-decode-replica load (queued + decoding sequences) from the
        batched replicas' stats(), TTL-cached; None when unavailable
        (plain replicas / probe failure) — the guard then stands down."""
        import ray_tpu

        ts, loads = self._loads_cache
        now = time.monotonic()
        if ts > 0 and now - ts < self._loads_ttl_s:
            return loads  # a cached None (plain pool) also holds for TTL
        try:
            if self._is_actor(self.decode_replicas[0]):
                stats = ray_tpu.get(
                    [d.stats.remote() for d in self.decode_replicas],
                    timeout=5,
                )
            else:
                stats = [d.stats() for d in self.decode_replicas]
            loads = [
                int(s["occupancy"]) + int(s["queue_depth"]) for s in stats
            ]
        except Exception:  # noqa: BLE001 — guard degrades to affinity-only
            loads = None
        self._loads_cache = (now, loads)
        return loads

    def _try_cached(self, d, prompt: str, params, timeout_s: float):
        """Prefix-cache fast path if the replica supports it.  Actor
        handles synthesize any method name, so support is learned from
        the first call: a missing-method error marks the replica plain
        (DecodeReplica) and is never retried."""
        import ray_tpu

        key = id(d)
        if self._cached_support.get(key) is False:
            return None
        if not self._is_actor(d):
            if not hasattr(d, "try_add_cached"):
                self._cached_support[key] = False
                return None
            self._cached_support[key] = True
            return d.try_add_cached(prompt, params)
        try:
            rid = ray_tpu.get(
                d.try_add_cached.remote(prompt, params), timeout=timeout_s
            )
        except Exception as e:  # noqa: BLE001 — capability probe
            # Concurrent first calls may all be probing: re-raise only
            # when support was already CONFIRMED (a real failure on a
            # batched replica), not when a sibling thread just marked
            # the replica plain.
            if self._cached_support.get(key) is not True and (
                _missing_method(e, "try_add_cached")
            ):
                self._cached_support[key] = False
                return None
            raise
        self._cached_support[key] = True
        return rid

    def _admit(self, prompt: str, params, d, timeout_s: float):
        """Admit ``prompt`` on decode replica ``d``: prefix-cache fast
        path first (no prefill hop), else prefill + zero-copy KV handoff.
        Returns the replica-local request id."""
        import ray_tpu

        rid = self._try_cached(d, prompt, params, timeout_s)
        if rid is not None:
            return rid
        p = self.prefill_replicas[next(self._p_rr)]
        if self._is_actor(d):
            meta = ray_tpu.get(
                p.prefill.remote(prompt, params), timeout=timeout_s
            )
            return ray_tpu.get(d.add_from_kv.remote(meta), timeout=timeout_s)
        return d.add_from_kv(p.prefill(prompt, params))

    def _generate_on(self, d, prompt: str, params, timeout_s: float) -> dict:
        """Full generate on decode replica ``d``.  Batched actor replicas
        take the FUSED round trips (generate_cached: cached admission +
        completion in one call; run_from_kv: KV admission + completion in
        one call) so the hot repeat-prompt path costs one RPC like a
        monolithic engine call; plain replicas keep the two-phase path."""
        import ray_tpu

        if not self._is_actor(d):
            rid = self._admit(prompt, params, d, timeout_s)
            return d.run(rid, timeout_s=timeout_s)
        key = id(d)
        support = self._cached_support.get(key)
        result = None
        if support is not False:
            try:
                result = ray_tpu.get(
                    d.generate_cached.remote(prompt, params, timeout_s),
                    timeout=timeout_s,
                )
                self._cached_support[key] = True
            except Exception as e:  # noqa: BLE001 — capability probe
                if support is not True and _missing_method(
                    e, "generate_cached"
                ):
                    self._cached_support[key] = False
                else:
                    raise
        if result is not None:
            return result
        p = self.prefill_replicas[next(self._p_rr)]
        meta = ray_tpu.get(p.prefill.remote(prompt, params), timeout=timeout_s)
        if self._cached_support.get(key):
            return ray_tpu.get(
                d.run_from_kv.remote(meta, timeout_s), timeout=timeout_s
            )
        rid = ray_tpu.get(d.add_from_kv.remote(meta), timeout=timeout_s)
        return ray_tpu.get(d.run.remote(rid), timeout=timeout_s)

    def generate(
        self,
        prompt: str,
        params: Optional[SamplingParams] = None,
        timeout_s: float = 300.0,
    ) -> dict:
        import ray_tpu
        from ray_tpu.util import flight_recorder, tracing

        d, _ = self._select_decode(prompt)
        # One request-scoped span per generate: the prefill and decode
        # actor calls inside inherit the trace, so the router -> prefill
        # -> decode path exports as a single stitched cluster trace.
        # TTFT here is prompt-in to first-token-out (the admission hop —
        # prefill, or the prefix-cache fast path), the disaggregation
        # pattern's protected latency.
        t0 = time.perf_counter()
        ttft_s = None
        outcome = "ok"
        try:
            with tracing.start_span(
                "llm.disagg.generate", {"deployment": "llm_disagg"}
            ) as span:
                try:
                    result = self._generate_on(d, prompt, params, timeout_s)
                    # Fused round trips fold admission into completion,
                    # so router-side TTFT is whole-request latency; the
                    # decode engine records the true per-request TTFT
                    # under its own deployment tag.
                    ttft_s = time.perf_counter() - t0
                    span.set_attribute("ttft_s", ttft_s)
                except BaseException as e:
                    span.set_attribute("error", str(e))
                    raise
            return result
        except BaseException:
            outcome = "error"
            raise
        finally:
            flight_recorder.record_serve_request(
                "llm_disagg", "router", 0.0,
                ttft_s if ttft_s is not None
                else time.perf_counter() - t0,
                outcome=outcome,
            )

    def stream(self, prompt: str,
               params: Optional[SamplingParams] = None,
               timeout_s: float = 300.0):
        """Streaming generate through the disaggregated path: admit (prefix
        cache or prefill+KV handoff), then yield the decode replica's text
        deltas.  Inside a traced caller (e.g. the serve SSE path) the
        admission and decode calls inherit the active span, so one
        stitched trace covers router -> prefill -> decode."""
        import ray_tpu

        d, _ = self._select_decode(prompt)
        rid = self._admit(prompt, params, d, timeout_s)
        if self._is_actor(d):
            gen = d.run_stream.options(num_returns="streaming").remote(rid)
            for ref in gen:
                yield ray_tpu.get(ref, timeout=timeout_s)
        else:
            yield from d.run_stream(rid, timeout_s=timeout_s)

    def generate_many(
        self,
        prompts: List[str],
        params: Optional[SamplingParams] = None,
        timeout_s: float = 300.0,
    ) -> List[dict]:
        """Pipelined fan-out: all prefills dispatch first (spread over the
        prefill pool), continuations spread over the decode pool."""
        import ray_tpu

        if not self._is_actor(self.prefill_replicas[0]):
            return [self.generate(p, params, timeout_s) for p in prompts]
        # Each prompt routes to its prefix-affine decode replica first; a
        # prefix-cache hit admits immediately (no prefill dispatched).
        # The misses' prefills all dispatch up-front (spread over the
        # prefill pool); each prompt's continuation pipeline
        # (add_from_kv -> run) starts the moment ITS prefill completes —
        # no barrier, so one slow prefill never delays the other prompts'
        # decode starts.
        deadline = time.time() + timeout_s
        run_refs: List[Any] = [None] * len(prompts)
        meta_refs: Dict[Any, tuple] = {}
        # Cached-admission probes dispatch as refs FIRST and resolve
        # overlapped — a blocking probe per prompt would serialize N
        # round trips ahead of the prefill fan-out and break its
        # all-dispatch-immediately property.
        probes: List[tuple] = []
        for i, prompt in enumerate(prompts):
            d, _ = self._select_decode(prompt)
            key = id(d)
            if self._cached_support.get(key) is False or not hasattr(
                type(d) if not self._is_actor(d) else d, "try_add_cached"
            ):
                probes.append((i, prompt, d, None))
            elif self._is_actor(d):
                probes.append(
                    (i, prompt, d, d.try_add_cached.remote(prompt, params))
                )
            else:
                probes.append(
                    (i, prompt, d, d.try_add_cached(prompt, params))
                )
        for i, prompt, d, probe in probes:
            rid = None
            if probe is not None:
                if self._is_actor(d):
                    try:
                        rid = ray_tpu.get(probe, timeout=timeout_s)
                        self._cached_support[id(d)] = True
                    except Exception as e:  # noqa: BLE001 — probe
                        if self._cached_support.get(id(d)) is not True and (
                            _missing_method(e, "try_add_cached")
                        ):
                            self._cached_support[id(d)] = False
                        else:
                            raise
                else:
                    rid = probe
            if rid is not None:
                run_refs[i] = d.run.remote(rid)
            else:
                ref = self.prefill_replicas[next(self._p_rr)].prefill.remote(
                    prompt, params
                )
                meta_refs[ref] = (i, d)
        pending = list(meta_refs)
        while pending:
            ready, pending = ray_tpu.wait(
                pending, num_returns=1,
                timeout=max(0.0, deadline - time.time()),
            )
            if not ready:
                raise TimeoutError("prefill fan-out timed out")
            for ref in ready:
                i, d = meta_refs[ref]
                meta = ray_tpu.get(ref, timeout=timeout_s)
                rid = ray_tpu.get(d.add_from_kv.remote(meta), timeout=timeout_s)
                run_refs[i] = d.run.remote(rid)
        return ray_tpu.get(run_refs, timeout=timeout_s)
