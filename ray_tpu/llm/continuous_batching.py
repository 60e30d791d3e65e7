"""Continuous-batching decode scheduler: one resident decode loop per
replica, admission and retirement at token boundaries.

Reference: ray ``llm/_internal/serve/serving_patterns/prefill_decode/``
decode replicas + the Orca insight (iteration-level scheduling): the
decode loop never drains to admit work — new sequences join the running
batch between decode steps, finished ones leave, and the chip stays at
full duty regardless of per-request lengths.  This is the subsystem the
``JaxLLMEngine`` slot pool approximates caller-side (every ``run()``
caller steps the shared engine under a lock); here ONE owner thread
steps, callers only enqueue and consume, so a replica's decode cadence
is independent of how many clients are connected.

TPU-native shape decisions:

  - **Padded-to-bucket batches.**  The physical KV cache is sized to the
    smallest power-of-two bucket that holds the active set, so decode
    compute scales with occupancy instead of always paying
    ``max_batch_size``.  XLA programs are compiled per bucket — decode,
    row splice, row move, and adjacent-bucket grow/shrink — which bounds
    total compiles at ``O(log2(max_batch_size))`` per program kind.
    Growth is immediate (demand present), shrink waits out
    ``shrink_patience`` consecutive low-occupancy steps so occupancy
    jitter cannot thrash reallocation.  Greedy outputs are
    token-parity-exact across bucket shapes (pinned in tests; raw logits
    are NOT bitwise-stable across batch shapes — XLA vectorizes each
    shape differently — so parity is defined at the sampled-token level).
  - **Per-slot KV over the zero-copy handoff.**  Admission splices a
    prefilled ``[L, 1, H, S, D]`` KV block into a batch row with one
    jitted ``dynamic_update_slice`` — the same block that rode the
    framing-v2 out-of-band path from a prefill replica
    (``llm.disagg``), so a disaggregated admission costs one H2D splice.
  - **Starvation guard.**  Admission is FIFO; when the queue head has
    waited past ``starvation_timeout_s`` with the bucket already at
    ``max_batch_size``, the scheduler preempts the longest-running
    eligible sequence: its KV row and generation state move to host, the
    starved request takes the slot, and the preempted sequence re-enters
    at the front of the resume queue to continue from its exact KV
    (token-exact for greedy — decode state is nothing but KV + generated
    ids).  ``max_preemptions_per_seq`` bounds churn so every sequence
    keeps forward progress.
  - **Prefix KV cache.**  Prompt KV blocks are indexed by a chained
    block hash (vLLM-style); a later prompt whose FULL token sequence is
    covered re-admits straight from the cache — no prefill replica hop,
    first token sampled from the cached last-position logits (exact).
    Partial-chain matches inform routing affinity only (suffix
    prefill-at-offset is not a compiled program on decode replicas; see
    docs/llm_serving.md).

Locking contract: ``_lock`` guards queue/slot METADATA, subscriber
queues, and counters.  Jax arrays (the cache) are touched only by the
stepping thread, device work and registry round trips happen outside the
lock, and consumers wait on per-request events/queues — never on the
engine lock.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..models import model_family
from .engine import EngineConfig, SamplingParams, encode_prompt
from .tokenizer import ByteTokenizer


@dataclasses.dataclass
class ContinuousBatchingConfig:
    """Knobs for the resident decode scheduler (docs/llm_serving.md)."""

    # Consecutive steps with occupancy <= bucket/2 before shrinking.
    shrink_patience: int = 16
    # Queue-head wait that triggers the starvation guard (only once the
    # bucket is maxed — growth always beats preemption).
    starvation_timeout_s: float = 2.0
    # A preemption victim must have generated at least this many tokens
    # (younger sequences are about to pay their admission cost back).
    preempt_min_tokens: int = 4
    # Per-sequence preemption budget: guarantees forward progress.
    max_preemptions_per_seq: int = 2
    # Prefix KV cache budget in cached prompt TOKENS (host memory).
    prefix_cache_tokens: int = 4096
    # Tokens per hash block in the prefix-cache chain.
    prefix_block_tokens: int = 16
    # Serving-telemetry deployment tag for per-request histograms.
    deployment: str = "llm_batched"


def prefix_block_keys(token_ids: List[int], block_tokens: int) -> List[bytes]:
    """Chained block digests: key_i commits to every token in blocks
    [0, i] — two prompts share key_i iff their first (i+1) blocks match.
    Routers use these for affinity; the engine cache uses the full-prompt
    key (chain tail + ragged tail tokens) for exact reuse."""
    keys: List[bytes] = []
    prev = b""
    for i in range(0, len(token_ids) - len(token_ids) % block_tokens,
                   block_tokens):
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.asarray(token_ids[i:i + block_tokens], np.int32).tobytes())
        prev = h.digest()
        keys.append(prev)
    return keys


def full_prompt_key(token_ids: List[int], block_tokens: int) -> bytes:
    chain = prefix_block_keys(token_ids, block_tokens)
    h = hashlib.blake2b(chain[-1] if chain else b"", digest_size=16)
    tail = len(token_ids) - len(token_ids) % block_tokens
    h.update(np.asarray(token_ids[tail:], np.int32).tobytes())
    h.update(len(token_ids).to_bytes(4, "little"))
    return h.digest()


class PrefixKVCache:
    """Host-side LRU of prompt KV blocks, keyed by chained block hashes.

    ``store`` keeps a trimmed ``[L, 1, H, prompt_len, D]`` host copy of a
    prompt's KV plus its last-position logits; ``lookup`` returns the
    entry only on FULL coverage of the new prompt's tokens (exact reuse —
    the first token re-samples from the cached logits, so even
    temperature>0 requests draw from the true distribution).  Evicts
    least-recently-used entries past the token budget.  Thread-safety is
    the caller's (engine lock)."""

    def __init__(self, max_tokens: int, block_tokens: int):
        self.max_tokens = max_tokens
        self.block_tokens = max(1, block_tokens)
        self._entries: "collections.OrderedDict[bytes, dict]" = (
            collections.OrderedDict()
        )
        self._block_index: Dict[bytes, bytes] = {}  # block key -> entry key
        self._tokens = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def build_entry(token_ids: List[int], k, v, logits,
                    block_tokens: int) -> dict:
        """Host copies for one prompt's KV (call OUTSIDE the engine lock —
        the copies are the expensive part)."""
        n = len(token_ids)
        return {
            "key": full_prompt_key(token_ids, block_tokens),
            "token_ids": list(token_ids),
            # Trim to the prompt span: the tail of the row is zeros.
            "k": np.ascontiguousarray(np.asarray(k)[:, :, :, :n]),
            "v": np.ascontiguousarray(np.asarray(v)[:, :, :, :n]),
            "logits": np.asarray(logits, np.float32).reshape(-1),
            "blocks": prefix_block_keys(token_ids, block_tokens),
        }

    def insert(self, entry: dict) -> None:
        if self.max_tokens <= 0 or not entry["token_ids"]:
            return
        key = entry["key"]
        if key in self._entries:
            self._entries.move_to_end(key)
            return
        self._entries[key] = entry
        for bk in entry["blocks"]:
            self._block_index[bk] = key
        self._tokens += len(entry["token_ids"])
        while self._tokens > self.max_tokens and len(self._entries) > 1:
            _, old = self._entries.popitem(last=False)
            self._tokens -= len(old["token_ids"])
            for bk in old["blocks"]:
                if self._block_index.get(bk) == old["key"]:
                    del self._block_index[bk]

    def contains(self, key: bytes) -> bool:
        """Key-presence check without LRU touch or hit/miss accounting
        (dedupe probe on the store path)."""
        return key in self._entries

    def lookup(self, token_ids: List[int]) -> Optional[dict]:
        key = full_prompt_key(token_ids, self.block_tokens)
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            return entry
        self.misses += 1
        return None

    def match_depth(self, token_ids: List[int]) -> int:
        """Longest cached block-chain prefix, in blocks (routing signal)."""
        depth = 0
        for bk in prefix_block_keys(token_ids, self.block_tokens):
            if bk not in self._block_index:
                break
            depth += 1
        return depth

    def stats(self) -> Dict[str, int]:
        return {
            "entries": len(self._entries),
            "tokens": self._tokens,
            "hits": self.hits,
            "misses": self.misses,
        }


@dataclasses.dataclass
class _Seq:
    rid: int
    prompt_len: int
    generated: List[int]
    params: SamplingParams
    enq_t: float
    admit_t: float = 0.0
    first_t: float = 0.0
    last_t: float = 0.0
    gaps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    preemptions: int = 0

    @property
    def last_pos(self) -> int:
        return self.prompt_len + len(self.generated) - 1


def _buckets(max_batch: int) -> List[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


class ContinuousBatchingEngine:
    """Decode-role engine with a resident batched decode loop.

    Callers enqueue (``submit_kv`` / ``submit_cached``) and consume
    (``stream`` / ``result``); the owner thread (started by ``start()``)
    runs ``step()`` — retire, starvation guard, admit, one decode — at
    every token boundary."""

    def __init__(self, cfg: Optional[EngineConfig] = None,
                 cb: Optional[ContinuousBatchingConfig] = None,
                 tokenizer=None):
        import jax

        from ray_tpu.util.debug_locks import make_condition

        self.cfg = cfg or EngineConfig()
        self.cb = cb or ContinuousBatchingConfig()
        self.tokenizer = tokenizer or ByteTokenizer()
        mcfg = self.cfg.model
        fam = model_family(mcfg)
        self.family = fam
        if self.cfg.param_loader is not None:
            self.params = self.cfg.param_loader()
        else:
            self.params = fam.init(jax.random.PRNGKey(self.cfg.seed), mcfg)
        self._key = jax.random.PRNGKey(self.cfg.seed + 1)
        self._buckets = _buckets(self.cfg.max_batch_size)
        self.bucket = self._buckets[0]
        self.cache = fam.init_cache(mcfg, self.bucket, self.cfg.max_seq_len)
        from .disagg import require_kv_cache

        require_kv_cache(fam, self.cache)  # rows move as k / v pages below
        self.slots: List[Optional[_Seq]] = [None] * self.bucket

        # Compiled-program caches, all keyed by bucket (bounded at
        # O(log2 max_batch) compiles per kind — the recompile contract).
        self._decode_fns: Dict[int, Any] = {}
        self._insert_fns: Dict[int, Any] = {}
        self._move_fns: Dict[int, Any] = {}
        self._resize_fns: Dict[Tuple[int, int], Any] = {}
        from ..models.gpt2_decode import sample_logits

        self._sample = jax.jit(
            sample_logits, static_argnames=("temperature", "top_k", "top_p")
        )

        self._cond = make_condition("llm.cb.scheduler")
        self._lock = self._cond  # the condition IS the engine lock
        self._next_id = itertools.count()
        # Pending admissions: (rid, meta, k_host, v_host).  Preempted
        # sequences go on _resume (drained before _waiting — they already
        # waited once), except that a starvation-guard preemption hands
        # its freed slot to the starved _waiting head first.
        self._waiting: "collections.deque" = collections.deque()
        self._resume: "collections.deque" = collections.deque()
        self._admit_waiting_first = False
        self._finished: Dict[int, dict] = {}
        self._subs: Dict[int, _queue.SimpleQueue] = {}
        self._events: Dict[int, threading.Event] = {}
        self.prefix_cache = PrefixKVCache(
            self.cb.prefix_cache_tokens, self.cb.prefix_block_tokens
        )
        self._starved_since: Optional[float] = None
        self._low_occupancy_steps = 0
        # Cumulative accounting (stats() + flight-recorder deltas).
        self.counters = {
            "admitted": 0, "retired": 0, "preempted": 0, "steps": 0,
            "max_occupancy": 0,
        }
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._fail_count = 0
        self._dead = False

    # ------------------------------------------------------------ programs
    def _decode_fn(self, b: int):
        fn = self._decode_fns.get(b)
        if fn is None:
            import jax

            fam, mcfg = self.family, self.cfg.model
            fn = jax.jit(
                lambda params, cache, tokens, pos: fam.decode_step(
                    params, tokens, pos, cache, mcfg
                ),
                donate_argnums=(1,),
            )
            self._decode_fns[b] = fn
        return fn

    def _insert_fn(self, b: int):
        fn = self._insert_fns.get(b)
        if fn is None:
            import jax

            def insert(cache, k1, v1, idx):
                return {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k1, (0, idx, 0, 0, 0)
                    ),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v1, (0, idx, 0, 0, 0)
                    ),
                }

            fn = jax.jit(insert, donate_argnums=(0,))
            self._insert_fns[b] = fn
        return fn

    def _move_fn(self, b: int):
        fn = self._move_fns.get(b)
        if fn is None:
            import jax

            def move(cache, src, dst):
                # Row shape from the traced operand ([L, b, H, S, D] —
                # static at trace time), NOT from engine state: this fn is
                # keyed by bucket and may be compiled ahead of use.
                shape = cache["k"].shape
                row = (shape[0], 1) + tuple(shape[2:])
                k1 = jax.lax.dynamic_slice(cache["k"], (0, src, 0, 0, 0), row)
                v1 = jax.lax.dynamic_slice(cache["v"], (0, src, 0, 0, 0), row)
                return {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k1, (0, dst, 0, 0, 0)
                    ),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v1, (0, dst, 0, 0, 0)
                    ),
                }

            fn = jax.jit(move, donate_argnums=(0,))
            self._move_fns[b] = fn
        return fn

    def _resize_fn(self, old: int, new: int):
        fn = self._resize_fns.get((old, new))
        if fn is None:
            import jax
            import jax.numpy as jnp

            fam, mcfg = self.family, self.cfg.model
            max_len = self.cfg.max_seq_len

            if new > old:
                def resize(cache):
                    fresh = fam.init_cache(mcfg, new, max_len)
                    return {
                        "k": jax.lax.dynamic_update_slice(
                            fresh["k"], cache["k"], (0, 0, 0, 0, 0)
                        ),
                        "v": jax.lax.dynamic_update_slice(
                            fresh["v"], cache["v"], (0, 0, 0, 0, 0)
                        ),
                    }
            else:
                def resize(cache):
                    return {
                        "k": jnp.asarray(cache["k"][:, :new]),
                        "v": jnp.asarray(cache["v"][:, :new]),
                    }

            # No donation: the output shape differs from the input's, so
            # XLA cannot reuse the buffer (donating only warns).
            fn = jax.jit(resize)
            self._resize_fns[(old, new)] = fn
        return fn

    def compile_buckets(self) -> None:
        """Compile every bucket's programs up front (insert, decode, row
        move, adjacent grow/shrink) against throwaway caches, so no jit
        compile can land inside serving and masquerade as a multi-second
        inter-token stall.  Touches only the compiled-fn caches — safe
        to call while the loop runs (worst case both threads compile the
        same key once)."""
        import jax.numpy as jnp

        fam, mcfg = self.family, self.cfg.model
        max_len = self.cfg.max_seq_len
        row = None
        for i, b in enumerate(self._buckets):
            cache = fam.init_cache(mcfg, b, max_len)
            if row is None:
                one = fam.init_cache(mcfg, 1, max_len)
                row = (one["k"], one["v"])
            cache = self._insert_fn(b)(cache, row[0], row[1], 0)
            zeros = jnp.zeros(b, jnp.int32)
            _, cache = self._decode_fn(b)(self.params, cache, zeros, zeros)
            self._move_fn(b)(cache, 0, 0)
            if i + 1 < len(self._buckets):
                nb = self._buckets[i + 1]
                grown = self._resize_fn(b, nb)(
                    fam.init_cache(mcfg, b, max_len)
                )
                self._resize_fn(nb, b)(grown)

    # ----------------------------------------------------------- admission
    def submit_kv(self, meta: Dict[str, Any], k, v) -> int:
        """Enqueue a prefilled request (disaggregated admission).  ``meta``
        carries prompt_len / first_token / sampling / logits / token_ids
        (see llm.disagg.PrefillEngine.prefill); ``k``/``v`` are the
        [L, 1, H, S, D] prompt KV pages (device or host).  Also feeds the
        prefix cache so future identical prompts skip prefill."""
        if self._dead:
            raise RuntimeError("decode engine failed; replica is dead")
        kh = np.asarray(k)
        vh = np.asarray(v)
        token_ids = meta.get("token_ids")
        entry = None
        if token_ids and meta.get("logits") is not None:
            # Cheap key check before the expensive host copies: a repeat
            # prompt arriving via the prefill path (affinity re-home,
            # evicted router entry) is already cached and build_entry's
            # full-KV copies would be discarded by insert()'s dedupe.
            key = full_prompt_key(token_ids, self.cb.prefix_block_tokens)
            with self._lock:
                known = self.prefix_cache.contains(key)
            if not known:
                entry = PrefixKVCache.build_entry(
                    token_ids, kh, vh, meta["logits"],
                    self.cb.prefix_block_tokens,
                )
        with self._lock:
            rid = next(self._next_id)
            if entry is not None:
                self.prefix_cache.insert(entry)
            self._enqueue_locked(rid, dict(meta), kh, vh)
            return rid

    def submit_cached(self, prompt: str,
                      params: Optional[SamplingParams] = None
                      ) -> Optional[int]:
        """Prefix-cache admission: if the prompt's full token sequence is
        cached, enqueue straight from the cached KV (no prefill anywhere)
        and return a rid; else None (caller falls back to a prefill
        replica — and the miss is accounted)."""
        if self._dead:
            raise RuntimeError("decode engine failed; replica is dead")
        params = params or SamplingParams()
        token_ids = encode_prompt(
            self.tokenizer, prompt, self.cfg.max_seq_len
        )
        from ray_tpu.util import flight_recorder

        with self._lock:
            cached = self.prefix_cache.lookup(token_ids)
            if cached is not None:
                logits = cached["logits"]
                kc, vc = cached["k"], cached["v"]
        flight_recorder.record_llm_prefix_lookup("engine", cached is not None)
        if cached is None:
            return None
        # Row assembly outside the lock.  The first token is NOT sampled
        # here: sampling may split the engine PRNG key, which belongs to
        # the stepping thread alone (a caller-thread split would race
        # _decode_once and hand two requests the same subkey) — the
        # admission path samples from the cached logits at the token
        # boundary instead (meta carries them).
        n = len(token_ids)
        shape = list(kc.shape)
        shape[3] = self.cfg.max_seq_len
        k = np.zeros(shape, kc.dtype)
        v = np.zeros(shape, vc.dtype)
        k[:, :, :, :n] = kc
        v[:, :, :, :n] = vc
        meta = {
            "prompt_len": n,
            "first_logits": logits,
            "sampling": params,
            "token_ids": token_ids,
        }
        with self._lock:
            rid = next(self._next_id)
            self._enqueue_locked(rid, meta, k, v)
            return rid

    def _enqueue_locked(self, rid: int, meta: dict, k, v) -> None:
        meta.setdefault("enq_t", time.monotonic())
        self._waiting.append((rid, meta, k, v))
        self._subs.setdefault(rid, _queue.SimpleQueue())
        self._events.setdefault(rid, threading.Event())
        self._cond.notify_all()

    def prefix_match_depth(self, prompt: str) -> int:
        token_ids = encode_prompt(self.tokenizer, prompt, self.cfg.max_seq_len)
        with self._lock:
            return self.prefix_cache.match_depth(token_ids)

    def _sample_host(self, logits: np.ndarray, params: SamplingParams):
        """Sample next token(s) from host logits.  Greedy is a pure
        argmax (no PRNG consumed — batch composition can't perturb the
        key stream, the parity contract); stochastic params go through
        the jitted sampler with a fresh subkey.  Called only from the
        stepping thread (the PRNG key is unguarded by design)."""
        if params.temperature == 0.0:
            return np.argmax(logits, axis=-1)
        import jax

        self._key, sub = jax.random.split(self._key)
        return np.asarray(
            self._sample(
                logits, sub, temperature=params.temperature,
                top_k=params.top_k, top_p=params.top_p,
            )
        )

    # ----------------------------------------------------- lifecycle/loop
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="llm-cb-decode", daemon=True
        )
        self._thread.start()

    def stop(self, timeout_s: float = 10.0) -> None:
        self._stop.set()
        with self._lock:
            self._cond.notify_all()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout_s)

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                has_work = (
                    self._waiting or self._resume
                    or any(s is not None for s in self.slots)
                )
                if not has_work:
                    # Bounded idle wait (RTL006); woken by submissions.
                    self._cond.wait(timeout=0.05)
                    continue
            try:
                self.step()
            except Exception:  # noqa: BLE001 — fail every waiter, loudly
                import logging
                import traceback

                logging.getLogger(__name__).error(
                    "continuous-batching step failed:\n%s",
                    traceback.format_exc(),
                )
                self._fail_all()

    def _fail_all(self) -> None:
        with self._lock:
            seqs = [s for s in self.slots if s is not None]
            pend = list(self._resume) + list(self._waiting)
            self._resume.clear()
            self._waiting.clear()
            for i in range(len(self.slots)):
                self.slots[i] = None
            for s in seqs:
                self._finish_locked(s, error="decode loop failed")
            for rid, _meta, _k, _v in pend:
                self._finish_rid_locked(rid, error="decode loop failed")
            retired = seqs
        for s in retired:
            self._record_request(s, outcome="error")
        # Recover device state: a failure inside a DONATING jitted call
        # (decode/insert/move) may have invalidated self.cache even
        # though the assignment never landed — without reinit every
        # later step fails against the dead buffer and the replica
        # black-holes requests forever.  Repeated failures mark the
        # engine dead instead (crash-loop: surface, don't mask).
        self._fail_count += 1
        if self._fail_count >= 3:
            self._dead = True
            self._stop.set()
            return
        try:
            fresh = self.family.init_cache(
                self.cfg.model, self._buckets[0], self.cfg.max_seq_len
            )
            with self._lock:
                self.bucket = self._buckets[0]
                self.slots = [None] * self.bucket
                self._low_occupancy_steps = 0
            self.cache = fresh
        except Exception:  # noqa: BLE001 — can't recover: go dead
            self._dead = True
            self._stop.set()

    @property
    def healthy(self) -> bool:
        return not self._dead

    # ----------------------------------------------------------- stepping
    def step(self) -> None:
        """One token boundary + one decode step for the active set."""
        admitted, retired = self._token_boundary()
        active = self._decode_once()
        with self._lock:
            occupancy = sum(1 for s in self.slots if s is not None)
            queue_depth = len(self._waiting) + len(self._resume)
            self.counters["steps"] += 1
            self.counters["max_occupancy"] = max(
                self.counters["max_occupancy"], active
            )
            if active and active * 2 <= self.bucket:
                self._low_occupancy_steps += 1
            else:
                self._low_occupancy_steps = 0
        from ray_tpu.util import flight_recorder

        flight_recorder.record_llm_step(
            occupancy=occupancy, queue_depth=queue_depth,
            admitted=admitted, retired=retired, bucket=self.bucket,
        )
        self._maybe_shrink()

    def _token_boundary(self) -> Tuple[int, int]:
        """Retire finished, run the starvation guard, admit waiters.
        Returns (admissions, retirements)."""
        retired = self._retire()
        self._starvation_guard()
        return self._admit(), retired

    def _retire(self) -> int:
        with self._lock:
            done = [
                (i, s) for i, s in enumerate(self.slots)
                if s is not None and (s.done or s.cancelled)
            ]
            for i, s in done:
                self.slots[i] = None
                if not s.cancelled:
                    self._finish_locked(s)
                    self.counters["retired"] += 1
                else:
                    self._finish_rid_locked(s.rid, cancelled=True)
        # Histograms outside the engine lock (registry has its own).
        retired = 0
        for _, s in done:
            if not s.cancelled:
                retired += 1
                self._record_request(s, outcome="ok")
        return retired

    def _finish_locked(self, s: _Seq, error: Optional[str] = None) -> None:
        if s.rid not in self._subs and s.rid not in self._events:
            return  # consumer already released; storing would leak
        gen = s.generated
        stop = (
            s.params.stop_token if s.params.stop_token is not None
            else getattr(self.tokenizer, "EOS", None)
        )
        if stop is not None and gen and gen[-1] == stop:
            gen = gen[:-1]
        result = {
            "request_id": s.rid,
            "token_ids": gen,
            "text": self.tokenizer.decode(gen),
            "num_generated": len(s.generated),
        }
        if error:
            result["error"] = error
        self._finished[s.rid] = result
        q = self._subs.get(s.rid)
        if q is not None:
            q.put(None)  # stream sentinel
        ev = self._events.get(s.rid)
        if ev is not None:
            ev.set()

    def _finish_rid_locked(self, rid: int, error: Optional[str] = None,
                           cancelled: bool = False) -> None:
        if cancelled and rid not in self._subs and rid not in self._events:
            return  # consumer already released; storing would leak
        result = {"request_id": rid, "token_ids": [], "text": "",
                  "num_generated": 0}
        if error:
            result["error"] = error
        if cancelled:
            result["cancelled"] = True
        self._finished[rid] = result
        q = self._subs.get(rid)
        if q is not None:
            q.put(None)
        ev = self._events.get(rid)
        if ev is not None:
            ev.set()

    def _record_request(self, s: _Seq, outcome: str) -> None:
        """Per-request serving telemetry (PR-10 histograms): queue wait =
        enqueue→admission, TTFT = enqueue→first token, plus every
        inter-token gap — recorded engine-side so thousands of queued
        clients need no consumer thread each to be measured."""
        from ray_tpu.util import flight_recorder

        try:
            flight_recorder.record_serve_stream(
                self.cb.deployment, "engine",
                max(0.0, s.admit_t - s.enq_t),
                max(0.0, (s.first_t or s.admit_t) - s.enq_t),
                s.gaps, outcome=outcome,
            )
        except Exception:  # raylint: waive[RTL003] telemetry must not fail retirement
            pass

    def _starvation_guard(self) -> None:
        with self._lock:
            if not self._waiting and not self._resume:
                self._starved_since = None
                return
            free = any(s is None for s in self.slots)
            if free or self.bucket < self.cfg.max_batch_size:
                self._starved_since = None
                return
            now = time.monotonic()
            if self._starved_since is None:
                self._starved_since = now
                return
            if now - self._starved_since < self.cb.starvation_timeout_s:
                return
            victims = [
                (len(s.generated), i, s)
                for i, s in enumerate(self.slots)
                if s is not None and not s.done and not s.cancelled
                and len(s.generated) >= self.cb.preempt_min_tokens
                and s.preemptions < self.cb.max_preemptions_per_seq
            ]
            if not victims:
                self._starved_since = now  # re-arm; nothing eligible yet
                return
            _, idx, victim = max(victims, key=lambda t: (t[0], -t[1]))
            self.slots[idx] = None
            self._starved_since = None
            victim.preemptions += 1
            self.counters["preempted"] += 1
        # KV extraction outside the lock: one D2H of the victim's row.
        kh = np.asarray(self.cache["k"][:, idx:idx + 1])
        vh = np.asarray(self.cache["v"][:, idx:idx + 1])
        meta = {
            "prompt_len": victim.prompt_len,
            "sampling": victim.params,
            "resume_seq": victim,
        }
        with self._lock:
            self._resume.appendleft((victim.rid, meta, kh, vh))
            # The freed slot belongs to the starved head, not the victim.
            self._admit_waiting_first = True
        from ray_tpu.util import flight_recorder

        flight_recorder.record_llm_preemption()

    def _admit(self) -> int:
        """Drain pending admissions into free slots, growing the bucket
        (adjacent steps) while demand remains.  Splices happen outside
        the lock; slot metadata commits under it."""
        admitted = 0
        while True:
            with self._lock:
                pending = len(self._waiting) + len(self._resume)
                if pending == 0:
                    return admitted
                idx = next(
                    (i for i, s in enumerate(self.slots) if s is None), None
                )
                if idx is None and self.bucket >= self.cfg.max_batch_size:
                    return admitted
                entry = None
                if idx is not None:
                    if self._admit_waiting_first and self._waiting:
                        source = self._waiting
                    else:
                        source = self._resume if self._resume else self._waiting
                    self._admit_waiting_first = False
                    entry = source.popleft()
                    rid = entry[0]
                    if rid in self._finished:  # cancelled while queued
                        continue
            if entry is None:
                self._grow()
                continue
            rid, meta, kh, vh = entry
            import jax.numpy as jnp

            self.cache = self._insert_fn(self.bucket)(
                self.cache, jnp.asarray(kh), jnp.asarray(vh), idx
            )
            first = meta.get("first_token")
            if first is None and meta.get("resume_seq") is None:
                # Prefix-cache admission: the first token is sampled HERE
                # (stepping thread — the only legal owner of the PRNG
                # key) from the cached last-position logits.
                first = int(
                    self._sample_host(
                        np.asarray(meta["first_logits"])[None],
                        meta["sampling"],
                    )[0]
                )
            now = time.monotonic()
            with self._lock:
                if rid in self._finished or (
                    rid not in self._subs and rid not in self._events
                ):
                    # Cancelled/released while we were splicing (the
                    # unlocked window can be long on a cold bucket):
                    # don't commit the slot — the spliced row is garbage
                    # in a FREE slot, overwritten by the next admission.
                    continue
                seq = meta.get("resume_seq")
                if seq is None:
                    seq = _Seq(
                        rid=rid,
                        prompt_len=meta["prompt_len"],
                        generated=[first],
                        params=meta["sampling"],
                        enq_t=meta.get("enq_t", now),
                        admit_t=now,
                        first_t=now,
                        last_t=now,
                    )
                    self.counters["admitted"] += 1
                    self._push_delta_locked(seq, [first])
                    self._check_done_locked(seq)
                self.slots[idx] = seq
                admitted += 1

    def _grow(self) -> None:
        new = self._buckets[self._buckets.index(self.bucket) + 1]
        self.cache = self._resize_fn(self.bucket, new)(self.cache)
        with self._lock:
            self.slots.extend([None] * (new - self.bucket))
            self.bucket = new

    def _maybe_shrink(self) -> None:
        with self._lock:
            if self.bucket == self._buckets[0]:
                return
            if self._low_occupancy_steps < self.cb.shrink_patience:
                return
            old = self.bucket
            new = self._buckets[self._buckets.index(old) - 1]
            # Plan compaction: every OCCUPIED slot >= new moves to a free
            # low slot.  The low-occupancy trigger counts decoding
            # sequences, but slots can also hold cancelled-not-yet-
            # retired sequences — if the free low slots don't cover the
            # high occupants, skip this round instead of crashing the
            # loop (the next boundary retires the cancelled ones).
            moves = []
            free_low = [i for i in range(new) if self.slots[i] is None]
            for i in range(new, old):
                if self.slots[i] is not None:
                    if not free_low:
                        self._low_occupancy_steps = 0
                        return
                    moves.append((i, free_low.pop(0)))
        for src, dst in moves:
            self.cache = self._move_fn(old)(self.cache, src, dst)
        with self._lock:
            for src, dst in moves:
                self.slots[dst] = self.slots[src]
                self.slots[src] = None
        self.cache = self._resize_fn(old, new)(self.cache)
        with self._lock:
            self.slots = self.slots[:new]
            self.bucket = new
            self._low_occupancy_steps = 0

    def _decode_once(self) -> int:
        import jax.numpy as jnp

        with self._lock:
            active = [
                (i, s) for i, s in enumerate(self.slots)
                if s is not None and not s.done and not s.cancelled
            ]
            if not active:
                return 0
            tokens = np.zeros(self.bucket, np.int32)
            pos = np.zeros(self.bucket, np.int32)
            for i, s in active:
                tokens[i] = s.generated[-1]
                pos[i] = s.last_pos
        logits, self.cache = self._decode_fn(self.bucket)(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos)
        )
        logits_np = np.asarray(logits)
        # Sampling outside the lock (may hit the jitted sampler).
        sampled = [
            (i, s, int(self._sample_host(logits_np[i:i + 1], s.params)[0]))
            for i, s in active
        ]
        now = time.monotonic()
        with self._lock:
            for i, s, token in sampled:
                if self.slots[i] is not s:  # retired/preempted mid-decode
                    continue
                s.generated.append(token)
                s.gaps.append(now - s.last_t)
                s.last_t = now
                self._push_delta_locked(s, [token])
                self._check_done_locked(s)
        return len(active)

    def _push_delta_locked(self, s: _Seq, token_ids: List[int]) -> None:
        q = self._subs.get(s.rid)
        if q is not None:
            q.put(list(token_ids))

    def _check_done_locked(self, s: _Seq) -> None:
        stop = (
            s.params.stop_token if s.params.stop_token is not None
            else getattr(self.tokenizer, "EOS", None)
        )
        token = s.generated[-1]
        total_len = s.prompt_len + len(s.generated)
        if (
            (stop is not None and token == stop)
            or len(s.generated) >= s.params.max_tokens
            or total_len >= self.cfg.max_seq_len - 1
        ):
            s.done = True

    # --------------------------------------------------------- consumption
    def result(self, rid: int, timeout_s: float = 300.0) -> dict:
        ev = self._events.get(rid)
        if ev is None:
            with self._lock:
                done = self._finished.pop(rid, None)
            if done is not None:
                return done
            raise KeyError(f"unknown request {rid}")
        if not ev.wait(timeout=timeout_s):
            self.cancel(rid)
            with self._lock:  # drop delivery state; nobody will consume
                self._subs.pop(rid, None)
                self._events.pop(rid, None)
                self._finished.pop(rid, None)
            raise TimeoutError(f"request {rid} timed out")
        with self._lock:
            done = self._finished.pop(rid)
            self._events.pop(rid, None)
            self._subs.pop(rid, None)
        if done.get("error"):
            raise RuntimeError(done["error"])
        return done

    def stream(self, rid: int, timeout_s: float = 300.0):
        """Yield text deltas for ``rid`` as tokens land (token-boundary
        granularity).  The consumer never steps the engine."""
        q = self._subs.get(rid)
        if q is None:
            raise KeyError(f"unknown request {rid}")
        deadline = time.monotonic() + timeout_s
        emitted = 0
        try:
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"stream of request {rid} timed out")
                try:
                    item = q.get(timeout=min(remaining, 1.0))
                except _queue.Empty:
                    continue
                if item is None:
                    with self._lock:
                        done = self._finished.get(rid, {})
                    if done.get("error"):
                        raise RuntimeError(done["error"])
                    # Flush the tail: stop-token trimming can shorten the
                    # final text vs streamed ids; emit whatever decode of
                    # the final ids adds beyond what we already sent.
                    tail = self.tokenizer.decode(
                        done.get("token_ids", [])[emitted:]
                    )
                    if tail:
                        yield tail
                    return
                emitted += len(item)
                text = self.tokenizer.decode(item)
                if text:
                    yield text
        finally:
            self._release(rid)

    def _release(self, rid: int) -> None:
        finished = False
        with self._lock:
            finished = rid in self._finished
            self._finished.pop(rid, None)
            self._subs.pop(rid, None)
            self._events.pop(rid, None)
        if not finished:
            self.cancel(rid)

    def cancel(self, rid: int) -> None:
        with self._lock:
            self._waiting = collections.deque(
                w for w in self._waiting if w[0] != rid
            )
            self._resume = collections.deque(
                w for w in self._resume if w[0] != rid
            )
            for s in self.slots:
                if s is not None and s.rid == rid:
                    s.cancelled = True  # loop frees the slot at boundary
                    return
            if rid not in self._finished:
                self._finish_rid_locked(rid, cancelled=True)

    # -------------------------------------------------------------- stats
    def has_unfinished(self) -> bool:
        with self._lock:
            return bool(self._waiting) or bool(self._resume) or any(
                s is not None for s in self.slots
            )

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            occupancy = sum(1 for s in self.slots if s is not None)
            return {
                "bucket": self.bucket,
                "occupancy": occupancy,
                "queue_depth": len(self._waiting) + len(self._resume),
                "prefix_cache": self.prefix_cache.stats(),
                **dict(self.counters),
            }


class BatchedDecodeReplica:
    """Actor-friendly decode replica over the resident scheduler — the
    continuous-batching successor of ``llm.disagg.DecodeReplica``.

    Deploy with ``max_concurrency`` > 1: ``add_from_kv``/``run``/
    ``run_stream`` calls only enqueue and wait; the owner thread decodes.
    """

    def __init__(self, engine_cfg: Optional[EngineConfig] = None,
                 cb_cfg: Optional[ContinuousBatchingConfig] = None,
                 warm: bool = False):
        self.engine = ContinuousBatchingEngine(
            engine_cfg or EngineConfig(), cb_cfg
        )
        if warm:
            self.engine.compile_buckets()
        self.engine.start()

    def warm(self) -> bool:
        """Pre-compile every bucket's programs (serving deployments call
        this once so no jit compile lands inside a live request)."""
        self.engine.compile_buckets()
        return True

    def add_from_kv(self, meta: Dict[str, Any]) -> int:
        """Fetch the KV pages from the prefill owner and enqueue (token-
        boundary admission into the running batch)."""
        from .disagg import fetch_prefill_kv

        k, v = fetch_prefill_kv(meta)
        return self.engine.submit_kv(meta, k, v)

    def try_add_cached(self, prompt: str,
                       params: Optional[SamplingParams] = None
                       ) -> Optional[int]:
        return self.engine.submit_cached(prompt, params)

    def generate_cached(self, prompt: str,
                        params: Optional[SamplingParams] = None,
                        timeout_s: float = 300.0) -> Optional[dict]:
        """Fused prefix-cache fast path: admission + completion in ONE
        actor round trip (None on a cache miss) — the hot repeat-prompt
        path costs the same RPC count as a monolithic engine call."""
        rid = self.engine.submit_cached(prompt, params)
        if rid is None:
            return None
        return self.engine.result(rid, timeout_s)

    def run_from_kv(self, meta: Dict[str, Any],
                    timeout_s: float = 300.0) -> dict:
        """Fused disaggregated admission + completion (one round trip
        instead of add_from_kv + run)."""
        from .disagg import fetch_prefill_kv

        k, v = fetch_prefill_kv(meta)
        rid = self.engine.submit_kv(meta, k, v)
        return self.engine.result(rid, timeout_s)

    def prefix_match_depth(self, prompt: str) -> int:
        return self.engine.prefix_match_depth(prompt)

    def run(self, request_id: int, timeout_s: float = 300.0) -> dict:
        return self.engine.result(request_id, timeout_s)

    def run_stream(self, request_id: int, timeout_s: float = 300.0):
        """Stream text deltas (engine records per-request TTFT/inter-token
        histograms at retirement — no double accounting here)."""
        yield from self.engine.stream(request_id, timeout_s)

    def cancel(self, request_id: int) -> None:
        self.engine.cancel(request_id)

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def health_check(self) -> bool:
        if not self.engine.healthy:
            raise RuntimeError(
                "continuous-batching engine failed repeatedly; replica "
                "needs replacement"
            )
        return True

    def close(self) -> None:
        self.engine.stop()
