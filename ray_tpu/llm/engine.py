"""JAX LLM engine: slot-based continuous batching over a KV cache.

Role-equivalent of the reference's vLLM engine wrapper (ray
``python/ray/llm/_internal/serve/engines/vllm/``) — but the engine IS the
TPU program: a fixed pool of batch slots shares one jitted decode step, so
requests join and leave the batch at token granularity (continuous
batching) and the chip never waits for the longest request in a batch.

Model-agnostic: any config type with a registered ``ModelFamily``
(``ray_tpu.models.model_family`` — GPT-2, Llama and LongCat ship in-tree,
mirroring the reference's vLLM model registry) plugs in; the engine only
speaks init/init_cache/prefill/decode_step.  The cache is the family's: a
pytree of which the engine knows one thing, that every leaf's slot axis is
axis 1 (``splice_row``); dense keys and values, or one latent leaf, are the
same to it.  A family whose steps also return counts (``*_counted``: the
routing counts of an expert layer) has them returned by the same two
programs, read ONE STEP LATE (so a step still waits for the device once),
summed in ``stats()`` and written on ``engine.counts``.

Shapes are static.  The cache is max_batch_size × max_seq_len, and so is
the decode step.  Prefill runs at the PROMPT's length, not the cache's: one
request a call, padded to the smallest rung of ``prefill_ladder(max_seq_len)``
(256, 512, 1024, … and ``max_seq_len`` itself) that holds it, and the row it
writes covers positions ``[0, rung)`` of the slot.  Every rung is one
compilation of the same ``prefill_one``, made when the engine is BUILT
(ahead of time, in threads, while the weights load), so no prompt length
meets a compiler later; an engine of ``max_seq_len <= 256`` has one rung.
The decode step and two samplers over its ``[max_batch_size, V]`` logits
compile at the first request: ``sample_logits_greedy`` when every active
slot has temperature 0, ``sample_logits_rows`` otherwise (both also at
``[1, V]``, for a prefill's first token).  Sampling parameters reach the
sampler as per-row ARRAYS, so a new ``SamplingParams`` value compiles
nothing; a step reads its tokens from the device ONCE, whatever the number
of slots.  At ``temperature > 0`` the draws for a given ``seed`` differ from
versions that split one key per slot on the host: the key is now split once
a step, inside the program.

Program names are a contract too: the benchmark's readers find the decode
program as the only ``jit__lambda`` and the samplers by ``jit_sample_logits``,
so whatever is jitted here beside the decode step is a NAMED function.

Observability (names are a contract: tests pin them, PERF.md lists which
metric reads which).  Host work runs inside ``util.tracing.host_span``s —
``engine.lock_wait``, ``engine.step`` > ``engine.admit`` >
(``engine.prefill.dispatch``, ``engine.sample``), ``engine.decode.dispatch``,
``engine.sample``, ``engine.retire``, and a zero-length ``engine.counts`` at
the end of every step — which a profiler session writes on the device
trace's clock.  ``stats()`` gives the same counts with no session, and every
step feeds ``flight_recorder.record_llm_step`` (``/metrics``).
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..models import GPT2Config, model_family
from ..models.gpt2_decode import sample_logits_greedy, sample_logits_rows
from ..util import flight_recorder, tracing
from ..util.tracing import host_span
from .tokenizer import ByteTokenizer


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0
    top_p: float = 1.0
    stop_token: Optional[int] = None  # default: tokenizer EOS


@dataclasses.dataclass
class EngineConfig:
    # Any config with a registered ModelFamily (GPT2Config, LlamaConfig, …).
    model: Any = dataclasses.field(
        default_factory=lambda: GPT2Config.tiny(vocab_size=384)
    )
    max_batch_size: int = 8
    max_seq_len: int = 128
    seed: int = 0
    # Optional: callable returning trained params (checkpoint load); default
    # random init (tests / smoke).
    param_loader: Optional[Callable[[], Any]] = None


def encode_prompt(tokenizer, prompt: str, max_seq_len: int) -> List[int]:
    """Tokenize + left-truncate to the cache budget — the ONE place prompt
    shaping happens (the disagg prefill role must match the monolithic
    engine byte-for-byte or outputs diverge)."""
    token_ids = tokenizer.encode(prompt)
    return token_ids[-(max_seq_len - 1):]


@dataclasses.dataclass
class _Slot:
    request_id: int
    prompt_len: int
    generated: List[int]
    params: SamplingParams
    done: bool = False

    @property
    def last_pos(self) -> int:
        """Cache position of the most recent token."""
        return self.prompt_len + len(self.generated) - 1


def _arrival() -> tuple:
    """What a queue entry ends with: when it arrived (``perf_counter``) and
    the cluster trace it belongs to, if any."""
    ctx = tracing.current_context()
    return time.perf_counter(), (ctx[0] if ctx else None)


def _drop_queued(queue: List[tuple], request_id: int) -> int:
    """Remove a request's entries IN PLACE.  ``add_request`` appends
    without the engine lock (a lock turn there would add a whole step to
    every TTFT), so the list must never be rebound: an append racing a
    rebind lands in the discarded list and its stream spins to its
    timeout.  Appends only ever grow the tail; this walks from the tail
    it saw down, under the lock that every ``pop`` also holds."""
    dropped = 0
    for i in range(len(queue) - 1, -1, -1):
        if queue[i][0] == request_id:
            del queue[i]
            dropped += 1
    return dropped


# The shortest prefill rung.  With bf16 weights a prefill of S tokens does S
# FLOP for every byte of weights it reads, and the v5e's peaks cross at
# 197e12 FLOP/s / 819e9 B/s = 240: below ~256 tokens a prefill costs the
# weights' read whatever its length, so a rung there buys a compilation and
# no time.
MIN_PREFILL_RUNG = 256


def prefill_ladder(max_seq_len: int) -> List[int]:
    """The padded lengths prefill is compiled at, a pure function of
    ``max_seq_len``: 256 x 2**k for every such value below it, then
    ``max_seq_len`` itself.  A prompt runs at the smallest rung that holds
    it (``prefill_rung``)."""
    rungs = []
    rung = MIN_PREFILL_RUNG
    while rung < max_seq_len:
        rungs.append(rung)
        rung *= 2
    return rungs + [max_seq_len]


def prefill_rung(rungs: List[int], n_tokens: int) -> int:
    """The smallest of the ascending ``rungs`` that is >= ``n_tokens``."""
    return rungs[bisect.bisect_left(rungs, n_tokens)]


def splice_row(cache, row, idx):
    """Write a one-slot cache ``row`` into slot ``idx`` of ``cache``, from
    position 0 on (a row shorter than the slot leaves the slot's tail as it
    was): the one thing known of a family's cache is that every leaf's slot
    axis is axis 1."""
    import jax

    def put(whole, one):
        start = (0, idx) + (0,) * (whole.ndim - 2)
        return jax.lax.dynamic_update_slice(whole, one, start)

    return jax.tree.map(put, cache, row)


def _without_counts(step):
    """A family step that returns (logits, cache), as one that counts
    nothing: (logits, cache, {})."""
    def counted(*args):
        logits, cache = step(*args)
        return logits, cache, {}
    return counted


class JaxLLMEngine:
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
        self.cache = fam.init_cache(mcfg, cfg.max_batch_size, cfg.max_seq_len)
        # Per-slot state; None = free.
        self.slots: List[Optional[_Slot]] = [None] * cfg.max_batch_size
        self._next_id = itertools.count()
        # (request_id, token_ids, params, perf_counter at arrival, trace_id)
        self._waiting: List[tuple] = []
        self._finished: Dict[int, dict] = {}
        # ALL engine-state mutation serializes on this lock: step() may be
        # driven concurrently by batched calls (replica event loop) and by
        # generate_stream callers (replica executor threads).  Reentrant:
        # generate/generate_stream hold it across pop+step.  Take it
        # through ``locked()``, which accounts for the wait.
        self._step_lock = threading.RLock()
        self._lock_owner: Optional[int] = None  # thread ident, outermost hold
        # Counters behind stats(); all but the two gauges only grow.
        self._counts: Dict[str, Any] = dict.fromkeys(
            ("steps", "decode_steps", "admitted", "retired", "cancelled",
             "prompt_tokens", "padded_prompt_tokens", "generated_tokens",
             "occupied_slot_steps", "host_syncs"), 0)
        self._counts.update(queue_wait_s_total=0.0, lock_wait_s_total=0.0)

        prefill = fam.prefill_counted or _without_counts(fam.prefill)
        decode_step = (fam.decode_step_counted
                       or _without_counts(fam.decode_step))

        def prefill_one(params, cache, tokens, length, slot_idx):
            """Prefill a single request, padded to ``tokens``' length (a
            rung), into positions ``[0, rung)`` of batch row ``slot_idx``.
            What the slot's last tenant left beyond the rung stays: decode
            reads nothing at or beyond a slot's ``pos``."""
            import jax.numpy as jnp

            one_cache = fam.init_cache(mcfg, 1, tokens.shape[0])
            logits, one_cache, counts = prefill(
                params, tokens[None], jnp.asarray([length]), one_cache, mcfg
            )
            # [1, V]: a batch of one for the sampler
            return logits, splice_row(cache, one_cache, slot_idx), counts

        # ONE named function under one jit (the program's name is what the
        # benchmark's reader finds), compiled ahead of time once a rung: on
        # shapes alone, so beside the weights' load (a jitted loader returns
        # before the device has run it), and in threads, because XLA
        # compiles outside the GIL.  ``_admit`` calls these executables.
        self._prefill_rungs = prefill_ladder(cfg.max_seq_len)
        jitted = jax.jit(prefill_one, donate_argnums=(1,))
        scalar = jax.ShapeDtypeStruct((), np.int32)

        def compile_rung(rung: int):
            tokens = jax.ShapeDtypeStruct((rung,), np.int32)
            return jitted.lower(
                self.params, self.cache, tokens, scalar, scalar).compile()

        with ThreadPoolExecutor(len(self._prefill_rungs)) as pool:
            self._prefill_one = dict(zip(
                self._prefill_rungs,
                pool.map(compile_rung, self._prefill_rungs)))
        # Disaggregated admission: the one-slot cache arrives from a prefill
        # replica instead of the local prefill program.
        self._insert_row = jax.jit(splice_row, donate_argnums=(0,))
        self._waiting_kv: List[tuple] = []  # (rid, meta, one-slot cache, ..)

        # A lambda, so that the program keeps the name the readers know.
        self._decode = jax.jit(
            lambda params, cache, tokens, pos: decode_step(
                params, tokens, pos, cache, mcfg
            ),
            donate_argnums=(1,),
        )
        # What the family's programs count (int32 scalars a run; nothing for
        # a family that counts nothing), summed here as Python ints.
        names = () if fam.decode_step_counted is None else jax.eval_shape(
            lambda p, c: decode_step(
                p, np.zeros(cfg.max_batch_size, np.int32),
                np.zeros(cfg.max_batch_size, np.int32), c, mcfg)[2],
            self.params, self.cache)
        self._family_counts = {
            kind: dict.fromkeys(names, 0) for kind in ("decode", "prefill")}
        # (kind, a run's counts on the device, their copy to the host under
        # way) in dispatch order, until ``_fold_counts`` reads them.
        self._unread_counts: List[tuple] = []
        self._sample_rows = jax.jit(sample_logits_rows)
        self._sample_greedy = jax.jit(sample_logits_greedy)

    # ----------------------------------------------------------------- queue
    def add_request(
        self, prompt: str, params: Optional[SamplingParams] = None
    ) -> int:
        params = params or SamplingParams()
        token_ids = encode_prompt(self.tokenizer, prompt, self.cfg.max_seq_len)
        request_id = next(self._next_id)
        self._waiting.append((request_id, token_ids, params, *_arrival()))
        return request_id

    def add_request_from_kv(self, meta: dict, row) -> int:
        """Disaggregated admission: enqueue a request whose prompt was
        prefilled elsewhere.  ``meta`` carries prompt_len / first_token /
        sampling (see llm.disagg.PrefillEngine.prefill); ``row`` is the
        prompt's one-slot cache, a pytree like this engine's cache with one
        slot."""
        import jax
        import jax.numpy as jnp

        arrival = _arrival()  # before the wait for the lock
        with self.locked():
            request_id = next(self._next_id)
            self._waiting_kv.append(
                (request_id, meta, jax.tree.map(jnp.asarray, row), *arrival)
            )
            return request_id

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit_kv(self):
        """Drain adopted-KV requests into free slots (no local prefill)."""
        while self._waiting_kv:
            idx = self._free_slot()
            if idx is None:
                return
            request_id, meta, row, t_arrive, trace_id = (
                self._waiting_kv.pop(0))
            # A one-slot cache's extent is the family's to know: counted as
            # this engine's own padded length.
            with self._admit_span(request_id, idx, meta["prompt_len"],
                                  self.cfg.max_seq_len, t_arrive, trace_id):
                self.cache = self._insert_row(self.cache, row, idx)
                slot = _Slot(
                    request_id=request_id,
                    prompt_len=meta["prompt_len"],
                    generated=[meta["first_token"]],
                    params=meta["sampling"],
                )
                self.slots[idx] = slot
                self._check_done(slot, meta["first_token"])

    def _admit_span(self, request_id: int, slot: int, prompt_len: int,
                    padded_len: int, t_arrive: float,
                    trace_id: Optional[str]):
        """Count one admission and open its ``engine.admit`` span (the
        attributes are all known at entry; ``trace_id`` joins the span to
        the cluster trace of ``tracing.start_span``)."""
        wait_s = time.perf_counter() - t_arrive
        c = self._counts
        c["admitted"] += 1
        c["prompt_tokens"] += prompt_len
        c["padded_prompt_tokens"] += padded_len
        c["queue_wait_s_total"] += wait_s
        attrs = {"trace_id": trace_id} if trace_id else {}
        return host_span(
            "engine.admit", request_id=request_id, slot=slot,
            prompt_len=prompt_len, padded_len=padded_len,
            queue_wait_ms=wait_s * 1e3, **attrs)

    def _admit(self):
        import jax.numpy as jnp

        self._admit_kv()
        while self._waiting:
            idx = self._free_slot()
            if idx is None:
                return
            request_id, token_ids, params, t_arrive, trace_id = (
                self._waiting.pop(0))
            rung = prefill_rung(self._prefill_rungs, len(token_ids))
            with self._admit_span(request_id, idx, len(token_ids),
                                  rung, t_arrive, trace_id):
                # Returns before the device finishes: the wait for the
                # prefill program shows in the sample span that follows.
                with host_span("engine.prefill.dispatch"):
                    tokens = np.zeros(rung, np.int32)
                    tokens[: len(token_ids)] = token_ids
                    logits, self.cache, counts = self._prefill_one[rung](
                        self.params,
                        self.cache,
                        jnp.asarray(tokens),
                        np.int32(len(token_ids)),
                        np.int32(idx),
                    )
                    self._note_counts("prefill", counts)
                with host_span("engine.sample", slots=1):
                    first = int(self._sample(logits, [(0, params)])[0])
                self._counts["generated_tokens"] += 1
                slot = _Slot(
                    request_id=request_id,
                    prompt_len=len(token_ids),
                    generated=[first],
                    params=params,
                )
                self.slots[idx] = slot
                self._check_done(slot, first)

    def _sample(self, logits, rows) -> np.ndarray:
        """One token for every row of the device's ``logits`` in ONE program
        and ONE device->host read; ``rows`` = (row, SamplingParams) of the
        rows that matter, the others are computed and ignored."""
        if all(p.temperature <= 0 for _, p in rows):
            tokens = self._sample_greedy(logits)
        else:
            n = logits.shape[0]
            temperature = np.zeros(n, np.float32)
            top_k = np.zeros(n, np.int32)
            top_p = np.ones(n, np.float32)
            for i, p in rows:
                temperature[i], top_k[i], top_p[i] = (
                    p.temperature, p.top_k, p.top_p)
            tokens, self._key = self._sample_rows(
                logits, self._key, temperature, top_k, top_p)
        self._counts["host_syncs"] += 1
        return np.asarray(tokens)

    def _check_done(self, slot: _Slot, token: int):
        stop = (
            slot.params.stop_token
            if slot.params.stop_token is not None
            else getattr(self.tokenizer, "EOS", None)
        )
        total_len = slot.prompt_len + len(slot.generated)
        if (
            (stop is not None and token == stop)
            or len(slot.generated) >= slot.params.max_tokens
            or total_len >= self.cfg.max_seq_len - 1
        ):
            slot.done = True

    # ------------------------------------------------------------------ step
    def step(self) -> List[dict]:
        """Admit waiting requests, run ONE decode step for all active slots,
        retire finished requests.  Returns newly finished outputs.
        Thread-safe (serialized on the engine lock)."""
        import jax.numpy as jnp

        with self.locked():
            return self._step_locked(jnp)

    @contextlib.contextmanager
    def locked(self, request_id: Optional[int] = None):
        """Hold the engine lock.  A thread's outermost acquisition is an
        ``engine.lock_wait`` span and counts into ``lock_wait_s_total``;
        re-entry (``stream_request`` -> ``step``) waits for nothing and
        records nothing."""
        me = threading.get_ident()
        if self._lock_owner == me:
            yield
            return
        attrs = {} if request_id is None else {"request_id": request_id}
        t0 = time.perf_counter()
        with host_span("engine.lock_wait", **attrs):
            self._step_lock.acquire()
        self._lock_owner = me
        self._counts["lock_wait_s_total"] += time.perf_counter() - t0
        try:
            yield
        finally:
            self._lock_owner = None
            self._step_lock.release()

    def _step_locked(self, jnp) -> List[dict]:
        c = self._counts
        admitted0, retired0 = c["admitted"], c["retired"]
        syncs0 = c["host_syncs"]
        # Counts of the runs dispatched before this step: their programs
        # will have ended when this step has read its own tokens.
        late = len(self._unread_counts)
        with host_span("engine.step", seq=c["steps"]):
            self._admit()
            finished = self._retire()  # requests that finished at admission
            active = [
                (i, s) for i, s in enumerate(self.slots)
                if s is not None and not s.done
            ]
            if active:
                with host_span("engine.decode.dispatch", active=len(active)):
                    tokens = np.zeros(self.cfg.max_batch_size, np.int32)
                    pos = np.zeros(self.cfg.max_batch_size, np.int32)
                    for i, s in active:
                        tokens[i] = s.generated[-1]
                        pos[i] = s.last_pos
                    logits, self.cache, counts = self._decode(
                        self.params, self.cache,
                        jnp.asarray(tokens), jnp.asarray(pos),
                    )
                    self._note_counts("decode", counts)
                with host_span("engine.sample", slots=len(active)):
                    sampled = self._sample(
                        logits, [(i, s.params) for i, s in active])
                    for i, s in active:
                        token = int(sampled[i])
                        s.generated.append(token)
                        self._check_done(s, token)
                c["decode_steps"] += 1
                c["generated_tokens"] += len(active)
            finished.extend(self._retire())
            occupied, waiting = self.occupied(), self._n_waiting()
            admitted = c["admitted"] - admitted0
            retired = c["retired"] - retired0
            c["steps"] += 1
            c["occupied_slot_steps"] += occupied
            # What is only known at the end of the step: zero-length, last.
            with host_span("engine.counts", occupied=occupied,
                           waiting=waiting, admitted=admitted,
                           retired=retired,
                           host_syncs=c["host_syncs"] - syncs0,
                           **self._fold_counts(late)):
                pass
        flight_recorder.record_llm_step(
            occupied, waiting, admitted, retired, self.cfg.max_batch_size)
        return finished

    def _retire(self) -> List[dict]:
        out = []
        with host_span("engine.retire"):
            for i, s in enumerate(self.slots):
                if s is not None and s.done:
                    gen = s.generated
                    stop = (
                        s.params.stop_token
                        if s.params.stop_token is not None
                        else getattr(self.tokenizer, "EOS", None)
                    )
                    if stop is not None and gen and gen[-1] == stop:
                        gen = gen[:-1]
                    result = {
                        "request_id": s.request_id,
                        "token_ids": gen,
                        "text": self.tokenizer.decode(gen),
                        "num_generated": len(s.generated),
                    }
                    self._finished[s.request_id] = result
                    out.append(result)
                    self.slots[i] = None
        self._counts["retired"] += len(out)
        return out

    # ---------------------------------------------------------------- counts
    def _note_counts(self, kind: str, counts: dict) -> None:
        """Keep what a run of the prefill or decode program counted, and
        start its copy to the host; nothing for a family without counts."""
        import jax

        if counts:
            for leaf in jax.tree.leaves(counts):
                leaf.copy_to_host_async()
            self._unread_counts.append((kind, counts))

    def _fold_counts(self, n: int) -> Dict[str, int]:
        """Add the ``n`` oldest unread runs' counts to the totals; returns
        what the decode steps among them counted (``engine.counts``'
        attributes: one step late, because a step folds only runs dispatched
        before it, whose copies have arrived: no wait, no ``host_syncs``)."""
        decoded: Dict[str, int] = {}
        for kind, counts in self._unread_counts[:n]:
            for name, value in counts.items():
                self._family_counts[kind][name] += int(value)
                if kind == "decode":
                    decoded[name] = decoded.get(name, 0) + int(value)
        del self._unread_counts[:n]
        return decoded

    def occupied(self) -> int:
        """Slots that hold a request right now."""
        return sum(1 for s in self.slots if s is not None)

    def _n_waiting(self) -> int:
        return len(self._waiting) + len(self._waiting_kv)

    def stats(self) -> Dict[str, Any]:
        """Counters since the engine was built (plain numbers that only
        grow) and two gauges, ``occupied`` and ``waiting``; taken under the
        engine lock, so one step's counts are never seen half-added.
        ``generated_tokens`` counts tokens THIS engine sampled (an adopted
        KV request's first token came from its prefill replica);
        ``occupied_slot_steps`` sums, over steps, the slots occupied when
        the step returns: over ``steps`` it is the mean batch occupancy;
        ``host_syncs`` counts device->host token reads: one a decode step,
        one a locally prefilled admission.  What the family's programs
        counted (an expert layer's routing) follows under the family's
        names for decode steps and with ``prefill_`` before them for
        prefills (runs not yet read are read here)."""
        with self.locked():
            self._fold_counts(len(self._unread_counts))  # may wait: exact
            counted = dict(self._family_counts["decode"])
            counted.update(("prefill_" + k, v) for k, v
                           in self._family_counts["prefill"].items())
            return dict(self._counts, **counted, occupied=self.occupied(),
                        waiting=self._n_waiting())

    def has_unfinished(self) -> bool:
        return bool(self._waiting) or bool(self._waiting_kv) or any(
            s is not None for s in self.slots
        )

    # ------------------------------------------------------------- generate
    def cancel_request(self, request_id: int) -> None:
        """Drop a request wherever it is (queue, slot, finished results) —
        abandoned streams must not keep decoding or park results forever."""
        with self.locked(request_id):
            dropped = _drop_queued(self._waiting, request_id)
            dropped += _drop_queued(self._waiting_kv, request_id)
            for i, slot in enumerate(self.slots):
                if slot is not None and slot.request_id == request_id:
                    self.slots[i] = None
                    dropped += 1
            self._counts["cancelled"] += dropped
            self._finished.pop(request_id, None)

    def generate_stream(self, prompt: str,
                        params: Optional[SamplingParams] = None,
                        timeout_s: float = 300.0):
        """Incremental generation: yields the text delta after every decode
        step for this request.  Concurrent streams (and batched generate
        calls) share the slot pool — every state access holds the engine
        lock; only the yields happen outside it."""
        yield from self.stream_request(
            self.add_request(prompt, params), timeout_s
        )

    def stream_request(self, request_id: int, timeout_s: float = 300.0):
        """Stream an ALREADY-QUEUED request's deltas (the disaggregated
        streaming path: the id came from add_request_from_kv, whose prompt
        was prefilled on another replica)."""
        emitted = 0
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                if time.monotonic() > deadline:
                    raise TimeoutError("generation exceeded timeout")
                done = None
                delta_tokens: list = []
                with self.locked(request_id):
                    done = self._finished.pop(request_id, None)
                    if done is None:
                        self.step()
                        done = self._finished.pop(request_id, None)
                    if done is None:
                        slot = next(
                            (s for s in self.slots
                             if s is not None
                             and s.request_id == request_id),
                            None,
                        )
                        if slot is not None and len(slot.generated) > emitted:
                            delta_tokens = list(slot.generated[emitted:])
                            emitted += len(delta_tokens)
                if done is not None:
                    tail = self.tokenizer.decode(done["token_ids"][emitted:])
                    if tail:
                        yield tail
                    return
                if delta_tokens:
                    text = self.tokenizer.decode(delta_tokens)
                    if text:
                        yield text
        finally:
            # Timeout or abandoned consumer: release the slot/queue entry.
            self.cancel_request(request_id)

    def wait(self, request_ids: List[int],
             timeout_s: float = 300.0) -> List[dict]:
        """Step the engine, one lock turn at a time, until every one of
        THIS caller's ``request_ids`` has finished; returns their results in
        that order.  Other callers' in-flight work (streams, other waits)
        shares the steps and delays nothing here.  Past ``timeout_s`` the
        requests are cancelled (slots and queue entries freed) and
        ``TimeoutError`` is raised.  With ``stream_request``, one of the two
        bodies that step the engine."""
        def done() -> bool:
            return all(i in self._finished for i in request_ids)

        deadline = time.monotonic() + timeout_s
        while True:
            with self.locked():
                if not done():
                    self.step()
                if done():
                    return [self._finished.pop(i) for i in request_ids]
            if time.monotonic() > deadline:
                for i in request_ids:
                    self.cancel_request(i)
                raise TimeoutError(
                    f"requests {list(request_ids)} exceeded {timeout_s} s")

    def generate(
        self,
        prompts: List[str],
        params: Optional[SamplingParams] = None,
        timeout_s: float = 300.0,
    ) -> List[dict]:
        """Blocking batch generation (requests stream through the slot pool
        regardless of len(prompts) vs max_batch_size)."""
        return self.wait(
            [self.add_request(p, params) for p in prompts], timeout_s)
