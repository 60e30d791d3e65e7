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
programs, read ONE STEP LATE like the tokens (copies to the host started at
dispatch), summed in ``stats()`` and written on ``engine.counts``.

Shapes are static.  The cache is max_batch_size × max_seq_len, and so is
the decode step.  Prefill runs at the PROMPT's length, not the cache's: one
request a call, padded to the smallest rung of ``prefill_ladder(max_seq_len)``
(256, 512, 1024, … and ``max_seq_len`` itself) that holds it, and the row it
writes covers positions ``[0, rung)`` of the slot.  Every rung is one
compilation of the same ``prefill_one``, made when the engine is BUILT
(ahead of time, in threads), so no prompt length meets a compiler later; an
engine of ``max_seq_len <= 256`` has one rung.  The decode step is compiled
there too, FIRST, because it decides where the weights lie: the compiler
chooses their device layouts for it (``jit_decode_step``), the rungs are
compiled for the weights as they will lie there, so that no program relays
a weight when it runs, and the build moves each leaf that lies otherwise
into the layout chosen, once (``stats()["relaid_param_bytes"]``; logical
shapes, dtypes and values are the loader's).  All of that is compiled from
shapes, beside the weights' load.  Two samplers over
its ``[max_batch_size, V]`` logits compile at the first request:
``sample_logits_greedy`` when every active slot has temperature 0,
``sample_logits_rows`` otherwise (both also at ``[1, V]``, for a prefill's
first token), and ``put_first_token``.  Sampling
parameters reach the sampler as per-row ARRAYS, so a new ``SamplingParams``
value compiles nothing.  At ``temperature > 0`` the draws for a given
``seed`` differ from versions that split one key per slot on the host: the
key is now split once a step, inside the program.

The loop runs ONE STEP AHEAD of the device.  A step's sampled tokens stay on
the device: the sampler's ``[max_batch_size]`` vector is the next decode's
token operand as it is (an admission's first token is put into it at its
row, on the device, as a new array), and ``pos`` is the host's, a COUNT it
knows without the tokens' values.  One iteration: admit -> dispatch decode k
(fed by step k - 1's vector) -> dispatch sampler k -> READ vector k - 1,
whose copy to the host started when it was dispatched and which was complete
before decode k could start -> read this iteration's admissions' first
tokens (the host waits for their prefills here, with decode k queued behind
them) -> retire, deliver.  The device always has a decode and a sampler
queued while the host reads, retires, admits and launches; a step still
reads ONE vector, whatever the number of slots, and one first token an
admission.  Stops are of two kinds.  One the host knows by COUNT
(``max_tokens``, the cache's extent) is known before the last token's value:
the slot rides no further decode and its row is the next tenant's at once;
the slot itself is kept with the unread vector until its last token is on
the host.  A stop by VALUE (``stop_token``, the tokenizer's ``EOS``) is seen
one step late: the row rides one decode more, whose token is dropped (never
appended, delivered or counted; ``stats()["overrun_row_steps"]`` counts the
row-steps so lost, a cancel with a step in flight among them), and whose
write at the row's next position (and, in a recurrent family, into its
state) is the next tenant's prefill's to replace, as ``splice_row`` does for
every leaf.  Such a position is inside the cache: the count rule stops a
slot at ``max_seq_len - 1``.

Program names are a contract too: the benchmark's readers find the decode
program as the only ``jit__lambda`` and the samplers by ``jit_sample_logits``,
so whatever is jitted here beside the decode step is a NAMED function.

Who steps: ONE thread an engine, ``engine.loop``.  It runs ``step()`` while
anything is unfinished (a dispatched step whose tokens are unread counts) and
otherwise sleeps on a condition that ``add_request``, ``add_request_from_kv``
and ``shutdown`` notify; it starts when the first caller blocks in ``wait``
or ``stream_request`` (an engine stepped by hand through the public
``step()``, which is one iteration of the same loop, never grows a thread)
and ``shutdown()`` joins it.  Callers never step.  Each request has a
mailbox.  The tokens its slot gained are put there as they are read, which
is after the iteration's decode is dispatched (the device then has a step's
work, and the woken callers' threads have the interpreter to themselves
while the loop waits for it; a request's first token leaves in the iteration
that prefilled it); its result is put there when it retires, the iteration
after the one that sampled its last token.  Its caller, blocked on the
mailbox, takes everything it finds: a stream yields that as one delta, so
one token a delta while the consumer keeps up, and the deltas grow by
themselves while it does not.  No chunk size, no flush interval, no knob.  A
step that raises fails every request there is.  The engine lock is the
loop's for a step and an outside caller's (``cancel_request``,
``add_request_from_kv``, ``stats``) between two steps, with a step in flight
on the device.

Observability (names are a contract: tests pin them, PERF.md lists which
metric reads which).  Host work runs inside ``util.tracing.host_span``s —
``engine.lock_wait`` (outside callers only), ``engine.step`` >
``engine.admit`` > (``engine.prefill.dispatch``, ``engine.sample``: the first
token's sampler and its way into the operand), ``engine.decode.dispatch``
(``longest``: the riding rows' highest ``pos``; ``read_positions``: how far
the step's attention reads each full-extent cache for it,
``ops/decode_attention.live_extent``, the function the program bounds its
loop with; ``cache_positions``: ``max_seq_len``; ``live_positions``: the
riding rows' mean ``pos``, what a read bounded a slot would take),
``engine.sample`` (the sampler's dispatch and every read of the iteration) >
``engine.retire``, and a zero-length ``engine.counts`` at the end of every
step — which a profiler session writes on the device trace's clock.
``stats()`` gives the same counts with no session, and every step feeds
``flight_recorder.record_llm_step`` (``/metrics``).  ``engine.admit`` carries
the request's cluster ``trace_id`` and ``unix_ns``, the wall clock at its
entry: the anchor between the device trace's clock and the cluster trace's
(``util/tracing.py``).

On the wall clock (``tracing.start_span`` / ``record_span``: the cluster
trace, written to ``spans.jsonl`` at shutdown, whether or not a session
ran): ``llm.engine.build`` (the constructor) > ``llm.engine.weights`` (the
host's part of the load), ``llm.engine.relayout`` (the dispatch of the
weights' move into the decode step's layouts, ``relaid_param_bytes``),
``llm.engine.compile`` (one a program: ``program`` = ``prefill_one`` with
its ``rung``, ``decode_step``); and ONE
``engine.stream`` a streamed request, recorded by ``stream_request`` when
the stream ends, under its caller's context: ``start`` = ``add_request``
(after the tokenizer), ``request_id``, ``admitted_unix_ns``,
``first_token_unix_ns`` (the loop's first ``put`` of a token into the
mailbox), ``deltas`` and ``tokens`` (a delta is whatever the mailbox held:
``tokens`` / ``deltas`` is 1.0 while the caller's thread keeps up with the
loop).  ``stats()["stream_deltas"]`` and ``["stream_delta_tokens"]`` are
their sums over the streams that have ended.  No span a token anywhere.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..models import GPT2Config, model_family
from ..models.sampling import sample_logits_greedy, sample_logits_rows
from ..ops.decode_attention import live_extent
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
    generated: List[int]  # the tokens that have reached the host
    params: SamplingParams
    sampled: int = 1  # tokens sampled for it on the device, read or not
    done: bool = False  # stopped or cancelled: nothing more is appended
    delivered: int = 0  # of ``generated``, handed to the request's mailbox

    @property
    def last_pos(self) -> int:
        """Cache position of the most recent token: a COUNT, which the host
        knows without the token's value."""
        return self.prompt_len + self.sampled - 1


class _Mailbox(queue.SimpleQueue):
    """A request's mailbox (``JaxLLMEngine._mailboxes``), with the stamps of
    its way through the engine that ``engine.stream`` reports: wall clock,
    each written once by the one thread that knows it."""

    def __init__(self):
        super().__init__()
        self.added = time.time()  # ``add_request``, after the tokenizer
        self.admitted_unix_ns = 0  # the loop: ``engine.admit``'s entry
        self.first_token_unix_ns = 0  # the loop: the first ``put`` of a token


def _arrival() -> tuple:
    """What a queue entry ends with: when it arrived (``perf_counter``) and
    the cluster trace it belongs to, if any."""
    ctx = tracing.current_context()
    return time.perf_counter(), (ctx[0] if ctx else None)


def _drop_queued(waiting: List[tuple], request_id: int) -> int:
    """Remove a request's entries IN PLACE.  ``add_request`` appends
    without the engine lock (a lock turn there would add a whole step to
    every TTFT), so the list must never be rebound: an append racing a
    rebind lands in the discarded list and its caller waits to its
    timeout.  Appends only ever grow the tail; this walks from the tail
    it saw down, under the lock that every ``pop`` also holds."""
    dropped = 0
    for i in range(len(waiting) - 1, -1, -1):
        if waiting[i][0] == request_id:
            del waiting[i]
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


def put_first_token(feed, token, idx):
    """The decode step's ``[max_batch_size]`` token operand with row ``idx``
    replaced by an admission's first token (``[1]``), as a NEW array: the
    vector it is made from may still be unread, and holds there the last
    token of the row's last tenant."""
    import jax

    return jax.lax.dynamic_update_slice(feed, token, (idx,))


def _without_counts(step):
    """A family step that returns (logits, cache), as one that counts
    nothing: (logits, cache, {})."""
    def counted(*args):
        logits, cache = step(*args)
        return logits, cache, {}
    return counted


def jit_decode_step(family, model, params):
    """The decode program as the engine jits it, ``(params, cache, tokens,
    pos) -> (logits, cache, counts)`` with the cache donated and the PARAMS'
    device layouts left to the compiler.  ``params``: a pytree of anything
    with a ``sharding`` (arrays, ``jax.ShapeDtypeStruct``s); a leaf stays
    where that puts it.  Lowered from SHAPES and compiled, the program's
    ``input_formats[0][0]`` say how it wants each weight laid out: the default
    for most, another where a product could not read the default in place
    and the step would copy the weight first, every time it runs (Mistral's
    ``wq`` / ``wk`` / ``wv``: 0.81 GB a step).  A lambda, so that the program
    keeps the name the benchmark's readers know."""
    import jax
    from jax.experimental.layout import Format, Layout

    decode_step = (family.decode_step_counted
                   or _without_counts(family.decode_step))
    anywhere = jax.tree.map(
        lambda leaf: Format(Layout.AUTO, leaf.sharding), params)
    return jax.jit(
        lambda params, cache, tokens, pos: decode_step(
            params, tokens, pos, cache, model),
        in_shardings=(anywhere, None, None, None),
        donate_argnums=(1,),
    )


def jit_prefill_one(family, model):
    """The prefill program as the engine jits it, ``(params, cache, tokens
    [rung], length, slot_idx) -> (logits [1, V], cache, counts)`` with the
    cache donated: ONE named function under one jit (the program's name is
    what the benchmark's reader finds), lowered once a rung."""
    import jax
    import jax.numpy as jnp

    prefill = family.prefill_counted or _without_counts(family.prefill)

    def prefill_one(params, cache, tokens, length, slot_idx):
        """Prefill a single request, padded to ``tokens``' length (a
        rung), into positions ``[0, rung)`` of batch row ``slot_idx``.
        What the slot's last tenant left beyond the rung stays: decode
        reads nothing at or beyond a slot's ``pos``."""
        one_cache = family.init_cache(model, 1, tokens.shape[0])
        logits, one_cache, counts = prefill(
            params, tokens[None], jnp.asarray([length]), one_cache, model
        )
        # [1, V]: a batch of one for the sampler
        return logits, splice_row(cache, one_cache, slot_idx), counts

    return jax.jit(prefill_one, donate_argnums=(1,))


def lay_out(params, formats):
    """``params`` with every leaf in its ``formats`` leaf's device layout
    (``jax.experimental.layout.Format``: layout + sharding), and the bytes of
    the leaves that had to move for it.  A leaf that lies so already is
    returned as it is; one that moves is copied on its device, and HELD to
    the layout asked for.  Logical shapes, dtypes and values do not
    change."""
    import jax

    def put(leaf, fmt):
        """``device_put`` into a layout is not taken on trust.  On the v5e,
        in a build whose programs all come out of the compile cache, an
        UNCOMMITTED leaf (a jitted loader's output) has come back as a
        committed copy in its OLD layout, in silence, and the first prefill
        then refused it (PERF.md, PR 45); put again, the committed copy
        moves.  What still lies otherwise is an error here, at build."""
        moved = jax.device_put(leaf, fmt)
        if moved.format != fmt:
            moved = jax.device_put(moved, fmt)
        if moved.format != fmt:
            raise RuntimeError(
                f"a {leaf.dtype}{list(leaf.shape)} weight stays in layout "
                f"{moved.format.layout} where {fmt.layout} was asked for")
        return moved

    leaves, tree = jax.tree.flatten(params)
    laid = [leaf if leaf.format == fmt else put(leaf, fmt)
            for leaf, fmt in zip(leaves, tree.flatten_up_to(formats))]
    return tree.unflatten(laid), sum(
        was.nbytes for was, now in zip(leaves, laid) if now is not was)


class JaxLLMEngine:
    def __init__(self, cfg: EngineConfig, tokenizer=None):
        with tracing.start_span("llm.engine.build"):
            self._build(cfg, tokenizer)

    def _build(self, cfg: EngineConfig, tokenizer) -> None:
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.tokenizer = tokenizer or ByteTokenizer()
        mcfg = cfg.model
        fam = model_family(mcfg)
        self.family = fam
        self._key = jax.random.PRNGKey(cfg.seed + 1)
        # Per-slot state; None = free.
        self.slots: List[Optional[_Slot]] = [None] * cfg.max_batch_size
        self._next_id = itertools.count()
        # (request_id, token_ids, params, perf_counter at arrival, trace_id)
        self._waiting: List[tuple] = []
        # A mailbox a request, from ``add_request`` until its caller has
        # collected or cancelled it: whoever steps puts there the tokens
        # the request's slot gained (lists), then its result (a dict), or
        # the error that ended it (an exception).
        self._mailboxes: Dict[int, _Mailbox] = {}
        self._blocked: set = set()  # idents of threads waiting on a mailbox
        # ALL engine-state mutation serializes on this lock: the loop holds
        # it for a step, an outside caller (cancel, stats, an adopted
        # request) between two steps.  Reentrant: the loop holds it around
        # ``step()``.  Outside callers take it through ``locked()``, which
        # accounts for the wait; everybody takes it through ``_turn``, or
        # the loop, which asks again the moment it lets go, would never
        # lose it.
        self._step_lock = threading.RLock()
        self._turn = threading.Lock()
        self._lock_owner: Optional[int] = None  # thread ident, outermost hold
        # The loop sleeps on this while there is nothing to step;
        # ``_loop`` and ``_stopped`` change under it.
        self._wake = threading.Condition()
        self._loop: Optional[threading.Thread] = None
        self._stopped = False
        # Counters behind stats(); all but the two gauges only grow.
        self._counts: Dict[str, Any] = dict.fromkeys(
            ("steps", "loop_steps", "decode_steps", "admitted", "retired",
             "cancelled", "prompt_tokens", "padded_prompt_tokens",
             "generated_tokens", "occupied_slot_steps", "host_syncs",
             "overrun_row_steps", "stream_deltas", "stream_delta_tokens"), 0)
        self._counts.update(queue_wait_s_total=0.0, lock_wait_s_total=0.0)

        decode_step = (fam.decode_step_counted
                       or _without_counts(fam.decode_step))
        # Compiled ahead of time once a rung, in threads, because XLA
        # compiles outside the GIL.  ``_admit`` calls these executables.
        self._prefill_rungs = prefill_ladder(cfg.max_seq_len)
        jitted = jit_prefill_one(fam, mcfg)
        scalar = jax.ShapeDtypeStruct((), np.int32)
        a_slot = jax.ShapeDtypeStruct((cfg.max_batch_size,), np.int32)

        build = tracing.current_context()

        def compiled(program: str, lower, **attrs):
            """One ``llm.engine.compile`` span a program.  A pool thread
            copies no context: the build's is handed over."""
            tracing.set_context(build)
            with tracing.start_span(
                    "llm.engine.compile", {"program": program, **attrs}):
                return lower().compile()

        def shaped(leaf, sharding):
            """All a compilation needs of an array: its shape and dtype, and
            where it lies (a sharding, or a format = layout + sharding)."""
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding)

        def compile_rung(params, cache, rung: int):
            tokens = jax.ShapeDtypeStruct((rung,), np.int32)
            return compiled("prefill_one", lambda: jitted.lower(
                params, cache, tokens, scalar, scalar), rung=rung)

        def compile_all(params, cache):
            """From shapes: the decode step, whose answer is where the
            weights lie, then every other program that takes the weights
            (the rungs, in the pool) for the weights as they will lie
            there, so that none relays a weight when it runs.  Returns
            (the decode step, a future a rung)."""
            decode = compiled("decode_step", lambda: jit_decode_step(
                fam, mcfg, params).lower(params, cache, a_slot, a_slot))
            lying = jax.tree.map(shaped, params, decode.input_formats[0][0])
            return decode, [pool.submit(compile_rung, lying, cache, rung)
                            for rung in self._prefill_rungs]

        # The compilations FIRST, from the shapes the family's ``init`` and
        # ``init_cache`` give, where this process puts what nobody placed:
        # they need no weight, and run beside the weights' load.
        expected = jax.tree.map(
            lambda leaf: shaped(leaf, self._key.sharding), jax.eval_shape(
                lambda: (fam.init(jax.random.PRNGKey(cfg.seed), mcfg),
                         fam.init_cache(
                             mcfg, cfg.max_batch_size, cfg.max_seq_len))))
        with ThreadPoolExecutor(len(self._prefill_rungs) + 1) as pool:
            programs = pool.submit(compile_all, *expected)
            # The host's part: a jitted loader returns before the device
            # has run it.
            with tracing.start_span("llm.engine.weights"):
                if cfg.param_loader is not None:
                    # (a loader's host arrays go to the device here)
                    self.params = jax.tree.map(
                        jnp.asarray, cfg.param_loader())
                else:
                    self.params = fam.init(jax.random.PRNGKey(cfg.seed), mcfg)
                self.cache = fam.init_cache(
                    mcfg, cfg.max_batch_size, cfg.max_seq_len)
            self._decode, rungs = programs.result()
            arrived = jax.tree.map(
                lambda leaf: shaped(leaf, leaf.sharding),
                (self.params, self.cache))
            if arrived != expected:  # a loader's own dtypes or placement
                for stale in rungs:
                    stale.cancel()
                self._decode, rungs = compile_all(*arrived)
            # The step's layouts are the engine's: each leaf that lies
            # otherwise moves there, once, on the device.
            with tracing.start_span("llm.engine.relayout") as span:
                self.params, relaid = lay_out(
                    self.params, self._decode.input_formats[0][0])
                span.set_attribute("relaid_param_bytes", relaid)
            self._counts["relaid_param_bytes"] = relaid
            self._prefill_one = {rung: program.result() for rung, program
                                 in zip(self._prefill_rungs, rungs)}
        # Disaggregated admission: the one-slot cache arrives from a prefill
        # replica instead of the local prefill program.
        self._insert_row = jax.jit(splice_row, donate_argnums=(0,))
        self._waiting_kv: List[tuple] = []  # (rid, meta, one-slot cache, ..)

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
        # The decode step's token operand lives on the device: the last
        # step's sampled vector, with this step's admissions' first tokens
        # put in.
        self._put_first_token = jax.jit(put_first_token)
        self._feed = jnp.zeros(cfg.max_batch_size, jnp.int32)
        # The last decode step's sampled vector while the host has not read
        # it, with who rode the step: (tokens on the device, [(row, slot)]).
        # A slot that left its row by count is kept HERE until its last
        # token is on the host.
        self._unread: Optional[tuple] = None

    # ----------------------------------------------------------------- queue
    def add_request(
        self, prompt: str, params: Optional[SamplingParams] = None
    ) -> int:
        params = params or SamplingParams()
        token_ids = encode_prompt(self.tokenizer, prompt, self.cfg.max_seq_len)
        request_id = next(self._next_id)
        self._mailboxes[request_id] = _Mailbox()  # before the queue
        self._waiting.append((request_id, token_ids, params, *_arrival()))
        self._notify_loop()
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
            self._mailboxes[request_id] = _Mailbox()
            self._waiting_kv.append(
                (request_id, meta, jax.tree.map(jnp.asarray, row), *arrival)
            )
        self._notify_loop()
        return request_id

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _seat(self, idx: int, slot: _Slot, token) -> None:
        """Row ``idx`` is ``slot``'s from the next decode on, and its first
        token (``[1]``, on the device) is that decode's operand there.  A
        request whose first token is its last by count has no decode to
        ride and takes no row."""
        if self._spent(slot, slot.sampled):
            return
        self._feed = self._put_first_token(self._feed, token, np.int32(idx))
        self.slots[idx] = slot

    def _admit_kv(self, jnp) -> List[_Slot]:
        """Drain adopted-KV requests into free slots (no local prefill).
        Returns those whose first token, which came with them, was their
        last."""
        stopped = []
        while self._waiting_kv:
            idx = self._free_slot()
            if idx is None:
                break
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
                self._check_done(slot, meta["first_token"])
                if slot.done:
                    stopped.append(slot)
                else:  # the host's token: the decode's operand is the device's
                    self._seat(idx, slot, jnp.asarray(
                        [meta["first_token"]], jnp.int32))
        return stopped

    def _admit_span(self, request_id: int, slot: int, prompt_len: int,
                    padded_len: int, t_arrive: float,
                    trace_id: Optional[str]):
        """Count one admission and open its ``engine.admit`` span (the
        attributes are all known at entry; ``trace_id`` joins the span to
        the cluster trace of ``tracing.start_span``, and ``unix_ns``, the
        wall clock here, anchors that trace's clock to this one's)."""
        wait_s = time.perf_counter() - t_arrive
        unix_ns = time.time_ns()
        box = self._mailboxes.get(request_id)
        if box is not None:
            box.admitted_unix_ns = unix_ns
        c = self._counts
        c["admitted"] += 1
        c["prompt_tokens"] += prompt_len
        c["padded_prompt_tokens"] += padded_len
        c["queue_wait_s_total"] += wait_s
        attrs = {"trace_id": trace_id} if trace_id else {}
        return host_span(
            "engine.admit", request_id=request_id, slot=slot,
            prompt_len=prompt_len, padded_len=padded_len,
            queue_wait_ms=wait_s * 1e3, unix_ns=unix_ns, **attrs)

    def _admit(self, jnp) -> tuple:
        """Fill free slots from the queues.  Nothing here waits for the
        device: a prefill, its sampler and the first token's way into the
        decode's operand are dispatched one behind the other.  Returns
        (the first tokens sampled here, each ``(tokens [1] on the device,
        [(0, slot)])`` as ``_absorb`` takes them; the adopted requests that
        stopped at their first token)."""
        firsts = []
        stopped = self._admit_kv(jnp)
        while self._waiting:
            idx = self._free_slot()
            if idx is None:
                break
            request_id, token_ids, params, t_arrive, trace_id = (
                self._waiting.pop(0))
            rung = prefill_rung(self._prefill_rungs, len(token_ids))
            with self._admit_span(request_id, idx, len(token_ids),
                                  rung, t_arrive, trace_id):
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
                slot = _Slot(
                    request_id=request_id,
                    prompt_len=len(token_ids),
                    generated=[],
                    params=params,
                )
                with host_span("engine.sample", slots=1):
                    first = self._sample(logits, [(0, params)])
                    self._seat(idx, slot, first)
                firsts.append((first, [(0, slot)]))
        return firsts, stopped

    def _sample(self, logits, rows):
        """One token for every row of the device's ``logits`` in ONE
        program; ``rows`` = (row, SamplingParams) of the rows that matter,
        the others are computed and ignored.  The tokens stay on the device
        (``int32 [rows of logits]``) with their copy to the host under way:
        ``_absorb`` reads them."""
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
        tokens.copy_to_host_async()
        return tokens

    def _spent(self, slot: _Slot, n: int) -> bool:
        """Whether ``n`` tokens are all the request may have: a stop known
        by COUNT, so before the last token's value is."""
        return (n >= slot.params.max_tokens
                or slot.prompt_len + n >= self.cfg.max_seq_len - 1)

    def _stop_token(self, slot: _Slot) -> Optional[int]:
        if slot.params.stop_token is not None:
            return slot.params.stop_token
        return getattr(self.tokenizer, "EOS", None)

    def _check_done(self, slot: _Slot, token: int):
        """After ``token`` has joined ``slot.generated``."""
        if (token == self._stop_token(slot)
                or self._spent(slot, len(slot.generated))):
            slot.done = True

    def _absorb(self, tokens, rows) -> List[_Slot]:
        """ONE device->host read: a dispatched sampler's ``tokens`` reach
        the slots that were in its ``rows``.  Returns the slots this ended.
        A slot that is done already (it stopped by VALUE, which the host
        sees a step late, or was cancelled with the step in flight) rode
        that step for nothing: its token is dropped and counted."""
        values = np.asarray(tokens)
        c = self._counts
        c["host_syncs"] += 1
        ended = []
        for i, s in rows:
            if s.done:
                c["overrun_row_steps"] += 1
                continue
            token = int(values[i])
            s.generated.append(token)
            c["generated_tokens"] += 1
            self._check_done(s, token)
            if s.done:
                ended.append(s)
        return ended

    # ------------------------------------------------------------------ step
    def step(self) -> List[dict]:
        """One iteration of the loop: admit waiting requests, dispatch ONE
        decode step and its sampler for every slot that holds a row, then
        read what the LAST step sampled (and this step's admissions' first
        tokens) and retire the requests that ended there.  Returns newly
        finished outputs: a request's last token is read the step after the
        one that sampled it, and ``has_unfinished()`` stays true until then.
        Thread-safe (serialized on the engine lock).  For a caller that IS
        the loop (the engine's own thread; a test or a debugger stepping by
        hand): everybody else adds a request and waits (``wait``,
        ``stream_request``)."""
        import jax.numpy as jnp

        with self.locked():
            return self._step_locked(jnp)

    def _acquire(self) -> None:
        """Take the engine lock in turn: whoever waits for it holds
        ``_turn``, so a thread that has just released it queues behind."""
        with self._turn:
            self._step_lock.acquire()
        self._lock_owner = threading.get_ident()

    def _release(self) -> None:
        self._lock_owner = None
        self._step_lock.release()

    @contextlib.contextmanager
    def locked(self, request_id: Optional[int] = None):
        """Hold the engine lock, as a caller from outside the loop does for
        at most one step.  A thread's outermost acquisition is an
        ``engine.lock_wait`` span and counts into ``lock_wait_s_total``;
        re-entry (the loop -> ``step``) waits for nothing and records
        nothing."""
        if self._lock_owner == threading.get_ident():
            yield
            return
        attrs = {} if request_id is None else {"request_id": request_id}
        t0 = time.perf_counter()
        with host_span("engine.lock_wait", **attrs):
            self._acquire()
        self._counts["lock_wait_s_total"] += time.perf_counter() - t0
        try:
            yield
        finally:
            self._release()

    # ------------------------------------------------------------------ loop
    def _notify_loop(self) -> None:
        with self._wake:
            self._wake.notify()

    def _ensure_loop(self) -> None:
        """Start the loop at the first caller that is about to block: an
        engine only ever stepped by hand never grows a thread."""
        if self._loop is not None and not self._stopped:
            return  # every later call: no lock
        with self._wake:
            if self._stopped:
                raise RuntimeError("the engine is shut down")
            if self._loop is None:
                self._loop = threading.Thread(
                    target=self._run_loop, name="engine.loop", daemon=True)
                self._loop.start()

    def _run_loop(self) -> None:
        """The one thread that steps the engine on its callers' behalf:
        steps while anything is unfinished, sleeps until ``add_request``,
        ``add_request_from_kv`` or ``shutdown`` notify.  A step that raises
        fails every request there is and the loop goes on."""
        while True:
            with self._wake:
                while not (self._stopped or self.has_unfinished()):
                    self._wake.wait()
                if self._stopped:
                    return
            self._acquire()  # not ``locked()``: the loop's turn is no wait
            try:
                self.step()
                self._counts["loop_steps"] += 1
            except Exception as e:  # noqa: BLE001 - the callers' to raise
                self._fail_all(e)
            finally:
                self._release()

    def _held(self) -> List[_Slot]:
        """Under the lock: the slots of the requests that are not over, in
        a row or out of it by count with the last token unread (one that
        is both comes twice)."""
        leaving = self._unread[1] if self._unread is not None else ()
        return [s for s in (*self.slots, *(s for _, s in leaving))
                if s is not None and not s.done]

    def _deliver_tokens(self) -> None:
        """Under the lock: the tokens each live slot has gained since the
        last call go to its request's mailbox, which wakes its waiter.  (A
        request's last tokens are in its result: ``_retire``.)"""
        for s in self._held():
            if len(s.generated) > s.delivered:
                box = self._mailboxes.get(s.request_id)
                if box is not None:
                    if not s.delivered:
                        box.first_token_unix_ns = time.time_ns()
                    box.put(s.generated[s.delivered:])
                s.delivered = len(s.generated)

    def _fail_all(self, error: BaseException) -> None:
        """Under the lock: drop every request there is and put ``error`` in
        its mailbox."""
        del self._waiting[:], self._waiting_kv[:]
        self.slots = [None] * len(self.slots)
        self._unread = None
        for box in list(self._mailboxes.values()):
            box.put(error)

    def shutdown(self) -> None:
        """Stop the loop and join it.  Every caller blocked in ``wait`` or
        ``stream_request`` gets a ``RuntimeError``, and so does every later
        one; ``step()`` by hand still works."""
        with self._wake:
            self._stopped = True
            self._wake.notify()
            loop = self._loop
        if loop is not None:
            loop.join()
        with self.locked():
            self._fail_all(RuntimeError("the engine is shut down"))

    def _take(self, request_id: int, deadline: float) -> list:
        """Block until the request's mailbox holds something or ``deadline``
        (``time.monotonic``) passes; returns EVERYTHING it holds: lists of
        tokens in step order, then perhaps the result.  Raises what the loop
        put there for an error, ``TimeoutError`` at the deadline."""
        box = self._mailboxes.get(request_id)
        if box is None:
            raise KeyError(f"request {request_id} is not this engine's, or "
                           "was collected or cancelled already")
        self._ensure_loop()
        left = deadline - time.monotonic()
        me = threading.get_ident()
        self._blocked.add(me)
        try:
            if left <= 0:
                raise queue.Empty
            items = [box.get(timeout=None if left == float("inf") else left)]
        except queue.Empty:
            raise TimeoutError(
                f"request {request_id} exceeded its timeout") from None
        finally:
            self._blocked.discard(me)
        with contextlib.suppress(queue.Empty):
            while True:
                items.append(box.get_nowait())
        for item in items:
            if isinstance(item, BaseException):
                raise item
        return items

    def _step_locked(self, jnp) -> List[dict]:
        c = self._counts
        admitted0, retired0 = c["admitted"], c["retired"]
        syncs0, overrun0 = c["host_syncs"], c["overrun_row_steps"]
        # Counts of the runs dispatched before this step: their programs
        # will have ended when this step has read the last step's tokens.
        late = len(self._unread_counts)
        with host_span("engine.step", seq=c["steps"]):
            firsts, stopped = self._admit(jnp)
            unread, self._unread = self._unread, None
            # Whoever holds a row has a decode to ride (``_seat``; the rows
            # given up by count, below).
            riding = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
            if riding:
                pos = np.zeros(self.cfg.max_batch_size, np.int32)
                for i, s in riding:
                    pos[i] = s.last_pos
                # What the step's attention reads of each full-extent cache:
                # the program bounds it by the same function of ``pos``.
                longest = int(pos.max())
                with host_span(
                        "engine.decode.dispatch", active=len(riding),
                        longest=longest,
                        read_positions=live_extent(
                            longest, self.cfg.max_seq_len),
                        cache_positions=self.cfg.max_seq_len,
                        live_positions=float(pos.sum()) / len(riding)):
                    logits, self.cache, counts = self._decode(
                        self.params, self.cache, self._feed, jnp.asarray(pos))
                    self._note_counts("decode", counts)
                c["decode_steps"] += 1
            with host_span("engine.sample", slots=len(riding)):
                if riding:
                    self._feed = self._sample(
                        logits, [(i, s.params) for i, s in riding])
                    self._unread = (self._feed, riding)
                    for i, s in riding:
                        s.sampled += 1
                        if self._spent(s, s.sampled):
                            # Known by count: the row is the next tenant's
                            # now, the slot is ``_unread``'s until its last
                            # token is on the host.
                            self.slots[i] = None
                # The device has this step queued; the host catches up
                # with the last one, whose vector was complete before this
                # step's decode could start, and then waits for this step's
                # prefills.  Callers wake only here, when the interpreter
                # is not needed to start the device.
                if unread is not None:
                    stopped += self._absorb(*unread)
                finished = self._settle(stopped)
                for tokens, rows in firsts:
                    finished += self._settle(self._absorb(tokens, rows))
            occupied, waiting = self.occupied(), self._n_waiting()
            admitted = c["admitted"] - admitted0
            retired = c["retired"] - retired0
            c["steps"] += 1
            c["occupied_slot_steps"] += occupied
            # What is only known at the end of the step: zero-length, last.
            with host_span("engine.counts", occupied=occupied,
                           waiting=waiting, admitted=admitted,
                           retired=retired, waiters=len(self._blocked),
                           host_syncs=c["host_syncs"] - syncs0,
                           overrun=c["overrun_row_steps"] - overrun0,
                           **self._fold_counts(late)):
                pass
        flight_recorder.record_llm_step(
            occupied, waiting, admitted, retired, self.cfg.max_batch_size)
        return finished

    def _settle(self, ended: List[_Slot]) -> List[dict]:
        """The slots that ``ended`` retire, then every live slot's new
        tokens go to its mailbox."""
        out = self._retire(ended) if ended else []
        self._deliver_tokens()
        return out

    def _retire(self, ended: List[_Slot]) -> List[dict]:
        """Each slot's result to its mailbox, and its row freed if it still
        holds one (a stop by value; a stop by count gave its row up when it
        was known)."""
        out = []
        with host_span("engine.retire"):
            for s in ended:
                gen = s.generated
                if gen and gen[-1] == self._stop_token(s):
                    gen = gen[:-1]
                result = {
                    "request_id": s.request_id,
                    "token_ids": gen,
                    "text": self.tokenizer.decode(gen),
                    "num_generated": len(s.generated),
                }
                box = self._mailboxes.get(s.request_id)
                if box is not None:
                    if not box.first_token_unix_ns:  # its first was its last
                        box.first_token_unix_ns = time.time_ns()
                    box.put(result)  # its caller wakes: no step is owed
                out.append(result)
            for i, s in enumerate(self.slots):
                if s is not None and s.done:
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
        what the decode steps among them counted under the family's names
        and what the prefills counted with ``prefill_`` before them, as
        ``stats()`` has them (``engine.counts``' attributes: one step late,
        because a step folds only runs dispatched before it, whose copies
        have arrived: no wait, no ``host_syncs``)."""
        folded: Dict[str, int] = {}
        for kind, counts in self._unread_counts[:n]:
            for name, value in counts.items():
                self._family_counts[kind][name] += int(value)
                name = name if kind == "decode" else "prefill_" + name
                folded[name] = folded.get(name, 0) + int(value)
        del self._unread_counts[:n]
        return folded

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
        """Anything queued, in a slot, or sampled and not yet read."""
        return bool(self._waiting) or bool(self._waiting_kv) or any(
            s is not None for s in self.slots
        ) or self._unread is not None

    # ------------------------------------------------------------- generate
    def cancel_request(self, request_id: int) -> None:
        """Drop a request wherever it is (queue, slot, finished results) —
        abandoned streams must not keep decoding or park results forever."""
        with self.locked(request_id):
            dropped = _drop_queued(self._waiting, request_id)
            dropped += _drop_queued(self._waiting_kv, request_id)
            # What a step in flight samples for it is dropped when read.
            for slot in self._held():
                if slot.request_id == request_id and not slot.done:
                    slot.done = True
                    dropped += 1
            self.slots = [None if s is not None and s.done else s
                          for s in self.slots]
            self._counts["cancelled"] += dropped
            self._mailboxes.pop(request_id, None)

    def generate_stream(self, prompt: str,
                        params: Optional[SamplingParams] = None,
                        timeout_s: float = 300.0):
        """Incremental generation: yields this request's text as the loop
        samples it, a delta for every step the consumer keeps up with.
        Concurrent streams (and batched generate calls) share the slot
        pool and the loop's steps."""
        yield from self.stream_request(
            self.add_request(prompt, params), timeout_s
        )

    def stream_request(self, request_id: int, timeout_s: float = 300.0):
        """Stream an ALREADY-QUEUED request's deltas (the disaggregated
        streaming path: the id came from add_request_from_kv, whose prompt
        was prefilled on another replica).  Blocks on the request's mailbox
        and never steps: each delta is everything the loop has put there
        since the last one, so one token a delta while the consumer keeps
        up, and more by themselves while it does not."""
        box = self._mailboxes.get(request_id)
        context = tracing.current_context()
        deltas = emitted = 0
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                tokens, done = [], None
                for item in self._take(request_id, deadline):
                    if isinstance(item, dict):
                        done = item
                    else:
                        tokens.extend(item)
                if done is not None:  # its ids hold the tail, stop cut off
                    tokens = done["token_ids"][emitted:]
                emitted += len(tokens)
                deltas += bool(tokens)
                text = self.tokenizer.decode(tokens)
                if text:
                    yield text
                if done is not None:
                    return
        finally:
            end = time.time()
            with self.locked(request_id):
                self._counts["stream_deltas"] += deltas
                self._counts["stream_delta_tokens"] += emitted
                # Timeout or abandoned consumer: release the slot/queue
                # entry.
                self.cancel_request(request_id)
            if box is not None:
                tracing.record_span(
                    "engine.stream", box.added, end,
                    {"request_id": request_id,
                     "admitted_unix_ns": box.admitted_unix_ns,
                     "first_token_unix_ns": box.first_token_unix_ns,
                     "deltas": deltas, "tokens": emitted},
                    context=context)

    def wait(self, request_ids: List[int],
             timeout_s: float = 300.0) -> List[dict]:
        """Block until the loop has finished every one of THIS caller's
        ``request_ids``; returns their results in that order.  Other
        callers' in-flight work (streams, other waits) shares the steps and
        delays nothing here.  Past ``timeout_s`` the requests are cancelled
        (slots and queue entries freed) and ``TimeoutError`` is raised."""
        deadline = time.monotonic() + timeout_s
        results = []
        try:
            for request_id in request_ids:
                done = None
                while done is None:
                    done = next(
                        (item for item in self._take(request_id, deadline)
                         if isinstance(item, dict)), None)
                results.append(done)
        except BaseException:
            for request_id in request_ids:
                self.cancel_request(request_id)
            raise
        for request_id in request_ids:  # collected
            self._mailboxes.pop(request_id, None)
        return results

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
