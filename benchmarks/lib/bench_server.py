"""The serving cells' deployment and weights, from the benchmark's own files.

``BenchLLMServer`` is ``ray_tpu.llm``'s ``LLMServer`` by subclassing: the
request path is the program's, untouched, and the job traces it and reads its
engine's counters through ``LLMServer``'s own ``start_profile`` /
``stop_profile`` / ``engine_stats``.  It adds what the program does not offer:

* a tokenizer whose ``decode`` renders every id as one visible character, so
  that each generated token reaches the client (``ByteTokenizer.decode`` drops
  ids >= 256: with random weights over 32,768 rows nearly all of them);
* ``check_reference``: prefill then decode through a cache against the plain
  float32 forward, on the live weights;
* ``counters``: compilations in this process, the device's memory counters.

The weights come from the family's ``load_params`` (``families/<family>.py``)
through ``EngineConfig.param_loader``.
"""

from __future__ import annotations

import importlib

from benchmarks.lib.device import device_memory
from ray_tpu.llm.serve_app import LLMServer as _LLMServerDeployment
from ray_tpu.llm.tokenizer import ByteTokenizer

_LLMServer = _LLMServerDeployment.func_or_class
VISIBLE_BASE = 0x100

# Root-mean-square of (program logit - float32 reference logit) over the
# vocabulary, as a share of the reference logits' standard deviation at that
# position.  The program rounds to bf16 (2^-9 = 0.2 % on average) after every
# operation of two blocks and the head, some fifteen roundings: about 1 %
# expected, 1.3-1.8 % measured on the chip at d 4096 (PERF.md; the largest
# single error over 32,768 logits is 4-5 times that, 5-7 %, which is why the
# first form of this check, a maximum held to 4 %, failed on the chip).
# What the limit tells apart is pinned by selftest.py at d 512 on the CPU:
# bf16 passes (0.7 %); weights rounded to float8_e4m3 fail (11-12 %); a
# decode position off by one fails narrowly (3.5-3.7 %: random weights attend
# almost evenly, so one step of rotation moves little).  It is a check of
# the mathematics and of coarse rounding in the weights.  It does not see
# rounding that costs under about 1 % a matrix product, and it sees the
# blocks at all only because at d 4096 they add more to the logits than the
# embedding does (at d 64 they add a tenth, and no fault in a block shows).
LOGIT_TOL = 0.03


class VisibleTokenizer(ByteTokenizer):
    def decode(self, ids):
        return "".join(chr(VISIBLE_BASE + i) for i in ids)


def ids_of(text: str):
    return [ord(c) - VISIBLE_BASE for c in text]


def logit_errors(got, want) -> dict:
    """``got``, ``want``: lists of [V] logits, position by position."""
    import numpy as np

    errs = [float(np.sqrt(((g - w) ** 2).mean()) / w.std())
            for g, w in zip(got, want)]
    worst = [float(np.abs(g - w).max() / w.std()) for g, w in zip(got, want)]
    return {"rel_errs": errs, "worst_logit": worst, "tolerance": LOGIT_TOL,
            "ok": bool(max(errs) <= LOGIT_TOL)}


def through_the_cache(fam, params, mcfg, toks, prompt_len: int, steps: int,
                      shift: int = 0):
    """The program's ``prefill`` of ``toks[:, :prompt_len]`` then ``steps`` x
    ``decode_step`` through a cache: the logits that predict positions
    ``prompt_len .. prompt_len + steps``.  ``shift`` moves the decode
    positions: the self-test's fault."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cache = fam.init_cache(mcfg, 1, prompt_len + steps + 1 + shift)
    logits, cache = jax.jit(
        lambda p, t, c: fam.prefill(p, t, jnp.asarray([prompt_len]), c, mcfg)
    )(params, jnp.asarray(toks[:, :prompt_len]), cache)
    got = [np.asarray(logits[0], np.float32)]
    decode = jax.jit(lambda p, t, pos, c: fam.decode_step(p, t, pos, c, mcfg))
    for i in range(steps):
        pos = prompt_len + i
        logits, cache = decode(params, jnp.asarray(toks[:, pos]),
                               jnp.asarray([pos + shift]), cache)
        got.append(np.asarray(logits[0], np.float32))
    return got


class BenchLLMServer(_LLMServer):
    def __init__(self, engine_cfg, model_name, family: str):
        import jax

        self._family = importlib.import_module("benchmarks.families." + family)
        self._memory_at_start = device_memory()
        self._compiles = 0

        def on_duration(name, *_a, **_kw):
            if name.endswith("backend_compile_duration"):
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        super().__init__(engine_cfg, model_name)
        self.engine.tokenizer = VisibleTokenizer()

    def counters(self) -> dict:
        return {"compiles": self._compiles,
                "memory_at_start": self._memory_at_start,
                "memory_stats": device_memory()}

    def check_reference(self, seed: int, prompt_len: int = 64,
                        steps: int = 3, layers: int = 2) -> dict:
        """First ``layers`` layers of the live weights: the program's
        ``prefill`` then ``steps`` x ``decode_step`` through a cache, against
        one plain float32 forward over the same tokens.  Logits, not tokens.
        These are the family's functions at one row, not the engine's
        compiled programs: those are held to agree with themselves
        (streamed = unary = sixteen at once) by the job."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        eng = self.engine
        mcfg = dataclasses.replace(eng.cfg.model, n_layer=layers)
        params = dict(eng.params, blocks=jax.tree.map(
            lambda a: a[:layers], eng.params["blocks"]))
        toks = np.random.default_rng(seed).integers(
            0, mcfg.vocab_size, (1, prompt_len + steps), dtype=np.int32)
        got = through_the_cache(eng.family, params, mcfg, toks, prompt_len,
                                steps)
        ref = np.asarray(jax.jit(
            lambda p, t: self._family.reference_logits(p, t, mcfg)
        )(params, jnp.asarray(toks)))[0]
        return logit_errors(
            got, [ref[prompt_len - 1 + i] for i in range(steps + 1)])
