"""The one traffic generator: a mix's data file + a seed -> the requests.

A pure function of ``(mix, seed)``.  The *sizes* of a mix are a fixed
population drawn once from ``population_seed``, in a fixed order; ``seed``
only says where in that round the clients start, and fills in the prompt
bytes.  Clients walk the list round and round, so every seed offers the same
lengths in the same cyclic order.  The order is part of the work: shuffled by
the seed (until PR 32) it set a window's tails, and two seeds differed by
five to ten times what two runs of one seed do (which long prompts fall into
the same lock round; PERF.md PR 32).
"""

from __future__ import annotations

import math
import random
from typing import List

PRINTABLE = "abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789,.;"


def _draw(spec: dict, rng: random.Random) -> int:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    return int(min(max(round(x), spec["min"]), spec["max"]))


def sizes(mix: dict) -> List[tuple]:
    """The mix's population of (prompt_tokens, output_tokens), seed-free."""
    rng = random.Random(mix["population_seed"])
    return [(_draw(mix["prompt_tokens"], rng), _draw(mix["output_tokens"], rng))
            for _ in range(mix["population"])]


def _text(n_chars: int, rng: random.Random) -> str:
    return "".join(rng.choices(PRINTABLE, k=n_chars))


def requests(mix: dict, seed: int) -> List[dict]:
    """Requests in send order.  ``prompt_tokens`` counts the byte tokenizer's
    BOS, so a prompt of n tokens is n - 1 ASCII characters."""
    rng = random.Random(seed)
    pop = sizes(mix)
    start = rng.randrange(len(pop))
    pop = pop[start:] + pop[:start]
    return [{"prompt": _text(n_prompt - 1, rng), "prompt_tokens": n_prompt,
             "max_tokens": n_out} for n_prompt, n_out in pop]
