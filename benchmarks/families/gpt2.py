"""Family ``gpt2``: what the job kinds need to run a GPT-2 configuration,
found by the ``family`` key of its file under ``configs/``.

A family gives the job kinds the program's own pieces (config class, init,
loss) next to the benchmark's (the plain float32 reference under
``reference/``, the operation count under ``lib/flops.py``).  A new family
is a new file here and its reference; no job kind names a family.

``train_dp`` reads: ``config``, ``init``, ``loss``, ``reference_loss``,
``train_flops_per_token``.
"""

from __future__ import annotations

from benchmarks.lib import flops
from benchmarks.reference.gpt2_ref import gpt2_ref_loss
from ray_tpu.models import GPT2Config, gpt2_init, gpt2_loss


def config(model: dict) -> GPT2Config:
    return GPT2Config(**model)


def init(key, cfg: GPT2Config):
    return gpt2_init(key, cfg)


def loss(params, tokens, cfg: GPT2Config):
    return gpt2_loss(params, tokens, cfg)


def reference_loss(params, tokens, cfg: GPT2Config):
    return gpt2_ref_loss(params, tokens, cfg.n_head)


train_flops_per_token = flops.gpt2_train_flops_per_token
