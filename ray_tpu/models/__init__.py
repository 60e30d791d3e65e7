import dataclasses as _dataclasses
import functools as _functools
from typing import Any as _Any, Callable as _Callable, Optional as _Optional

from .gpt2 import (  # noqa: F401
    GPT2Config,
    gpt2_apply,
    gpt2_hidden,
    gpt2_init,
    gpt2_loss,
    gpt2_param_axes,
)
from .gpt2_decode import (  # noqa: F401
    gpt2_decode_step,
    gpt2_init_cache,
    gpt2_prefill,
)
from .granite_h import (  # noqa: F401
    GraniteHConfig,
    granite_h_apply,
    granite_h_init,
    granite_h_loss,
    granite_h_param_axes,
)
from .granite_h_decode import (  # noqa: F401
    granite_h_decode_step,
    granite_h_init_cache,
    granite_h_prefill,
)
from .laguna import (  # noqa: F401
    LagunaConfig,
    laguna_apply,
    laguna_init,
    laguna_loss,
    laguna_param_axes,
)
from .laguna_decode import (  # noqa: F401
    laguna_decode_step,
    laguna_init_cache,
    laguna_prefill,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    llama_apply,
    llama_init,
    llama_loss,
    llama_param_axes,
)
from .llama_decode import (  # noqa: F401
    llama_decode_step,
    llama_init_cache,
    llama_prefill,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    kimi_linear_apply,
    kimi_linear_init,
    kimi_linear_loss,
    kimi_linear_param_axes,
)
from .kimi_linear_decode import (  # noqa: F401
    kimi_linear_decode_step,
    kimi_linear_init_cache,
    kimi_linear_prefill,
)
from .longcat import (  # noqa: F401
    LongcatConfig,
    longcat_apply,
    longcat_init,
    longcat_loss,
    longcat_param_axes,
)
from .longcat_decode import (  # noqa: F401
    longcat_decode_step,
    longcat_init_cache,
    longcat_prefill,
)
from .mimo_v2 import (  # noqa: F401
    MimoV2Config,
    mimo_v2_apply,
    mimo_v2_init,
    mimo_v2_loss,
    mimo_v2_param_axes,
)
from .mimo_v2_decode import (  # noqa: F401
    mimo_v2_decode_step,
    mimo_v2_init_cache,
    mimo_v2_prefill,
)
from .minicpm_sala import (  # noqa: F401
    MinicpmSalaConfig,
    minicpm_sala_apply,
    minicpm_sala_init,
    minicpm_sala_loss,
    minicpm_sala_param_axes,
)
from .minicpm_sala_decode import (  # noqa: F401
    minicpm_sala_decode_step,
    minicpm_sala_init_cache,
    minicpm_sala_prefill,
)
from .mistral4 import (  # noqa: F401
    Mistral4Config,
    mistral4_apply,
    mistral4_init,
    mistral4_loss,
    mistral4_param_axes,
)
from .mistral4_decode import (  # noqa: F401
    mistral4_decode_step,
    mistral4_init_cache,
    mistral4_prefill,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    nemotron_h_apply,
    nemotron_h_init,
    nemotron_h_loss,
    nemotron_h_param_axes,
)
from .nemotron_h_decode import (  # noqa: F401
    nemotron_h_decode_step,
    nemotron_h_init_cache,
    nemotron_h_prefill,
)
from .olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig,
    olmo_hybrid_apply,
    olmo_hybrid_init,
    olmo_hybrid_loss,
    olmo_hybrid_param_axes,
)
from .olmo_hybrid_decode import (  # noqa: F401
    olmo_hybrid_decode_step,
    olmo_hybrid_init_cache,
    olmo_hybrid_prefill,
)


@_dataclasses.dataclass(frozen=True)
class ModelFamily:
    """Uniform train + serve surface over a model architecture — what makes
    the LLM engine model-agnostic (round-1 finding: the engine was
    hard-wired to GPT-2 while llama sat unused; reference analog: vLLM's
    model registry consumed by ray's engine wrapper,
    ``python/ray/llm/_internal/serve/engines/vllm/vllm_models.py``)."""

    name: str
    init: _Callable  # (key, cfg) -> params
    apply: _Callable  # (params, tokens, cfg, mesh=None) -> logits
    loss: _Callable  # (params, tokens, cfg, mesh=None, ...) -> scalar
    param_axes: _Callable  # () -> logical sharding tree
    init_cache: _Callable  # (cfg, batch, max_len) -> cache
    # The cache is the family's: a pytree whose every leaf has the slot
    # (batch) axis at axis 1; nothing else of its layout is shared.  Both
    # return (logits, cache).
    prefill: _Callable  # (params, tokens, lengths, cache, cfg)
    decode_step: _Callable  # (params, tokens, pos, cache, cfg)
    # Optional twins that return (logits, cache, counts): a dict of int32
    # scalars the program counted (routing choices, ...).  The LLM engine
    # runs these where a family has them, reads the counts one step late
    # and sums them in stats().
    prefill_counted: _Optional[_Callable] = None
    decode_step_counted: _Optional[_Callable] = None


_FAMILIES = {}


def register_model_family(config_cls, family: ModelFamily) -> None:
    _FAMILIES[config_cls] = family


def model_family(cfg: _Any) -> ModelFamily:
    """Resolve the ModelFamily for a model config instance."""
    for cls, fam in _FAMILIES.items():
        if isinstance(cfg, cls):
            return fam
    raise TypeError(
        f"no registered model family for config type {type(cfg).__name__}"
    )
from .mlp import mlp_apply, mlp_init  # noqa: F401
from .resnet import (  # noqa: F401
    ResNetConfig,
    resnet_apply,
    resnet_init,
    resnet_loss,
    resnet_param_axes,
)
from .sampling import (  # noqa: F401
    sample_logits,
    sample_logits_greedy,
    sample_logits_rows,
)
from .vit import ViTConfig, vit_apply, vit_init, vit_loss, vit_param_axes  # noqa: F401


register_model_family(
    GPT2Config,
    ModelFamily(
        name="gpt2",
        init=gpt2_init,
        apply=gpt2_apply,
        loss=gpt2_loss,
        param_axes=gpt2_param_axes,
        init_cache=gpt2_init_cache,
        prefill=gpt2_prefill,
        decode_step=gpt2_decode_step,
    ),
)
register_model_family(
    LlamaConfig,
    ModelFamily(
        name="llama",
        init=llama_init,
        apply=llama_apply,
        loss=llama_loss,
        param_axes=llama_param_axes,
        init_cache=llama_init_cache,
        prefill=llama_prefill,
        decode_step=llama_decode_step,
    ),
)
register_model_family(
    LongcatConfig,
    ModelFamily(
        name="longcat",
        init=longcat_init,
        apply=longcat_apply,
        loss=longcat_loss,
        param_axes=longcat_param_axes,
        init_cache=longcat_init_cache,
        prefill=longcat_prefill,
        decode_step=longcat_decode_step,
        prefill_counted=_functools.partial(longcat_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            longcat_decode_step, with_counts=True),
    ),
)
register_model_family(
    NemotronHConfig,
    ModelFamily(
        name="nemotron_h",
        init=nemotron_h_init,
        apply=nemotron_h_apply,
        loss=nemotron_h_loss,
        param_axes=nemotron_h_param_axes,
        init_cache=nemotron_h_init_cache,
        prefill=nemotron_h_prefill,
        decode_step=nemotron_h_decode_step,
        prefill_counted=_functools.partial(
            nemotron_h_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            nemotron_h_decode_step, with_counts=True),
    ),
)
register_model_family(
    MimoV2Config,
    ModelFamily(
        name="mimo_v2",
        init=mimo_v2_init,
        apply=mimo_v2_apply,
        loss=mimo_v2_loss,
        param_axes=mimo_v2_param_axes,
        init_cache=mimo_v2_init_cache,
        prefill=mimo_v2_prefill,
        decode_step=mimo_v2_decode_step,
        prefill_counted=_functools.partial(mimo_v2_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            mimo_v2_decode_step, with_counts=True),
    ),
)
register_model_family(
    Mistral4Config,
    ModelFamily(
        name="mistral4",
        init=mistral4_init,
        apply=mistral4_apply,
        loss=mistral4_loss,
        param_axes=mistral4_param_axes,
        init_cache=mistral4_init_cache,
        prefill=mistral4_prefill,
        decode_step=mistral4_decode_step,
        prefill_counted=_functools.partial(mistral4_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            mistral4_decode_step, with_counts=True),
    ),
)
register_model_family(
    LagunaConfig,
    ModelFamily(
        name="laguna",
        init=laguna_init,
        apply=laguna_apply,
        loss=laguna_loss,
        param_axes=laguna_param_axes,
        init_cache=laguna_init_cache,
        prefill=laguna_prefill,
        decode_step=laguna_decode_step,
        prefill_counted=_functools.partial(laguna_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            laguna_decode_step, with_counts=True),
    ),
)
register_model_family(
    OlmoHybridConfig,
    ModelFamily(
        name="olmo_hybrid",
        init=olmo_hybrid_init,
        apply=olmo_hybrid_apply,
        loss=olmo_hybrid_loss,
        param_axes=olmo_hybrid_param_axes,
        init_cache=olmo_hybrid_init_cache,
        prefill=olmo_hybrid_prefill,
        decode_step=olmo_hybrid_decode_step,
        prefill_counted=_functools.partial(
            olmo_hybrid_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            olmo_hybrid_decode_step, with_counts=True),
    ),
)
register_model_family(
    GraniteHConfig,
    ModelFamily(
        name="granite_h",
        init=granite_h_init,
        apply=granite_h_apply,
        loss=granite_h_loss,
        param_axes=granite_h_param_axes,
        init_cache=granite_h_init_cache,
        prefill=granite_h_prefill,
        decode_step=granite_h_decode_step,
        prefill_counted=_functools.partial(
            granite_h_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            granite_h_decode_step, with_counts=True),
    ),
)
register_model_family(
    MinicpmSalaConfig,
    ModelFamily(
        name="minicpm_sala",
        init=minicpm_sala_init,
        apply=minicpm_sala_apply,
        loss=minicpm_sala_loss,
        param_axes=minicpm_sala_param_axes,
        init_cache=minicpm_sala_init_cache,
        prefill=minicpm_sala_prefill,
        decode_step=minicpm_sala_decode_step,
        prefill_counted=_functools.partial(
            minicpm_sala_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            minicpm_sala_decode_step, with_counts=True),
    ),
)
register_model_family(
    KimiLinearConfig,
    ModelFamily(
        name="kimi_linear",
        init=kimi_linear_init,
        apply=kimi_linear_apply,
        loss=kimi_linear_loss,
        param_axes=kimi_linear_param_axes,
        init_cache=kimi_linear_init_cache,
        prefill=kimi_linear_prefill,
        decode_step=kimi_linear_decode_step,
        prefill_counted=_functools.partial(
            kimi_linear_prefill, with_counts=True),
        decode_step_counted=_functools.partial(
            kimi_linear_decode_step, with_counts=True),
    ),
)
