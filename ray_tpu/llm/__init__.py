"""``ray_tpu.llm`` — LLM serving and batch inference.

Reference: ray ``python/ray/llm/`` — there a vLLM engine wrapper + OpenAI
server + batch processors; here the engine itself is TPU-native JAX
(``JaxLLMEngine``: a fixed pool of cache slots over any registered model
family), the server is a Serve app, and batch inference rides the Data
layer's actor pools.
"""

from .engine import EngineConfig, JaxLLMEngine, SamplingParams  # noqa: F401
from .serve_app import build_disagg_openai_app, build_openai_app  # noqa: F401
from .batch import build_llm_processor  # noqa: F401
from .tokenizer import ByteTokenizer  # noqa: F401
from .disagg import (  # noqa: F401
    DecodeReplica,
    DisaggRouter,
    PrefillEngine,
    PrefillReplica,
)
