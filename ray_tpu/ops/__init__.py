from .attention import (  # noqa: F401
    flash_attention,
    flash_attention_packed,
    reference_attention,
)
from .decode_attention import decode_attention  # noqa: F401
