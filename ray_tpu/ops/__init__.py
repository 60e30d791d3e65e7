from .attention import flash_attention, reference_attention  # noqa: F401
from .decode_attention import decode_attention  # noqa: F401
