from .flash_attn import flash_attention
from .ops import flash_mha, flash_mha_bias
from .ref import flash_attention_ref

__all__ = ["flash_attention", "flash_mha", "flash_mha_bias",
           "flash_attention_ref"]
