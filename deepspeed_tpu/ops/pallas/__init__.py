from .flash_attention import flash_attention, dense_attention
