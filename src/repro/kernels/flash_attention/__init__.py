from .ops import block_sizes, flash_attention, supports
