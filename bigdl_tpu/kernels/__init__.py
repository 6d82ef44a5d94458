"""Hand-written Pallas TPU kernels for the framework's hot ops.

The compute path is XLA-first (SURVEY §1: let the compiler fuse), but a few
ops benefit from explicit tiling/fusion beyond what XLA does automatically.
Those live here, each with an interpret-mode path so the CPU test suite
exercises the same kernel code the TPU runs.
"""
from .flash_attention import (flash_attention_fused, flash_attention_qkv,
                              flash_attention_rows)
from .paged_attention import paged_decode_attention

__all__ = ["flash_attention_fused", "flash_attention_qkv",
           "flash_attention_rows", "paged_decode_attention"]
