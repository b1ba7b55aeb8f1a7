"""Assigned architecture configs (``--arch <id>``). Importing this package
registers all of them; each module holds exactly one architecture with the
exact published shape, plus ``tiny()`` reductions for smoke tests."""

from . import (falcon_mamba_7b, granite_moe_1b_a400m, grok_1_314b,
               hymba_1_5b, jamba2_3b, minicpm3_4b, qwen2_5_32b, qwen2_vl_7b,
               qwen3_4b, seamless_m4t_large_v2, yi_34b)
from .tiny import tiny_config

ALL_ARCHS = [
    "hymba-1.5b", "granite-moe-1b-a400m", "grok-1-314b", "yi-34b",
    "minicpm3-4b", "qwen3-4b", "qwen2.5-32b", "qwen2-vl-7b",
    "seamless-m4t-large-v2", "falcon-mamba-7b",
]

__all__ = ["ALL_ARCHS", "tiny_config"]
