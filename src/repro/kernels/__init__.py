from repro.kernels.ops import gqa_flash_attention, ssd_mixer, fused_swiglu
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.swiglu_matmul import swiglu_matmul
from repro.kernels import ref

__all__ = [
    "gqa_flash_attention",
    "ssd_mixer",
    "fused_swiglu",
    "flash_attention",
    "ssd_scan",
    "swiglu_matmul",
    "ref",
]
