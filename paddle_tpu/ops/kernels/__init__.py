"""Pallas TPU kernels for the hot ops (flash attention, fused norms, rope).

Analog of the reference's fused GPU kernels (paddle/phi/kernels/fusion/gpu/)
— here implemented as Pallas TPU kernels with XLA-composite fallbacks on
non-TPU backends.
"""

from . import flash_attention  # noqa: F401
from . import moe_gemm_pallas, rope_pallas  # noqa: F401
