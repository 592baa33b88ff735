"""Device time per training step in the GEMM backend's operand encode: BFP
group max, divide, round, clip and scale fold (``mirage``), the bfloat16
cast (``bf16``), under the scopes ``quantize_x``, ``quantize_w`` and
``prequantize`` that ``core/backends`` and ``runtime/trainer`` open, in the
forward, remat and both backward GEMMs. Rounding that XLA fuses into a
matmul's fusion counts as the matmul (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "gemm_quantize")
