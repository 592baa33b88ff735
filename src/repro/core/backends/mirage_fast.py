"""mirage_fast: BFP-quantize, fold scales into mantissas, one MXU matmul.

Value-exact w.r.t. the faithful path whenever f32 accumulation is exact
(property-tested). The weight side quantizes in place along K via
``bfp_quantize_contract`` — bit-identical values to the seed's
transpose/quantize/transpose-back dance, without the two (K, N) copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.core.backends.base import register_fn


def _fold_x(x, policy):
    """Quantize-and-fold activations along the contraction dim -> (..., Kpad)."""
    with jax.named_scope("quantize_x"):
        t = bfp.bfp_quantize(x, policy.b_m, policy.g, policy.rounding)
        xg = t.mantissa * t.scale
        return xg.reshape(xg.shape[:-2] + (xg.shape[-2] * xg.shape[-1],))


def _dot_dtype(policy):
    """The folded-scale matmul's operand dtype. BFP(b_m <= 6) values are
    exact in bfloat16, so ``compute_dtype="bfloat16"`` changes no value,
    only bytes and MXU passes. The CPU runtime has no bf16 x bf16 -> f32
    dot kernel for every shape, so there the same values go in as f32."""
    if policy.compute_dtype != "bfloat16" or jax.default_backend() == "cpu":
        return jnp.float32
    return jnp.bfloat16


@register_fn("mirage_fast",
             description="BFP quantize -> fold scales -> one MXU matmul",
             supports_weight_stationary=True)
def _matmul_mirage_fast(x, w, policy, *, key=None):
    if policy.use_pallas:
        from repro.kernels import ops as kops
        return kops.mirage_matmul_fused(x, w, policy)
    dt = _dot_dtype(policy)
    xq = _fold_x(x, policy)                    # (..., Kpad)
    with jax.named_scope("quantize_w"):
        if policy.assume_quantized_weights:
            # weight operand already on the BFP grid (weight-stationary
            # quant: quantized once per step, reused across
            # microbatches/remat/transpose)
            wq = w
            if xq.shape[-1] != w.shape[0]:     # padding from x grouping
                wq = jnp.pad(w, ((0, xq.shape[-1] - w.shape[0]), (0, 0)))
        else:
            qw, sw = bfp.bfp_quantize_contract(w, policy.b_m, policy.g,
                                               policy.rounding)
            wq = (qw * sw).reshape(-1, w.shape[-1])  # (Kpad, N)
            if wq.shape[0] != xq.shape[-1]:
                wq = wq[: xq.shape[-1]]
    with jax.named_scope("matmul"):
        return jnp.matmul(xq.astype(dt), wq.astype(dt),
                          preferred_element_type=jnp.float32)
