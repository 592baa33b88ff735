"""Baseline GEMM backends the paper compares against (Table III)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backends.base import register_fn


def _cast_matmul(x, w, dtype):
    """Operands cast to ``dtype`` (the quantize phases), one f32-accumulated
    matmul."""
    with jax.named_scope("quantize_x"):
        x = x.astype(dtype)
    with jax.named_scope("quantize_w"):
        w = w.astype(dtype)
    with jax.named_scope("matmul"):
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)


@register_fn("fp32", description="plain f32 matmul (paper FP32 baseline)",
             quantized=False)
def _matmul_fp32(x, w, policy, *, key=None):
    return _cast_matmul(x, w, jnp.float32)


@register_fn("bf16", description="bfloat16 matmul, f32 accumulation",
             quantized=False)
def _matmul_bf16(x, w, policy, *, key=None):
    return _cast_matmul(x, w, jnp.bfloat16)


@register_fn("int8", description="per-tensor symmetric int8 systolic baseline")
def _matmul_int8(x, w, policy, *, key=None):
    with jax.named_scope("quantize_x"):
        sx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        qx = jnp.clip(jnp.round(x / sx), -127, 127)
    with jax.named_scope("quantize_w"):
        sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 127.0
        qw = jnp.clip(jnp.round(w / sw), -127, 127)
    with jax.named_scope("matmul"):
        acc = jnp.matmul(qx, qw, preferred_element_type=jnp.float32)
        return acc * (sx * sw)
