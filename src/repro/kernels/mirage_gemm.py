"""Pallas TPU kernel: fused Mirage GEMM (BFP quantize + matmul + f32 accumulate).

This is the TPU-native realization of the paper's dataflow steps 2-9 in one
VMEM round trip: each (block_m x block_k) activation tile and (block_k x
block_n) weight tile are BFP-quantized *in VMEM* (groups of g along K), the
power-of-two group scales are folded back into the mantissas (exact), and the
MXU contracts the block with f32 accumulation across the K grid dimension.

Compared to the photonic MMVMU, the "16-wide modular dot + CRT per group"
becomes "whole-block MXU dot with folded scales" — value-identical under the
paper's own Eq. 10 no-overflow invariant (see DESIGN.md Section 8.1), because
every per-group partial product is exactly representable in the f32
accumulator. Block shapes are MXU-aligned (multiples of 128 where possible)
and contain whole BFP groups (block_k % g == 0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import resolve_interpret
from repro.kernels.bfp_quantize import (_quantize_block, block_size,
                                        check_group, lane_multiple, round_up)


def _kernel(x_ref, w_ref, o_ref, *, b_m: int, g: int, rounding: str,
            compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = _quantize_block(x_ref[...].astype(jnp.float32), b_m, g, rounding)
    # weights: the contraction dim is axis 0, so groups run down sublanes
    wq = _quantize_block(w_ref[...].astype(jnp.float32), b_m, g, rounding,
                         axis=0)
    o_ref[...] += jnp.dot(
        xq.astype(compute_dtype), wq.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("b_m", "g", "rounding", "block_m", "block_n", "block_k",
                     "compute_dtype", "interpret"),
)
def mirage_gemm_pallas(
    x: jax.Array,
    w: jax.Array,
    b_m: int = 4,
    g: int = 16,
    rounding: str = "nearest",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    compute_dtype: str = "float32",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``x @ w`` with fused BFP(b_m, g) quantization. x: (..., K), w: (K, N).

    ``interpret=None`` follows the platform
    (:func:`repro.core.precision.resolve_interpret`)."""
    check_group(g)
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    K = orig_shape[-1]
    N = w.shape[1]
    assert w.shape[0] == K, (x.shape, w.shape)
    xf = x.reshape(-1, K).astype(jnp.float32)
    M = xf.shape[0]

    # K blocks are lanes of the x tile and sublanes of the w tile: whole
    # groups on whole 128-lane rows
    kp = round_up(K, lane_multiple(g))
    bm_ = block_size(M, block_m, 8)
    bn = block_size(N, block_n, 128)
    bk = block_size(kp, block_k, lane_multiple(g))

    pm = round_up(M, bm_) - M
    pn = round_up(N, bn) - N
    pk = round_up(kp, bk) - K
    if pm or pk:
        xf = jnp.pad(xf, ((0, pm), (0, pk)))
    wf = w.astype(jnp.float32)
    if pk or pn:
        wf = jnp.pad(wf, ((0, pk), (0, pn)))

    grid = (xf.shape[0] // bm_, wf.shape[1] // bn, xf.shape[1] // bk)
    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, b_m=b_m, g=g, rounding=rounding,
                          compute_dtype=cdt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xf.shape[0], wf.shape[1]), jnp.float32),
        interpret=interpret,
    )(xf, wf)
    return out[:M, :N].reshape(orig_shape[:-1] + (N,))
