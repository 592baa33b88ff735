"""Jit'd public wrappers around the Pallas kernels.

``policy.interpret`` (default ``None``) follows the platform: the kernels
run interpreted on CPU and compiled on TPU, and interpret mode on a TPU is
an error (``repro.core.precision.resolve_interpret``). Each wrapper handles
padding/reshaping so callers can pass arbitrary ranks; the kernels see
MXU-aligned 2-D blocks.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.precision import MiragePolicy
from repro.kernels.bfp_quantize import bfp_fake_quant_pallas
from repro.kernels.mirage_gemm import mirage_gemm_pallas
from repro.kernels.rns_matmul import (rns_matmul_pallas,
                                      rns_matmul_pallas_channel)


def bfp_fake_quant(x: jax.Array, policy: MiragePolicy) -> jax.Array:
    return bfp_fake_quant_pallas(
        x, b_m=policy.b_m, g=policy.g, rounding=policy.rounding,
        interpret=policy.interpret)


def mirage_matmul_fused(x: jax.Array, w: jax.Array,
                        policy: MiragePolicy) -> jax.Array:
    """Fused BFP-quantize + GEMM (paper dataflow steps 2-9 in one kernel)."""
    return mirage_gemm_pallas(
        x, w, b_m=policy.b_m, g=policy.g, rounding=policy.rounding,
        compute_dtype=policy.compute_dtype, interpret=policy.interpret)


def rns_residue_matmul(x_res: jax.Array, w_res: jax.Array,
                       moduli: Tuple[int, ...],
                       interpret: Optional[bool] = None) -> jax.Array:
    return rns_matmul_pallas(x_res, w_res, tuple(moduli), interpret=interpret)


def rns_group_matmul(x_res: jax.Array, w_res: jax.Array,
                     moduli: Tuple[int, ...],
                     interpret: Optional[bool] = None) -> jax.Array:
    """Group-batched residue GEMM through the Pallas kernel.

    x_res: (n_mod, G, M, g), w_res: (n_mod, G, g, N) -> (n_mod, G, M, N).

    The kernel's grid is modulus-major with the modulus values scalar-
    prefetched into SMEM, so one compiled kernel serves any number of
    "moduli" — flattening the (modulus, group) axes into n_mod * G slots
    with each modulus repeated G times executes ALL per-group modular GEMMs
    in a single pallas_call.
    """
    nm, G, M, g = x_res.shape
    N = w_res.shape[-1]
    xf = x_res.reshape(nm * G, M, g)
    wf = w_res.reshape(nm * G, g, N)
    flat_moduli = tuple(m for m in moduli for _ in range(G))
    res = rns_matmul_pallas(xf, wf, flat_moduli, interpret=interpret)
    return res.reshape(nm, G, M, N)


def rns_group_matmul_channel(x_res: jax.Array, w_res: jax.Array,
                             moduli: Tuple[int, ...],
                             noise: jax.Array,
                             adc_bits=None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Group-batched residue GEMM with the readout channel fused in-kernel.

    Same (modulus, group)-flattened grid as :func:`rns_group_matmul`, but
    each accumulated residue block gets detector noise + ADC re-gridding
    applied in the kernel epilogue (``rns_matmul_pallas_channel``). ``noise``
    is (n_mod, G, M, N) f32, pre-scaled to the per-modulus detector sigmas
    (zeros = noiseless readout).
    """
    nm, G, M, g = x_res.shape
    N = w_res.shape[-1]
    xf = x_res.reshape(nm * G, M, g)
    wf = w_res.reshape(nm * G, g, N)
    nzf = noise.reshape(nm * G, M, N)
    flat_moduli = tuple(m for m in moduli for _ in range(G))
    res = rns_matmul_pallas_channel(xf, wf, flat_moduli, nzf,
                                    adc_bits=adc_bits, interpret=interpret)
    return res.reshape(nm, G, M, N)
