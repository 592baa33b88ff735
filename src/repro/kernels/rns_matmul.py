"""Pallas TPU kernel: per-modulus residue GEMM (modular matmul).

The photonic MMVMU accumulates phase mod 2*pi/m per MAC; digitally the modulo
is a ring homomorphism, so the kernel accumulates exact integer partial dots
per K-block (kept below the f32 exact-integer window 2^24) and reduces
``mod m`` once per block, keeping the running accumulator in [0, m). This
preserves the paper's invariant that no stored value ever exceeds
ceil(log2 m) bits of information outside the accumulator.

Grid: (modulus, M blocks, N blocks, K blocks). The modulus values are
scalar-prefetched into SMEM and indexed by the first grid axis, so one
compiled kernel serves the whole moduli set.

``rns_matmul_pallas_channel`` is the analog-channel variant: the readout
side of the channel (SNR-parameterized detector noise + ADC re-gridding,
``repro.analog.channel``) is applied at **residue granularity inside the
kernel epilogue** — on the last K step the accumulated residue block gets
the pre-sampled, pre-scaled Gaussian phase noise added, is re-quantized to
the nearest phase level, wrapped mod m, and re-gridded onto the ADC levels,
all while the block is still VMEM-resident. The noise tensor is sampled
*outside* with the caller's PRNG key (``gemm.noise_key_scope`` plumbing),
so the kernel stays deterministic per key and bit-identical to the jnp
channel path (``channel.phase_noise`` + ``channel.converter_quantize``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import resolve_interpret
from repro.kernels.bfp_quantize import block_size, round_up


def _blocks(M: int, K: int, N: int, moduli, block_m: int, block_n: int,
            block_k: int):
    """(8, 128)-aligned blocks; K blocks also keep every block-partial dot
    exactly representable in f32 (block_k * (m-1)^2 < 2^24)."""
    exact_cap = (2**24) // max(1, (max(moduli) - 1) ** 2)
    return (block_size(M, block_m, 8),
            block_size(K, min(block_k, exact_cap), min(128, exact_cap)),
            block_size(N, block_n, 128))


def _kernel(mod_ref, x_ref, w_ref, o_ref):
    m = mod_ref[pl.program_id(0)]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # exact integer partial dot in f32 (block_k * (m-1)^2 < 2^24 enforced below)
    part = jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32)
    o_ref[0] = jnp.mod(o_ref[0] + jnp.mod(part, m), m)


@functools.partial(
    jax.jit,
    static_argnames=("moduli", "block_m", "block_n", "block_k", "interpret"),
)
def rns_matmul_pallas(
    x_res: jax.Array,
    w_res: jax.Array,
    moduli: Tuple[int, ...],
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(n_mod, M, K) x (n_mod, K, N) -> (n_mod, M, N) residue matmul.

    x_res/w_res: non-negative residues (int32 or exact f32).
    moduli: static tuple of modulus values.
    """
    interpret = resolve_interpret(interpret)
    nm, M, K = x_res.shape
    N = w_res.shape[2]
    assert len(moduli) == nm, (moduli, x_res.shape)
    xf = x_res.astype(jnp.float32)
    wf = w_res.astype(jnp.float32)
    mf = jnp.asarray(moduli, jnp.float32)

    bm_, bk, bn = _blocks(M, K, N, moduli, block_m, block_n, block_k)
    pm, pk, pn = (round_up(M, bm_) - M, round_up(K, bk) - K,
                  round_up(N, bn) - N)
    if pm or pk:
        xf = jnp.pad(xf, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        wf = jnp.pad(wf, ((0, 0), (0, pk), (0, pn)))

    grid = (nm, xf.shape[1] // bm_, wf.shape[2] // bn, xf.shape[2] // bk)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm_, bk), lambda mi, i, j, k, _: (mi, i, k)),
                pl.BlockSpec((1, bk, bn), lambda mi, i, j, k, _: (mi, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm_, bn),
                                   lambda mi, i, j, k, _: (mi, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nm, xf.shape[1], wf.shape[2]),
                                       jnp.float32),
        interpret=interpret,
    )(mf, xf, wf)
    return out[:, :M, :N].astype(jnp.int32)


def _kernel_channel(mod_ref, step_ref, x_ref, w_ref, nz_ref, o_ref, *,
                    nk: int):
    m = mod_ref[pl.program_id(0)]
    step = step_ref[pl.program_id(0)]
    last_k = pl.program_id(3) == nk - 1

    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    part = jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32)
    o_ref[0] = jnp.mod(o_ref[0] + jnp.mod(part, m), m)

    @pl.when(last_k)
    def _readout():
        # residue-level readout channel, fused on the VMEM-resident block:
        # detector phase noise (pre-scaled N(0, sigma_m) levels), nearest-
        # level re-quantize, ring wrap, then ADC re-grid. Bit-identical to
        # channel.phase_noise + channel.converter_quantize on the same draws.
        o = jnp.mod(jnp.round(o_ref[0] + nz_ref[0]), m)
        safe = jnp.where(step > 0, step, 1.0)
        oq = jnp.clip(jnp.round(jnp.round(o / safe) * safe), 0, m - 1)
        o_ref[0] = jnp.where(step > 0, oq, o)


@functools.partial(
    jax.jit,
    static_argnames=("moduli", "adc_bits", "block_m", "block_n", "block_k",
                     "interpret"),
)
def rns_matmul_pallas_channel(
    x_res: jax.Array,
    w_res: jax.Array,
    moduli: Tuple[int, ...],
    noise: jax.Array,
    adc_bits: Optional[int] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Residue matmul with the readout channel fused into the epilogue.

    x_res/w_res: (n_mod, M, K) x (n_mod, K, N) non-negative residues.
    noise: (n_mod, M, N) f32 — detector noise PRE-SCALED to per-modulus
      phase-level sigmas (zeros for noiseless channels); sampled by the
      caller so determinism/keying stays outside the kernel.
    adc_bits: ADC precision; identity whenever ``2^bits >= m`` (per slot).
    """
    interpret = resolve_interpret(interpret)
    nm, M, K = x_res.shape
    N = w_res.shape[2]
    assert len(moduli) == nm, (moduli, x_res.shape)
    assert noise.shape == (nm, M, N), (noise.shape, (nm, M, N))
    xf = x_res.astype(jnp.float32)
    wf = w_res.astype(jnp.float32)
    nz = noise.astype(jnp.float32)
    mf = jnp.asarray(moduli, jnp.float32)
    # per-slot ADC grid step; 0 flags the identity converter (2^bits >= m)
    steps = []
    for m in moduli:
        if adc_bits is None or 2 ** adc_bits >= m:
            steps.append(0.0)
        else:
            steps.append((m - 1) / (2 ** adc_bits - 1))
    sf = jnp.asarray(steps, jnp.float32)

    bm_, bk, bn = _blocks(M, K, N, moduli, block_m, block_n, block_k)
    pm, pk, pn = (round_up(M, bm_) - M, round_up(K, bk) - K,
                  round_up(N, bn) - N)
    if pm or pk:
        xf = jnp.pad(xf, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        wf = jnp.pad(wf, ((0, 0), (0, pk), (0, pn)))
    if pm or pn:
        nz = jnp.pad(nz, ((0, 0), (0, pm), (0, pn)))

    nk = xf.shape[2] // bk
    grid = (nm, xf.shape[1] // bm_, wf.shape[2] // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel_channel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm_, bk),
                             lambda mi, i, j, k, *_: (mi, i, k)),
                pl.BlockSpec((1, bk, bn),
                             lambda mi, i, j, k, *_: (mi, k, j)),
                pl.BlockSpec((1, bm_, bn),
                             lambda mi, i, j, k, *_: (mi, i, j)),
            ],
            out_specs=pl.BlockSpec((1, bm_, bn),
                                   lambda mi, i, j, k, *_: (mi, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nm, xf.shape[1], wf.shape[2]),
                                       jnp.float32),
        interpret=interpret,
    )(mf, sf, xf, wf, nz)
    return out[:, :M, :N].astype(jnp.int32)
