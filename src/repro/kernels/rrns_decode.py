"""Pallas kernel: fused single-pass RRNS majority decode.

Kernel counterpart of :func:`repro.analog.rrns.rrns_decode` (same
consistency-count voting identity — see that module's docstring), laid out
on a **subset-major grid**: ``grid = (element_blocks, S)`` with the subset
axis innermost, so for each output block the kernel revisits the block S
times, accumulating the running (first-max) winner directly in the output
refs — the per-subset reconstruction, the congruence checks, the binomial
vote lookup and the winner select all fuse into one VMEM-resident pass per
(block, subset) step. No ``(S, ...)`` intermediate ever exists.

Per-subset constants are scalar-prefetched into SMEM and indexed by the
subset grid axis (as ``rns_matmul`` does for the modulus value), so ONE
compiled kernel serves any (moduli, n_required, psi) table. Elements are
laid out as (rows, 128) tiles, so every residue row is whole vregs.

The kernel runs entirely in f32 and therefore requires ``tables.f32_exact``
(every reconstruction sum inside the exact-integer window 2^24 — always
true at the paper point k=5 with two redundant moduli); larger moduli sets
must use the jnp fallback decode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import resolve_interpret
from repro.kernels.bfp_quantize import block_size, round_up
from repro.obs import health as obs_health


_LANES = 128


def _decode_kernel(wrow_ref, sub_ref, minv_ref, res_ref, dec_ref, vot_ref,
                   *, n_total: int, n_required: int, psi: float,
                   binom: Tuple[int, ...]):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        dec_ref[...] = jnp.zeros_like(dec_ref)
        vot_ref[...] = jnp.full_like(vot_ref, -2.0)

    # reconstruction: weights are 0 for non-members, so the full-width
    # contraction over n_total positions equals the member sum exactly
    acc = None
    for i in range(n_total):
        term = res_ref[i] * wrow_ref[s * n_total + i]
        acc = term if acc is None else acc + term
    M_s = sub_ref[4 * s]
    inv_M = sub_ref[4 * s + 1]
    psi_s = sub_ref[4 * s + 2]
    lo = sub_ref[4 * s + 3]
    # round-based signed fold into [psi_s + 1 - M_s, psi_s] (two selects
    # absorb the half-up boundary and the reciprocal off-by-one)
    q = jnp.floor(acc * inv_M + 0.5)
    X = acc - q * M_s
    X = jnp.where(X > psi_s, X - M_s, X)
    X = jnp.where(X < lo, X + M_s, X)
    # consistency count over ALL positions: members are congruent by CRT
    # construction, so cons ranges over [n_required, n_total] and the vote
    # count is binom[cons - n_required]
    cons = None
    for i in range(n_total):
        d = X - res_ref[i]
        k = jnp.round(d * minv_ref[n_total + i])
        ok = (d - k * minv_ref[i] == 0.0).astype(jnp.float32)
        cons = ok if cons is None else cons + ok
    votes = jnp.full(X.shape, float(binom[0]))
    for e in range(1, n_total - n_required + 1):
        votes = jnp.where(cons == float(n_required + e), float(binom[e]),
                          votes)
    votes = jnp.where(jnp.abs(X) <= psi, votes, -1.0)
    # strict > keeps the FIRST max across the subset-major grid sweep ==
    # the oracle's dict-insertion-order tie-break
    better = votes > vot_ref[...]
    dec_ref[...] = jnp.where(better, X, dec_ref[...])
    vot_ref[...] = jnp.where(better, votes, vot_ref[...])


def _decode_flat(res_flat: jax.Array, tables, block_e: int,
                 interpret: bool) -> Tuple[jax.Array, jax.Array]:
    n_total, E = res_flat.shape
    S = tables.n_subsets
    rows = round_up(max(E, 1), _LANES) // _LANES
    br = block_size(rows, block_e // _LANES, 8)
    pad = round_up(rows, br) * _LANES - E
    if pad:
        res_flat = jnp.pad(res_flat, ((0, 0), (0, pad)))
    res_tiles = res_flat.astype(jnp.float32).reshape(n_total, -1, _LANES)
    # per-subset tables, flattened for SMEM: weights (S * n_total), then
    # (M_s, 1/M_s, psi_s, psi_s + 1 - M_s) per subset, then moduli and
    # their reciprocals
    wrow = jnp.asarray(tables.weights, jnp.float32).reshape(-1)
    sub = jnp.asarray(np.stack([
        tables.subset_M.astype(np.float32),
        (1.0 / tables.subset_M).astype(np.float32),
        tables.subset_psi.astype(np.float32),
        (tables.subset_psi + 1 - tables.subset_M).astype(np.float32),
    ], axis=1).reshape(-1))
    moduli = np.asarray(tables.moduli, np.float32)
    minv = jnp.asarray(np.concatenate([moduli, 1.0 / moduli]))
    n_rows = res_tiles.shape[1]
    grid = (n_rows // br, S)
    dec, vot = pl.pallas_call(
        functools.partial(
            _decode_kernel, n_total=n_total,
            n_required=tables.n_required, psi=float(tables.psi),
            binom=tables.binom),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec((n_total, br, _LANES),
                                   lambda e, s, *_: (0, e, 0))],
            out_specs=[
                pl.BlockSpec((br, _LANES), lambda e, s, *_: (e, 0)),
                pl.BlockSpec((br, _LANES), lambda e, s, *_: (e, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(wrow, sub, minv, res_tiles)
    dec, vot = dec.reshape(-1), vot.reshape(-1)
    dec, vot = dec[:E], vot[:E]
    any_legal = vot >= 0.0
    decoded = jnp.where(any_legal, dec, 0.0).astype(jnp.int32)
    corrected = jnp.where(any_legal, vot < float(S), True)
    if obs_health.active():
        # same correction-radius split as rrns.rrns_decode, recorded
        # here because the kernel epilogue is the only place the vote
        # counts still exist. One fused reduction (vot >= S implies
        # trusted, so trusted - full_agreement = repaired and E - trusted
        # = untrustworthy): these sums stay live in the decode hot path
        # and cost ~6% of decode throughput on the op-dispatch-bound
        # interpret-mode box — see the bench_serving obs_sweep notes.
        T = float(tables.vote_threshold)
        n = jnp.sum(jnp.stack([vot >= T, vot >= float(S)])
                    .astype(jnp.int32), axis=1)
        obs_health.record("rrns_corrected", n[0] - n[1])
        obs_health.record("rrns_uncorrected", jnp.int32(E) - n[0])
    return decoded, corrected


def rrns_decode_pallas(residues: jax.Array, tables, block_e: int = 4096,
                       interpret: Optional[bool] = None,
                       ) -> Tuple[jax.Array, jax.Array]:
    """RRNS majority decode through the subset-major Pallas kernel.

    residues: (n_total, ...) int32 over ``tables.moduli``; trailing dims are
    flattened into the kernel's element axis. Bit-identical outputs to
    :func:`repro.analog.rrns.rrns_decode` (and hence to the frozen
    ``rrns_decode_np`` oracle). Requires ``tables.f32_exact``.
    """
    if not tables.f32_exact:
        raise ValueError(
            "rrns_decode_pallas runs in f32 and needs every reconstruction "
            "bound inside the 2^24 exact-integer window; this moduli set "
            f"({tables.moduli}) exceeds it — use the jnp rrns_decode, whose "
            "int32 fallback handles large moduli")
    interpret = resolve_interpret(interpret)
    shape = residues.shape[1:]
    flat = residues.reshape(residues.shape[0], -1)
    decoded, corrected = _decode_flat(flat, tables, block_e, interpret)
    return decoded.reshape(shape), corrected.reshape(shape)
