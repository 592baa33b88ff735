"""Shared machinery for group-batched GEMM backends (faithful + RNS).

The paper's dataflow decomposes a K-contraction into ``G = K/g`` independent
g-wide integer group-dots followed by an FP32 scale-accumulate (Section
III-A steps 2-9). The seed implementation executed the groups with a
sequential ``jax.lax.fori_loop``; here the group axis is the *batch* axis of
a single ``dot_general`` (photonic hardware runs the groups in parallel
across MMVMU rows — the batched dot is the faithful execution model).

Layouts (group-major, so the group axis is leading everywhere):

  xv / qx : (G, M, g)   activations, M = prod(batch dims)
  wv / qw : (G, g, N)   weights
  sx      : (G, M, 1)   activation group scales (powers of two)
  sw      : (G, 1, N)   weight group scales (powers of two)

Exactness notes (load-bearing — the parity tests assert bit-identity with
the seed fori_loop backends):

* Folding the power-of-two group scales into the mantissas BEFORE the group
  dot is exact: every product and every within-group partial sum is an
  integer bounded by ``g * qmax^2 <= 2^14`` times a common power of two,
  hence exactly representable in f32. The scaled group dot therefore equals
  ``(p_int * sx) * sw`` bitwise.
* The cross-group accumulation is the only place f32 rounding happens. The
  seed folds groups left-to-right; a stacked-axis reduction matches that
  bitwise whenever partial sums stay inside the f32 exact window (always
  true at the paper operating point for activation-scale data; a documented
  property test covers the adversarial dynamic-range corner with allclose).

On CPU, XLA lowers *batched* dot_general to a slow non-Eigen path, so a
single huge (G, M, N) intermediate loses to streaming once it falls out of
cache. :func:`grouped_dot` is therefore adaptive: one batched dot while the
intermediate fits :data:`VECTORIZE_BUDGET_BYTES`, otherwise a ``lax.scan``
over group *blocks* (bounded memory, still block-batched inside). On TPU
the single-dot regime is always preferable (MXU batches natively); the
budget only matters for the CPU container.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bfp


def _env_int(name: str, default: int) -> int:
    """Integer env override; malformed values fall back to the default."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


# (G, M, N) f32 intermediates up to this size run as ONE group-batched dot;
# beyond it the scan-over-blocks regime keeps the working set bounded. The
# defaults are tuned for the 2-core CPU container; on TPU (where the MXU
# batches natively and the single-dot regime should always win) raise the
# budget via MIRAGE_VECTORIZE_BUDGET_BYTES without touching code.
VECTORIZE_BUDGET_BYTES = _env_int("MIRAGE_VECTORIZE_BUDGET_BYTES",
                                  32 * 1024 * 1024)

# Group-block size for the scan regime (MIRAGE_SCAN_BLOCK overrides).
DEFAULT_GROUP_BLOCK = _env_int("MIRAGE_SCAN_BLOCK", 8)

# f32 holds integers exactly up to 2^24: cap on any integer partial dot.
F32_EXACT_WINDOW = 1 << 24


def prepare_activations(
    x: jax.Array, policy,
) -> Tuple[jax.Array, jax.Array, Tuple[int, ...]]:
    """BFP-quantize the activation operand into group-major layout.

    Returns ``(qx, sx, batch)``; the weight-side counterpart lives in
    :func:`prepare_operands`. Split out so backends running against a
    pre-encoded stationary weight (``repro.core.stationary``) can skip the
    weight side entirely.
    """
    batch = x.shape[:-1]
    with jax.named_scope("quantize_x"):
        t = bfp.bfp_quantize(x, policy.b_m, policy.g, policy.rounding)
        G, g = t.mantissa.shape[-2], t.mantissa.shape[-1]
        M = 1
        for d in batch:
            M *= d
        qx = jnp.moveaxis(t.mantissa.reshape((M, G, g)), 1, 0)    # (G, M, g)
        sx = jnp.moveaxis(t.scale.reshape((M, G, 1)), 1, 0)       # (G, M, 1)
    return qx, sx, batch


def prepare_operands(
    x: jax.Array, w: jax.Array, policy,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Tuple[int, ...]]:
    """BFP-quantize both operands into group-major layout.

    Returns ``(qx, sx, qw, sw, batch)`` with the layouts documented above;
    ``batch`` is the original leading shape of ``x``. Mantissas/scales are
    bit-identical to the seed's ``gemm.quantize_operands`` (property-tested),
    but the weight side is grouped in place along K — no (K, N) <-> (N, K)
    transpose round-trip.

    Under ``policy.assume_quantized_weights`` (weight-stationary contract:
    ``w`` is already on the BFP grid along this K-grouping) the weight side
    uses the round/clip-free exact decomposition — bit-identical results,
    less work per call.
    """
    qx, sx, batch = prepare_activations(x, policy)
    with jax.named_scope("quantize_w"):
        if policy.assume_quantized_weights:
            qw, sw = bfp.bfp_decompose_contract(w, policy.b_m, policy.g)
        else:
            qw, sw = bfp.bfp_quantize_contract(w, policy.b_m, policy.g,
                                               policy.rounding)   # (G, g, N)
    return qx, sx, qw, sw, batch


def _block_dot(xb: jax.Array, wb: jax.Array) -> jax.Array:
    """(gb, M, g) x (gb, g, N) -> (M, N): block-batched dots + stacked sum."""
    if xb.shape[0] == 1:
        return jnp.matmul(xb[0], wb[0], preferred_element_type=jnp.float32)
    t = jax.lax.dot_general(xb, wb, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    return jnp.sum(t, axis=0)


def grouped_dot(xv: jax.Array, wv: jax.Array,
                group_block: int = 0) -> jax.Array:
    """Scale-accumulated sum of per-group dots: (G, M, g) x (G, g, N) -> (M, N).

    group_block: 0 = adaptive (single batched dot inside the vectorize
    budget, scan over DEFAULT_GROUP_BLOCK-sized blocks beyond it); -1 =
    force the single batched dot; n > 0 = force n-group blocks.
    """
    G, M, g = xv.shape
    N = wv.shape[-1]
    if group_block == 0:
        single = G * M * N * 4 <= VECTORIZE_BUDGET_BYTES
        gb = -1 if single else DEFAULT_GROUP_BLOCK
    else:
        gb = group_block
    if gb < 0 or gb >= G:
        t = jax.lax.dot_general(xv, wv, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        return jnp.sum(t, axis=0)
    pad = (-G) % gb
    if pad:
        # zero groups contribute exactly 0.0 to the accumulation
        xv = jnp.pad(xv, ((0, pad), (0, 0), (0, 0)))
        wv = jnp.pad(wv, ((0, pad), (0, 0), (0, 0)))
    nb = (G + pad) // gb
    xs = xv.reshape(nb, gb, M, g)
    ws = wv.reshape(nb, gb, g, N)

    def body(acc, blk):
        xb, wb = blk
        return acc + _block_dot(xb, wb), None

    acc, _ = jax.lax.scan(body, jnp.zeros((M, N), jnp.float32), (xs, ws))
    return acc


# --------------------------------------------------------------------------
# Exact modular reduction without fmod
# --------------------------------------------------------------------------

def exact_mod(a: jax.Array, m: int) -> jax.Array:
    """``a mod m`` for integer-valued f32 ``a`` in [0, 2^24), exact.

    Computes ``a - floor(a * (1/m)) * m`` and corrects the quotient's
    possible off-by-one from the rounded reciprocal — a handful of SIMD
    mul/floor/select ops instead of a libm fmod per element (the fmod is
    what made the seed RNS path fmod-bound). Property-tested exhaustively
    against ``jnp.mod`` over the full window for the paper's moduli.
    """
    mf = float(m)
    q = jnp.floor(a * (1.0 / mf))
    r = a - q * mf
    r = jnp.where(r < 0, r + mf, r)
    r = jnp.where(r >= mf, r - mf, r)
    return r


def grouped_residue_dot(xr: jax.Array, wr: jax.Array, m: int) -> jax.Array:
    """Per-group modular dot for one modulus: (G, M, g) x (G, g, N) -> (G, M, N).

    Residues are in [0, m); the exact integer group dot is bounded by
    ``g * (m-1)^2`` which must stay inside the f32 exact window — when it
    does not, the g axis is split into sub-chunks that are mod-reduced
    before combining (the same blocking the Pallas kernel applies).
    """
    G, M, g = xr.shape
    cap = max(1, (F32_EXACT_WINDOW - 1) // max(1, (m - 1) ** 2))
    if g <= cap:
        t = jax.lax.dot_general(xr, wr, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        return exact_mod(t, m)
    acc = None
    for k0 in range(0, g, cap):
        t = jax.lax.dot_general(
            xr[:, :, k0:k0 + cap], wr[:, k0:k0 + cap, :],
            (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
        part = exact_mod(t, m)
        acc = part if acc is None else acc + part
    # sum of < g/cap residues < m each stays far inside the exact window
    return exact_mod(acc, m)


def scale_accumulate(p: jax.Array, sx: jax.Array, sw: jax.Array,
                     batch: Tuple[int, ...]) -> jax.Array:
    """sum_G of p * sx * sw: (G, M, N) -> batch + (N,).

    Used by paths that materialize integer per-group results (the RNS path,
    where residues must stay unscaled through CRT). The multiplies are exact
    (power-of-two scales); only the cross-group sum rounds.
    """
    N = p.shape[-1]
    return jnp.sum(p * sx * sw, axis=0).reshape(batch + (N,))
