"""Pallas TPU kernel: BFP fake-quantization (shared-exponent groups).

Implements paper Section III-A step 2 as a tiled VMEM kernel: for each group
of ``g`` consecutive elements along the last axis, find the max exponent,
round mantissas to ``b_m`` bits, and write back the dequantized values.

The group exponent is extracted from the f32 bit pattern (exact — no log2
rounding hazards) and the power-of-two scale is *constructed* in the exponent
field, so the kernel is bit-exact against the pure-jnp reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import resolve_interpret


def _exp2_int(e: jax.Array) -> jax.Array:
    """Exact 2^e for integer e in [-126, 127], via exponent-field construction."""
    e = jnp.clip(e, -126, 127)
    bits = (e + 127).astype(jnp.int32) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _floor_log2(x: jax.Array) -> jax.Array:
    """floor(log2 x) for x > 0 (normal f32), from the exponent bit field."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def _group_max(a: jax.Array, g: int, axis: int) -> jax.Array:
    """Max of each aligned run of ``g`` elements along ``axis``, broadcast
    back to every element of its run.

    A butterfly over the power-of-two ``g``: at stride ``s`` every element
    takes the max with its partner at index ``i ^ s``, fetched with a
    circular roll. Partners never leave their run (runs are ``g``-aligned
    and ``g`` divides the axis), so the rolls' wrap-around is never read.
    Rolls and selects keep the block in its native (sublane, lane) layout,
    which Mosaic can lower; a ``(..., n // g, g)`` reshape puts ``g``
    elements on the 128-wide lane axis and it cannot. The rolled index
    vector picks the partner, so the result does not depend on the roll's
    direction convention. Max is exact, so this equals the reshape-and-
    reduce oracle bit for bit.
    """
    n = a.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    s = 1
    while s < g:
        fwd = pltpu.roll(a, s, axis)
        bwd = pltpu.roll(a, n - s, axis)
        from_fwd = pltpu.roll(idx, s, axis) == (idx ^ s)
        a = jnp.maximum(a, jnp.where(from_fwd, fwd, bwd))
        s *= 2
    return a


def _quantize_block(x: jax.Array, b_m: int, g: int, rounding: str,
                    axis: int = 1) -> jax.Array:
    """Fake-quantize a 2-D block in groups of ``g`` along ``axis``; that
    axis must be a multiple of the power-of-two ``g``."""
    maxabs = _group_max(jnp.abs(x), g, axis)
    e = _floor_log2(jnp.maximum(maxabs, 1e-30))
    e = jnp.where(maxabs > 0, e, 0)
    scale = _exp2_int(e - (b_m - 1))
    qmax = float(2**b_m - 1)
    v = x / scale
    q = jnp.trunc(v) if rounding == "truncate" else jnp.round(v)
    q = jnp.clip(q, -qmax, qmax)
    return q * scale


def check_group(g: int) -> None:
    if g < 1 or g & (g - 1):
        raise ValueError(f"the BFP Pallas kernels need a power-of-two "
                         f"group size, got g={g}")


def lane_multiple(g: int) -> int:
    """Smallest block width that holds whole groups on whole 128-lane rows."""
    return max(128, g)


def round_up(n: int, m: int) -> int:
    return n + (-n) % m


def block_size(n: int, want: int, align: int) -> int:
    """Block length along an axis of length ``n``: the whole axis when it
    fits in ``want``, else ``want`` rounded down to a multiple of ``align``
    (at least ``align``), as Mosaic's (8, 128) tiling requires."""
    return n if n <= want else max(align, want // align * align)


def _kernel(x_ref, o_ref, *, b_m: int, g: int, rounding: str):
    o_ref[...] = _quantize_block(x_ref[...].astype(jnp.float32), b_m, g, rounding)


@functools.partial(jax.jit, static_argnames=("b_m", "g", "rounding", "block_rows",
                                             "block_cols", "interpret"))
def bfp_fake_quant_pallas(
    x: jax.Array,
    b_m: int = 4,
    g: int = 16,
    rounding: str = "nearest",
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fake-quantize ``x`` along its last axis in BFP(b_m, g).

    Works for any rank: leading dims are flattened into rows. The last axis is
    padded to whole 128-lane rows (padding never leaks into group maxima:
    padded lanes are zero and fill only their own groups, which are
    discarded). ``interpret=None`` follows the platform
    (:func:`repro.core.precision.resolve_interpret`).
    """
    check_group(g)
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    k = orig_shape[-1]
    xf = x.reshape(-1, k).astype(jnp.float32)
    rows = xf.shape[0]
    lane = lane_multiple(g)
    kp = round_up(k, lane)
    br = block_size(rows, block_rows, 8)
    bc = block_size(kp, block_cols, lane)
    pad_r = round_up(rows, br) - rows
    pad_c = round_up(kp, bc) - k
    if pad_r or pad_c:
        xf = jnp.pad(xf, ((0, pad_r), (0, pad_c)))

    grid = (xf.shape[0] // br, xf.shape[1] // bc)
    out = pl.pallas_call(
        functools.partial(_kernel, b_m=b_m, g=g, rounding=rounding),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        interpret=interpret,
    )(xf)
    return out[:rows, :k].reshape(orig_shape)
