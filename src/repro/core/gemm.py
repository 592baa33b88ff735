"""Mirage GEMM: BFP + RNS matrix multiplication with a quantized backward pass.

This is the paper's contribution as a composable JAX op. ``mirage_matmul``
executes ``x @ w`` under a :class:`MiragePolicy`, dispatching on
``policy.mode`` through the backend registry (``repro.core.backends``):

  fp32 / bf16 / int8       baselines the paper compares against
  mirage_fast              BFP-quantize both operands along the contraction
                           dim, fold the power-of-two group scales back into
                           the mantissas, and run ONE MXU matmul. Value-exact
                           w.r.t. the faithful path whenever f32 accumulation
                           is exact (property-tested).
  mirage_faithful          group-batched integer dot products + FP32 partial
                           accumulation (paper dataflow steps 2-9, with the
                           RNS conversions elided exactly as the paper's own
                           accuracy model does, Section IV-A).
  mirage_rns               the full hardware path: forward conversion to the
                           special moduli set, per-modulus modular GEMM over
                           all groups at once, CRT reverse conversion, FP32
                           scale-accumulate. Optional Pallas kernel + analog
                           noise injection.
  mirage_rns_pallas        mirage_rns forced through the Pallas residue kernel.
  *_ref                    the seed fori_loop implementations, frozen as
                           parity oracles and benchmark baselines.

New modes register themselves (``backends.register_fn``) and are reachable
from every consumer without touching this module.

Training: ``mirage_matmul`` has a ``custom_vjp`` so BOTH backward GEMMs
(Eqs. 2-3) run the same quantized path, each BFP-grouped along its own
contraction dimension, while the caller keeps FP32 master weights (Eq. 4).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backends, bfp, stationary
from repro.core.precision import MiragePolicy
from repro.obs import health as obs_health


# --------------------------------------------------------------------------
# Ambient noise keys (serving: fresh analog noise per decode tick)
# --------------------------------------------------------------------------
#
# ``policy.noise_seed`` alone gives a STATIC error pattern per GEMM site
# (the key is the seed folded with operand shapes) — right for programming/
# fabrication error, wrong for shot/thermal noise which redraws every shot.
# The policy is a hashable static argument of every jitted step, so varying
# the seed per tick would recompile per tick. Instead a caller *inside* a
# jitted function opens :func:`noise_key_scope` with a traced key (a plain
# input of that jit); every ``mirage_matmul`` / ``mirage_matmul_nograd``
# traced under the scope whose backend ``supports_noise`` and got no
# explicit ``key`` derives a per-call subkey (scope key folded with a call
# counter, so each GEMM site draws independently). Deterministic backends
# never consult the scope, and nothing changes when no scope is open —
# training and the keyless static-seed path are untouched.

_AMBIENT = threading.local()


@contextlib.contextmanager
def noise_key_scope(key: jax.Array):
    """Make ``key`` the ambient randomness source for stochastic GEMMs
    traced inside the ``with`` block. Re-entrant (inner scopes shadow).

    Forward-only by design (serving): backward GEMMs (``_mm_bwd``) run
    outside the caller's scope and keep the existing key-or-seed
    requirement — training under noise still goes through
    ``policy.noise_seed``."""
    stack = getattr(_AMBIENT, "stack", None)
    if stack is None:
        stack = _AMBIENT.stack = []
    stack.append([key, 0])
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def fold_noise_scope(tag):
    """Nested scope whose key is the enclosing scope's key folded with
    ``tag`` — no-op when no scope is open. ``tag`` may be TRACED (a scan
    layer index): the per-call counter alone is a trace-time constant, so
    without this every iteration of a ``lax.scan`` over layers would reuse
    the same subkey per GEMM site. The model's layer scans open one of
    these per iteration so each layer draws independent noise."""
    stack = getattr(_AMBIENT, "stack", None)
    if not stack:
        yield
        return
    with noise_key_scope(jax.random.fold_in(stack[-1][0], tag)):
        yield


def _ambient_subkey() -> Optional[jax.Array]:
    stack = getattr(_AMBIENT, "stack", None)
    if not stack:
        return None
    top = stack[-1]
    top[1] += 1
    return jax.random.fold_in(top[0], top[1])


# --------------------------------------------------------------------------
# Operand quantization helpers (public API, used by tests and tooling)
# --------------------------------------------------------------------------

def quantize_operands(
    x: jax.Array, w: jax.Array, policy: MiragePolicy
) -> Tuple[bfp.BFPTensor, bfp.BFPTensor]:
    """BFP-quantize activations (grouped along last dim of x) and weights
    (grouped along first dim of w — i.e. the shared contraction dim)."""
    qx = bfp.bfp_quantize(x, policy.b_m, policy.g, policy.rounding)
    # Weights: (K, N) -> transpose so the contraction dim is last, quantize,
    # then restore layout as (G, g, N).
    qwt = bfp.bfp_quantize(w.T, policy.b_m, policy.g, policy.rounding)
    return qx, qwt


# --------------------------------------------------------------------------
# Registry dispatch
# --------------------------------------------------------------------------

def _forward_impl(x: jax.Array, w: jax.Array, policy: MiragePolicy,
                  key: Optional[jax.Array] = None) -> jax.Array:
    backend = backends.resolve(policy)
    if (isinstance(w, stationary.StationaryResidues)
            and not backend.supports_stationary_residues):
        raise TypeError(
            f"backend {backend.name!r} cannot execute a pre-encoded "
            f"StationaryResidues weight (capability flag "
            f"supports_stationary_residues is unset) — pass the raw FP32 "
            f"weight, or run an RNS-family mode")
    if key is None and backend.supports_noise:
        key = _ambient_subkey()
    with jax.named_scope("gemm"):
        return backend.forward(x, w, policy, key=key)


# --------------------------------------------------------------------------
# Differentiable op: quantized forward AND backward GEMMs
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mirage_matmul(x: jax.Array, w: jax.Array, policy: MiragePolicy) -> jax.Array:
    """``x @ w`` under the Mirage numerics policy. x: (..., K), w: (K, N)."""
    return _forward_impl(x, w, policy)


def _mm_fwd(x, w, policy):
    return _forward_impl(x, w, policy), (x, w)


def _mm_bwd(policy, residuals, gout):
    x, w = residuals
    gout = gout.astype(jnp.float32)
    # dX = dO @ W^T (contraction over N). Under weight-stationary quant the
    # transposed read reuses the SAME stored grid values (hardware-faithful).
    # Backends whose weight-stationary skip is only exact for aligned
    # groupings (group-dot/RNS: integer mantissas required) re-quantize the
    # transposed read instead — w.T is grouped along N, not the K grid.
    dx_policy = policy
    if (policy.assume_quantized_weights
            and backends.resolve(policy).weight_stationary_aligned_only):
        dx_policy = policy.replace(assume_quantized_weights=False)
    with jax.named_scope("gemm_dx"):
        dx = _forward_impl(gout, w.T, dx_policy)
    # dW = X^T @ dO (contraction over tokens): neither operand is a
    # stationary weight -> always quantize both sides.
    dw_policy = (policy.replace(assume_quantized_weights=False)
                 if policy.assume_quantized_weights else policy)
    with jax.named_scope("gemm_dw"):
        xf = x.reshape(-1, x.shape[-1])            # (M, K)
        gf = gout.reshape(-1, gout.shape[-1])      # (M, N)
        dw = _forward_impl(xf.T, gf, dw_policy)    # (K, N)
    return dx.astype(x.dtype), dw.astype(w.dtype)


mirage_matmul.defvjp(_mm_fwd, _mm_bwd)


def mirage_matmul_nograd(x, w, policy: MiragePolicy,
                         key: Optional[jax.Array] = None):
    """Forward-only variant (serving paths); avoids residual bookkeeping.

    ``key`` seeds stochastic backends (``policy.noise_sigma > 0`` analog
    noise); deterministic backends ignore it. When no key is passed and an
    enclosing :func:`noise_key_scope` is open (the serving engine opens one
    per decode tick), stochastic backends draw a per-call subkey from it.
    """
    return _forward_impl(x, w, policy, key=key)


def mirage_matmul_auto(x, w, policy: MiragePolicy) -> jax.Array:
    """:func:`mirage_matmul`, except under an open analog-health scope.

    ``custom_vjp`` traces its primal in a sub-trace whose intermediates
    cannot legally reach the enclosing scope, so health records made inside
    the differentiable op would leak. Health scopes are only opened by the
    serving engine's forward-only steps (``repro.obs.health``), where the
    custom backward is dead weight anyway — dispatch straight to the
    forward impl there. Model GEMM call sites shared between training and
    serving route through this."""
    if obs_health.active():
        return _forward_impl(x, w, policy)
    return mirage_matmul(x, w, policy)
