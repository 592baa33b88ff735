"""Pallas TPU kernel: GQA flash attention (forward).

This is §Perf iteration FA: the dry-run showed the training memory term is
dominated by materialized attention score/probability traffic (the chunked
JAX reference writes (q_chunk x kv_chunk) score blocks and online-softmax
carries through HBM every kv step). This kernel keeps scores, probabilities,
and the running (m, l, acc) statistics in VMEM scratch across the kv-block
grid dimension — per-layer attention HBM traffic drops from
O(L*S*H) score bytes to O((L+S)*H*D) pure operand/result bytes.

Layout: grid = (B * H q-heads, q blocks, kv blocks); GQA is handled in the
BlockSpec index maps (q head h reads kv head h // rep — no KV repetition is
materialized). Causal and sliding-window masks are applied from absolute
block offsets. Block shapes default to MXU-aligned (128, 128).

Validated against ref.py / the pure-jnp chunked reference in interpret mode
(tests/test_flash_kernel.py); ``interpret=None`` follows the platform
(interpreted on CPU, compiled on TPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0].astype(jnp.float32)            # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k                          # padding
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,        # (B, Lq, H, D), rope applied
    k: jax.Array,        # (B, S, Kv, D)
    v: jax.Array,        # (B, S, Kv, D)
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, Lq, H, D = q.shape
    S, Kv = k.shape[1], k.shape[2]
    assert H % Kv == 0
    rep = H // Kv
    sm_scale = 1.0 / math.sqrt(D)

    bq = min(block_q, Lq)
    bk = min(block_k, S)
    pad_q = (-Lq) % bq
    pad_k = (-S) % bk
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Lq, D)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * Kv, S, D)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * Kv, S, D)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))

    grid = (B * H, qh.shape[1] // bq, kh.shape[1] // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, seq_k=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            # GQA: q head bh reads kv head bh // rep (per batch)
            pl.BlockSpec((1, bk, D),
                         lambda bh, iq, ik, rep=rep, H=H, Kv=Kv:
                         ((bh // H) * Kv + (bh % H) // rep, ik, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda bh, iq, ik, rep=rep, H=H, Kv=Kv:
                         ((bh // H) * Kv + (bh % H) // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, qh.shape[1], D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = out[:, :Lq].reshape(B, H, Lq, D)
    return jnp.moveaxis(out, 1, 2)
