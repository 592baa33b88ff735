"""GQA attention: chunked (flash-style) training/prefill path + cached decode.

Memory discipline: scores are never materialized at (L x S). The prefill path
runs an online-softmax scan over KV chunks inside a map over Q chunks, so the
peak buffer is (B, q_chunk, H, kv_chunk). The backward (a ``custom_vjp``)
keeps the same bound: its residuals are q, k, v, the output and one
log-sum-exp per query row, and it recomputes each block's scores. Supports
causal masking, sliding windows (mixtral), QK-norm (qwen3), cross-attention
(seamless), and KV-head repetition so kv heads can be sharded over large TP
meshes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import MiragePolicy
from repro.models import common

NEG_INF = -1e30


class AttnParams(NamedTuple):
    pass  # attention params are plain dicts; see attn_init


def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              qkv_bias: bool, qk_norm: bool, d_in: Optional[int] = None):
    ks = jax.random.split(key, 5)
    d_in = d_in or d_model
    p = {
        "q": common.dense_init(ks[0], d_in, n_heads * head_dim, qkv_bias),
        "k": common.dense_init(ks[1], d_in, n_kv_heads * head_dim, qkv_bias),
        "v": common.dense_init(ks[2], d_in, n_kv_heads * head_dim, qkv_bias),
        "o": common.dense_init(ks[3], n_heads * head_dim, d_model, False),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((head_dim,), jnp.float32)
    return p


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Kv, D) -> (B, S, Kv*n_rep, D). Exact duplication, used to make
    kv heads divisible by the TP degree (value-identical; tested)."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def _chunk_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(Lq, Sk) boolean validity mask from absolute positions. Padded key
    slots carry position 2^30 and must be masked in the non-causal path too."""
    m = k_pos[None, :] < 2**29
    m = jnp.broadcast_to(m, (q_pos.shape[0], k_pos.shape[0]))
    if causal:
        m = m & (q_pos[:, None] >= k_pos[None, :])
    if window is not None:
        m = m & (q_pos[:, None] - k_pos[None, :] < window)
    return m


def chunked_attention(
    q: jax.Array,            # (B, Lq, H, D) — rope already applied
    k: jax.Array,            # (B, Sk, Kv, D)
    v: jax.Array,            # (B, Sk, Kv, D)
    q_positions: jax.Array,  # (Lq,) absolute positions
    k_positions: jax.Array,  # (Sk,)
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    score_dtype=jnp.float32,
) -> jax.Array:
    """Online-softmax attention; returns (B, Lq, H, D).

    Forward: a map over Q chunks of an online-softmax scan over KV chunks;
    the peak buffer is one (B, q_chunk, H, kv_chunk) block of scores.
    Backward (flash-style, ``jax.custom_vjp``): the residuals are q, k, v,
    the output and each query row's log-sum-exp; every block's scores and
    probabilities are recomputed from them, so nothing per KV step is kept.
    Query rows with no valid key (the pad up to a whole chunk, at position
    -1) take no gradient: their cotangent is zero.

    score_dtype=bfloat16 halves the HBM traffic of the materialized score/
    probability tensors (the dominant memory term of training cells — see
    EXPERIMENTS.md §Perf); running max/denominator/accumulator stay f32.
    The backward's products take the same operand dtype, f32 sums."""
    B, Lq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    assert H % Kv == 0, (H, Kv)

    qc = min(q_chunk, Lq)
    kc = min(kv_chunk, Sk)
    pad_q = (-Lq) % qc
    pad_k = (-Sk) % kc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad_k), constant_values=2**30)
    out = _blocked_attention(q, k, v, q_positions, k_positions, causal, window,
                             qc, kc, score_dtype)
    return out[:, :Lq]


def _block_scores(qi, ki, qp, kp, causal, window, score_dtype):
    """Scaled, masked scores of one (Q chunk, KV chunk) block:
    (B, qc, Kv, rep, D) x (B, kc, Kv, D) -> (B, qc, Kv, rep, kc)."""
    sm_scale = 1.0 / math.sqrt(qi.shape[-1])
    s = jnp.einsum("bqkrd,bskd->bqkrs",
                   qi.astype(score_dtype), ki.astype(score_dtype),
                   preferred_element_type=score_dtype) * sm_scale
    mask = _chunk_mask(qp, kp, causal, window)  # (qc, kc)
    neg = jnp.asarray(-3e4 if score_dtype == jnp.bfloat16 else NEG_INF,
                      score_dtype)
    return jnp.where(mask[None, :, None, None, :], s, neg)


def _attention_fwd(q, k, v, q_positions, k_positions, causal, window, qc, kc,
                   score_dtype):
    """Chunk-aligned forward: the output (B, Lq, H, D) and the row
    log-sum-exp (nq, B, qc, Kv, rep) in f32."""
    B, Lq, H, D = q.shape
    Kv = k.shape[2]
    rep = H // Kv
    nq, nk = Lq // qc, k.shape[1] // kc

    # (B, nq, qc, Kv, rep, D) view of q; k/v chunked on axis 1.
    q5 = q.reshape(B, nq, qc, Kv, rep, D)
    k4 = k.reshape(B, nk, kc, Kv, D)
    v4 = v.reshape(B, nk, kc, Kv, D)
    qpos = q_positions.reshape(nq, qc)
    kpos = k_positions.reshape(nk, kc)

    def one_q_chunk(args):
        qi, qp = args  # (B, qc, Kv, rep, D), (qc,)

        def kv_step(carry, inp):
            acc, m_run, l_run = carry
            ki, vi, kp = inp  # (B, kc, Kv, D), (B, kc, Kv, D), (kc,)
            s = _block_scores(qi, ki, qp, kp, causal, window, score_dtype)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(score_dtype))
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1, dtype=jnp.float32)
            pv = jnp.einsum("bqkrs,bskd->bqkrd", p, vi.astype(score_dtype),
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, qc, Kv, rep, D), jnp.float32)
        m0 = jnp.full((B, qc, Kv, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, Kv, rep), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(k4, 1, 0), jnp.moveaxis(v4, 1, 0), kpos))
        out = acc / jnp.maximum(l_run[..., None], 1e-30)
        # (B, qc, Kv, rep, D), and the row log-sum-exp (B, qc, Kv, rep)
        return out, m_run + jnp.log(l_run)

    outs, lse = jax.lax.map(one_q_chunk, (jnp.moveaxis(q5, 1, 0), qpos))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * qc, H, D)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _blocked_attention(q, k, v, q_positions, k_positions, causal, window, qc,
                       kc, score_dtype):
    return _attention_fwd(q, k, v, q_positions, k_positions, causal, window,
                          qc, kc, score_dtype)[0]


def _blocked_attention_fwd(q, k, v, q_positions, k_positions, causal, window,
                           qc, kc, score_dtype):
    out, lse = _attention_fwd(q, k, v, q_positions, k_positions, causal,
                              window, qc, kc, score_dtype)
    return out, (q, k, v, q_positions, k_positions, out, lse)


def _blocked_attention_bwd(causal, window, qc, kc, score_dtype, res, g):
    """Flash-attention backward over the forward's blocks. Per block:
    p = exp(s - lse), dP = dO V^T, dS = p (dP - rowsum(dO O)); dQ sums
    dS K over KV chunks (carried per Q chunk), dK and dV sum dS^T Q and
    p^T dO over the rep query heads of each KV head and over Q chunks."""
    q, k, v, q_positions, k_positions, out, lse = res
    B, Lq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    nq, nk = Lq // qc, Sk // kc
    sm_scale = 1.0 / math.sqrt(D)
    sd = score_dtype

    def q_major(x):  # (B, Lq, H, D) -> (nq, B, qc, Kv, rep, D)
        return jnp.moveaxis(x.reshape(B, nq, qc, Kv, rep, D), 1, 0)

    delta = jnp.sum(g * out, axis=-1)  # (B, Lq, H); out and g are f32
    delta = jnp.moveaxis(delta.reshape(B, nq, qc, Kv, rep), 1, 0)
    k4 = jnp.moveaxis(k.reshape(B, nk, kc, Kv, D), 1, 0)
    v4 = jnp.moveaxis(v.reshape(B, nk, kc, Kv, D), 1, 0)
    kpos = k_positions.reshape(nk, kc)

    def q_step(carry, inp):
        dk_acc, dv_acc = carry
        qi, doi, lse_i, delta_i, qp = inp

        def kv_step(dq_i, kin):
            ki, vi, kp = kin
            s = _block_scores(qi, ki, qp, kp, causal, window, sd)
            p = jnp.exp(s - lse_i[..., None])  # f32
            dp = jnp.einsum("bqkrd,bskd->bqkrs", doi.astype(sd),
                            vi.astype(sd), preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_i[..., None])).astype(sd)
            dq_i = dq_i + jnp.einsum("bqkrs,bskd->bqkrd", ds, ki.astype(sd),
                                     preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("bqkrs,bqkrd->bskd", ds, qi.astype(sd),
                              preferred_element_type=jnp.float32)
            dv_j = jnp.einsum("bqkrs,bqkrd->bskd", p.astype(sd),
                              doi.astype(sd),
                              preferred_element_type=jnp.float32)
            return dq_i, (dk_j, dv_j)

        dq0 = jnp.zeros((B, qc, Kv, rep, D), jnp.float32)
        dq_i, (dk_parts, dv_parts) = jax.lax.scan(kv_step, dq0,
                                                  (k4, v4, kpos))
        return (dk_acc + dk_parts, dv_acc + dv_parts), dq_i * sm_scale

    zeros = jnp.zeros((nk, B, kc, Kv, D), jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        q_step, (zeros, zeros),
        (q_major(q), q_major(g), lse, delta, q_positions.reshape(nq, qc)))
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, Lq, H, D)
    dk = jnp.moveaxis(dk * sm_scale, 0, 1).reshape(B, Sk, Kv, D)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, Sk, Kv, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_blocked_attention.defvjp(_blocked_attention_fwd, _blocked_attention_bwd)


def attn_apply(
    p, x, policy: MiragePolicy, *,
    n_heads: int, n_kv_heads: int, head_dim: int,
    positions: jax.Array, rope_theta: float,
    causal: bool = True, window: Optional[int] = None,
    qk_norm: bool = False, kv_repeat: int = 1,
    x_kv: Optional[jax.Array] = None, use_rope: bool = True,
    q_chunk: int = 1024, kv_chunk: int = 1024,
    kv_positions: Optional[jax.Array] = None, opt=None,
    skip_o_proj: bool = False,
):
    """Full attention block over a sequence (training / prefill path).

    x_kv: source for k/v (cross-attention); defaults to x (self-attention).
    skip_o_proj: return the pre-projection context (B, L, H*D) so the caller
    can merge the o-projection with another row-sharded GEMM (one TP
    all-reduce instead of two — §Perf iteration 3 for parallel blocks).
    Returns (out, (k_cache, v_cache)) so prefill can keep the projected KV.
    """
    B, L, _ = x.shape
    src = x if x_kv is None else x_kv
    S = src.shape[1]
    with jax.named_scope("attn_qkv"):
        q = common.dense(p["q"], x, policy).reshape(B, L, n_heads, head_dim)
        k = common.dense(p["k"], src, policy).reshape(B, S, n_kv_heads,
                                                      head_dim)
        v = common.dense(p["v"], src, policy).reshape(B, S, n_kv_heads,
                                                      head_dim)
        if qk_norm:
            q = common.head_rmsnorm(p["q_norm"], q)
            k = common.head_rmsnorm(p["k_norm"], k)
        kv_pos = kv_positions if kv_positions is not None else (
            positions if x_kv is None else jnp.arange(S))
        if use_rope:
            q = common.apply_rope(q, positions, rope_theta)
            k = common.apply_rope(k, kv_pos, rope_theta)
        k = _repeat_kv(k, kv_repeat)
        v = _repeat_kv(v, kv_repeat)
        # Pin head-parallel layout: batch over dp, heads over tp (replicated
        # when the head count doesn't divide TP — never resharded
        # mid-attention).
        q = common.constrain(q, opt, ("dp", None, "tp", None))
        k = common.constrain(k, opt, ("dp", None, "tp", None))
        v = common.constrain(v, opt, ("dp", None, "tp", None))
    score_dtype = (jnp.bfloat16 if opt is not None and
                   getattr(opt, "attn_dtype", "float32") == "bfloat16"
                   else jnp.float32)
    # Pallas flash kernel (TPU deployment path): valid for full-sequence
    # self-attention (contiguous positions starting at 0) — train/prefill.
    use_flash = (opt is not None and getattr(opt, "use_flash_kernel", False)
                 and x_kv is None and kv_positions is None)
    with jax.named_scope("attn_core"):
        if use_flash:
            from repro.kernels.flash_attention import flash_attention
            out = flash_attention(
                q, k, v, causal=causal, window=window,
                interpret=policy.interpret)
        else:
            out = chunked_attention(
                q, k, v, positions, kv_pos, causal=causal, window=window,
                q_chunk=q_chunk, kv_chunk=kv_chunk, score_dtype=score_dtype)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, L, n_heads * head_dim)
        out = common.constrain(out, opt, ("dp", None, "tp"))
        if skip_o_proj:
            return out, (k, v)
        return common.dense(p["o"], out, policy), (k, v)


def attn_decode_step(
    p, x, cache_k, cache_v, idx, policy: MiragePolicy, *,
    n_heads: int, n_kv_heads: int, head_dim: int, rope_theta: float,
    window: Optional[int] = None, qk_norm: bool = False, kv_repeat: int = 1,
    use_rope: bool = True, cross: bool = False,
    block_tables: Optional[jax.Array] = None,
):
    """One decode step. x: (B, 1, d). ``idx``: current length — a scalar
    (whole batch at one position: the per-slot oracle loop) or a ``(B,)``
    vector (continuous-batching engine: every slot decodes at its own
    position; write slots and validity masks are computed per row).

    Two cache layouts:

      * **dense** (``block_tables=None``): cache_k/v are ``(B, S_cap,
        Kv_eff, D)`` per-slot rings holding keys ALREADY rope'd at their
        absolute positions. Sliding windows use modular slot addressing
        (position p lives at slot ``p % S_cap``), so the cache capacity for
        SWA archs is min(seq, window). This is the parity oracle path.
      * **paged** (``block_tables`` = ``(B, max_blocks)`` int32): cache_k/v
        are GLOBAL page pools ``(n_blocks, block_size, Kv_eff, D)``; logical
        position p of row b lives at physical block ``block_tables[b,
        p // bs]`` offset ``p % bs`` (linear addressing, no ring wrap — the
        window is applied purely through the validity mask). Unmapped table
        entries carry the OOB sentinel ``n_blocks``: the scatter-write drops
        on device, and the gather clamps to a real block whose garbage is
        hidden by the ``kpos <= idx`` mask (the allocator guarantees blocks
        exist for every position <= idx). Requires vector ``idx``.

    Cross-attention reads a fixed precomputed dense cache and writes nothing.
    """
    B = x.shape[0]
    paged = block_tables is not None
    per_slot = jnp.ndim(idx) == 1
    assert not (paged and (cross or not per_slot)), \
        "paged decode needs a per-slot idx vector and a self-attention cache"
    q = common.dense(p["q"], x, policy).reshape(B, 1, n_heads, head_dim)
    if qk_norm:
        q = common.head_rmsnorm(p["q_norm"], q)
    rope_pos = jnp.reshape(idx, (B, 1)) if per_slot else jnp.reshape(idx, (1,))
    if use_rope:
        q = common.apply_rope(q, rope_pos, rope_theta)

    if not cross:
        knew = common.dense(p["k"], x, policy).reshape(B, 1, n_kv_heads, head_dim)
        vnew = common.dense(p["v"], x, policy).reshape(B, 1, n_kv_heads, head_dim)
        if qk_norm:
            knew = common.head_rmsnorm(p["k_norm"], knew)
        if use_rope:
            knew = common.apply_rope(knew, rope_pos, rope_theta)
        knew = _repeat_kv(knew, kv_repeat)
        vnew = _repeat_kv(vnew, kv_repeat)
        if paged:
            NB, bs = cache_k.shape[0], cache_k.shape[1]
            mb = block_tables.shape[1]
            LP = mb * bs
            blk = jnp.minimum(idx // bs, mb - 1)
            wb = jnp.where(idx < LP,
                           block_tables[jnp.arange(B), blk], NB)
            wo = jnp.mod(idx, bs)
            cache_k = cache_k.at[wb, wo].set(knew[:, 0], mode="drop")
            cache_v = cache_v.at[wb, wo].set(vnew[:, 0], mode="drop")
            keys = cache_k[jnp.minimum(block_tables, NB - 1)].reshape(
                B, LP, cache_k.shape[2], head_dim)
            vals = cache_v[jnp.minimum(block_tables, NB - 1)].reshape(
                B, LP, cache_v.shape[2], head_dim)
            kpos = jnp.arange(LP)
            idx_b = idx[:, None]
            valid = (kpos[None, :] <= idx_b) & \
                (kpos[None, :] >= (idx_b - (window - 1) if window else 0))
        else:
            S_cap = cache_k.shape[1]
            slot = jnp.mod(idx, S_cap)
            if per_slot:
                cache_k = cache_k.at[jnp.arange(B), slot].set(knew[:, 0])
                cache_v = cache_v.at[jnp.arange(B), slot].set(vnew[:, 0])
            else:
                cache_k = jax.lax.dynamic_update_slice(cache_k, knew,
                                                       (0, slot, 0, 0))
                cache_v = jax.lax.dynamic_update_slice(cache_v, vnew,
                                                       (0, slot, 0, 0))
            # absolute position held by each slot (after this write); per-row
            # when idx is a vector -> kpos/valid broadcast to (B, S_cap)
            slots = jnp.arange(S_cap)
            idx_b = idx[:, None] if per_slot else idx
            kpos = idx_b - jnp.mod(idx_b - slots, S_cap)
            valid = (kpos >= 0) & \
                (kpos >= (idx_b - (window - 1) if window else 0))
            keys, vals = cache_k, cache_v
    else:
        S_cap = cache_k.shape[1]
        valid = jnp.ones((S_cap,), bool)
        keys, vals = cache_k, cache_v

    Kv_eff = keys.shape[2]
    rep = n_heads // Kv_eff
    sm = 1.0 / math.sqrt(head_dim)
    q5 = q.reshape(B, 1, Kv_eff, rep, head_dim)
    s = jnp.einsum("bqkrd,bskd->bqkrs", q5, keys,
                   preferred_element_type=jnp.float32) * sm
    vmask = (valid[:, None, None, None, :] if valid.ndim == 2
             else valid[None, None, None, None, :])
    s = jnp.where(vmask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkrs,bskd->bqkrd", w, vals,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, n_heads * head_dim)
    return common.dense(p["o"], out, policy), cache_k, cache_v


def attn_verify_step(
    p, x, cache_k, cache_v, idx, policy: MiragePolicy, *,
    n_heads: int, n_kv_heads: int, head_dim: int, rope_theta: float,
    window: Optional[int] = None, qk_norm: bool = False, kv_repeat: int = 1,
    block_tables: jax.Array = None,
):
    """Multi-token verify step for speculative decoding (paged cache only).

    x: ``(B, T, d)`` — per slot, the current token plus ``T-1`` draft
    tokens, occupying absolute positions ``idx[b] + j`` (``idx`` is the
    engine's per-slot position vector). All ``T`` keys/values are
    scatter-written through the block tables FIRST (the server reserves
    the blocks up front; OOB sentinel entries drop), then row ``j``
    attends over the gathered pages masked at ``kpos <= idx + j`` — the
    same write-then-gather contract as :func:`attn_chunk_step`, which is
    what makes rejected draft tails safe: their garbage KV sits at
    positions ``> idx + accepted`` and the NEXT verify tick re-writes
    exactly those positions before any gather reads them.
    """
    assert block_tables is not None, "verify step requires the paged layout"
    B, T = x.shape[0], x.shape[1]
    q = common.dense(p["q"], x, policy).reshape(B, T, n_heads, head_dim)
    knew = common.dense(p["k"], x, policy).reshape(B, T, n_kv_heads, head_dim)
    vnew = common.dense(p["v"], x, policy).reshape(B, T, n_kv_heads, head_dim)
    if qk_norm:
        q = common.head_rmsnorm(p["q_norm"], q)
        knew = common.head_rmsnorm(p["k_norm"], knew)
    pos = idx[:, None] + jnp.arange(T)[None, :]          # (B, T)
    q = common.apply_rope(q, pos, rope_theta)
    knew = common.apply_rope(knew, pos, rope_theta)
    knew = _repeat_kv(knew, kv_repeat)
    vnew = _repeat_kv(vnew, kv_repeat)

    NB, bs = cache_k.shape[0], cache_k.shape[1]
    mb = block_tables.shape[1]
    LP = mb * bs
    blk = jnp.minimum(pos // bs, mb - 1)
    wb = jnp.where(pos < LP,
                   jnp.take_along_axis(block_tables, blk, axis=1), NB)
    wo = jnp.mod(pos, bs)
    # positions within a slot are distinct, and slots never share a
    # writable block (the server's copy-on-write guard forks shared blocks
    # before any write), so the scatter indices are collision-free
    cache_k = cache_k.at[wb, wo].set(knew, mode="drop")
    cache_v = cache_v.at[wb, wo].set(vnew, mode="drop")
    keys = cache_k[jnp.minimum(block_tables, NB - 1)].reshape(
        B, LP, cache_k.shape[2], head_dim)
    vals = cache_v[jnp.minimum(block_tables, NB - 1)].reshape(
        B, LP, cache_v.shape[2], head_dim)
    kpos = jnp.arange(LP)
    valid = kpos[None, None, :] <= pos[:, :, None]       # (B, T, LP)
    if window:
        valid = valid & (kpos[None, None, :] > pos[:, :, None] - window)

    Kv_eff = keys.shape[2]
    rep = n_heads // Kv_eff
    sm = 1.0 / math.sqrt(head_dim)
    q5 = q.reshape(B, T, Kv_eff, rep, head_dim)
    s = jnp.einsum("btkrd,bskd->btkrs", q5, keys,
                   preferred_element_type=jnp.float32) * sm
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkrs,bskd->btkrd", w, vals,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, T, n_heads * head_dim)
    return common.dense(p["o"], out, policy), cache_k, cache_v


def attn_chunk_step(
    p, x, k_pages, v_pages, table_row, pos0, true_len,
    policy: MiragePolicy, *,
    n_heads: int, n_kv_heads: int, head_dim: int, rope_theta: float,
    window: Optional[int] = None, qk_norm: bool = False, kv_repeat: int = 1,
    q_chunk: int = 1024, kv_chunk: int = 1024,
):
    """Chunked-prefill attention for ONE serving slot over the paged cache.

    x: ``(1, C, d)`` — the next ``C`` prompt tokens of the slot, starting at
    absolute position ``pos0`` (traced). ``true_len <= C`` is the number of
    REAL tokens (attention families right-pad the final chunk; pads are
    dropped at the page write and masked in attention, so their garbage
    never enters the cache). k/v_pages are the global ``(n_blocks,
    block_size, Kv_eff, D)`` pools and ``table_row`` the slot's
    ``(max_blocks,)`` block table.

    The chunk's keys are scatter-written into the pages FIRST, then q
    attends over the gathered prefix+chunk with absolute positions — the
    same online-softmax ``chunked_attention`` as full prefill, so cross-
    chunk causality (and SWA windows) come from the position mask alone.
    """
    B, C = x.shape[0], x.shape[1]
    NB, bs = k_pages.shape[0], k_pages.shape[1]
    mb = table_row.shape[0]
    LP = mb * bs
    positions = pos0 + jnp.arange(C)
    q = common.dense(p["q"], x, policy).reshape(B, C, n_heads, head_dim)
    k = common.dense(p["k"], x, policy).reshape(B, C, n_kv_heads, head_dim)
    v = common.dense(p["v"], x, policy).reshape(B, C, n_kv_heads, head_dim)
    if qk_norm:
        q = common.head_rmsnorm(p["q_norm"], q)
        k = common.head_rmsnorm(p["k_norm"], k)
    q = common.apply_rope(q, positions, rope_theta)
    k = common.apply_rope(k, positions, rope_theta)
    k = _repeat_kv(k, kv_repeat)
    v = _repeat_kv(v, kv_repeat)
    # scatter the chunk into the pages; pads and positions beyond the table
    # capacity route to the OOB sentinel and drop on device
    j = jnp.arange(C)
    blk = jnp.minimum(positions // bs, mb - 1)
    dest = jnp.where((j < true_len) & (positions < LP), table_row[blk], NB)
    off = jnp.mod(positions, bs)
    k_pages = k_pages.at[dest, off].set(k[0], mode="drop")
    v_pages = v_pages.at[dest, off].set(v[0], mode="drop")
    # gather prefix + chunk; unwritten positions get kpos 2^30 (masked)
    kb = k_pages[jnp.minimum(table_row, NB - 1)].reshape(
        LP, k_pages.shape[2], head_dim)[None]
    vb = v_pages[jnp.minimum(table_row, NB - 1)].reshape(
        LP, v_pages.shape[2], head_dim)[None]
    kpos = jnp.arange(LP)
    kpos = jnp.where(kpos < pos0 + true_len, kpos, 2**30)
    out = chunked_attention(q, kb, vb, positions, kpos, causal=True,
                            window=window, q_chunk=q_chunk,
                            kv_chunk=kv_chunk)
    out = out.reshape(B, C, n_heads * head_dim)
    return common.dense(p["o"], out, policy), k_pages, v_pages
