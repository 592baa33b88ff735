"""Chunked attention's flash-style backward (``jax.custom_vjp``).

The gradient must match autodiff through a plain dense softmax attention
for every mask the models use, and the forward must be what the online-
softmax scan gave before the backward was written by hand: a copy of that
forward, differentiated by autodiff, is kept here as the reference.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention
from repro.models.attention import NEG_INF, _chunk_mask, chunked_attention

# max |got - dense| over max |dense|, per score dtype: f32 products are
# exact on the CPU, so only summation order differs; bf16 operands carry
# 8 bits, and a few roundings of 2^-8 stack up in p, dP and dS
TOL = {jnp.float32: 5e-6, jnp.bfloat16: 3e-2}


def _autodiff_chunked_attention(q, k, v, q_positions, k_positions,
                                causal=True, window=None, q_chunk=1024,
                                kv_chunk=1024, score_dtype=jnp.float32):
    """The forward as it was before the custom backward, differentiated by
    autodiff through its scan (which stacks every KV step's residuals)."""
    B, Lq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    sm_scale = 1.0 / math.sqrt(D)
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
    nq, nk = q.shape[1] // qc, k.shape[1] // kc
    q5 = q.reshape(B, nq, qc, Kv, rep, D)
    k4 = k.reshape(B, nk, kc, Kv, D)
    v4 = v.reshape(B, nk, kc, Kv, D)
    qpos = q_positions.reshape(nq, qc)
    kpos = k_positions.reshape(nk, kc)

    def one_q_chunk(args):
        qi, qp = args

        def kv_step(carry, inp):
            acc, m_run, l_run = carry
            ki, vi, kp = inp
            s = jnp.einsum("bqkrd,bskd->bqkrs",
                           qi.astype(score_dtype), ki.astype(score_dtype),
                           preferred_element_type=score_dtype) * sm_scale
            mask = _chunk_mask(qp, kp, causal, window)
            neg = jnp.asarray(-3e4 if score_dtype == jnp.bfloat16 else NEG_INF,
                              score_dtype)
            s = jnp.where(mask[None, :, None, None, :], s, neg)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(score_dtype))
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1, dtype=jnp.float32)
            pv = jnp.einsum("bqkrs,bskd->bqkrd", p, vi.astype(score_dtype),
                            preferred_element_type=jnp.float32)
            return (acc * alpha[..., None] + pv, m_new, l_new), None

        acc0 = jnp.zeros((B, qc, Kv, rep, D), jnp.float32)
        m0 = jnp.full((B, qc, Kv, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, Kv, rep), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(k4, 1, 0), jnp.moveaxis(v4, 1, 0), kpos))
        return acc / jnp.maximum(l_run[..., None], 1e-30)

    outs = jax.lax.map(one_q_chunk, (jnp.moveaxis(q5, 1, 0), qpos))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * qc, H, D)
    return out[:, :Lq]


def _dense_attention(q, k, v, q_positions, k_positions, causal,
                     window: Optional[int]):
    """Softmax over the whole (Lq, Sk) score matrix in f32."""
    B, Lq, H, D = q.shape
    Kv = k.shape[2]
    q5 = q.reshape(B, Lq, Kv, H // Kv, D)
    s = jnp.einsum("bqkrd,bskd->bqkrs", q5, k,
                   precision="highest") / math.sqrt(D)
    mask = _chunk_mask(q_positions, k_positions, causal, window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkrs,bskd->bqkrd", w, v, precision="highest")
    return out.reshape(B, Lq, H, D)


# (B, Lq, Sk, H, Kv, D, causal, window, q_chunk, kv_chunk, q_offset)
# q_offset None: self-attention at positions 0..Lq-1; an int: cross-
# attention with queries at offset + 0..Lq-1 over keys at 0..Sk-1
CASES = {
    "causal_rep1": (2, 32, 32, 4, 4, 16, True, None, 8, 16, None),
    "causal_rep2": (2, 32, 32, 4, 2, 16, True, None, 16, 8, None),
    "causal_rep7_ragged": (1, 37, 37, 7, 1, 8, True, None, 16, 8, None),
    "window_rep2": (2, 40, 40, 4, 2, 8, True, 9, 16, 16, None),
    "window_rep7_ragged": (1, 45, 45, 14, 2, 8, True, 12, 16, 8, None),
    "cross_rep2_ragged": (2, 21, 30, 6, 3, 8, False, None, 8, 16, 5),
    "cross_rep7": (1, 16, 48, 7, 1, 16, False, None, 8, 16, 3),
}


def _inputs(case, seed=0):
    B, Lq, Sk, H, Kv, D, causal, window, qc, kc, q_offset = case
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Lq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, Kv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, Kv, D)), jnp.float32)
    q_pos = jnp.arange(Lq) + (q_offset or 0)
    k_pos = jnp.arange(Sk)
    return q, k, v, q_pos, k_pos


@pytest.mark.parametrize("score_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_chunked_attention_grad_matches_dense(name, score_dtype):
    case = CASES[name]
    causal, window, qc, kc = case[6:10]
    q, k, v, q_pos, k_pos = _inputs(case)
    opts = dict(causal=causal, window=window, q_chunk=qc, kv_chunk=kc,
                score_dtype=score_dtype)

    def new(q, k, v):
        return chunked_attention(q, k, v, q_pos, k_pos, **opts)

    def old(q, k, v):
        return _autodiff_chunked_attention(q, k, v, q_pos, k_pos, **opts)

    # the forward is the parent's, bit for bit: alone and as the primal
    # output of the custom forward rule (which also returns the LSE)
    want = np.asarray(jax.jit(old)(q, k, v))
    np.testing.assert_array_equal(np.asarray(jax.jit(new)(q, k, v)), want)
    out, vjp = jax.vjp(jax.jit(new), q, k, v)
    np.testing.assert_array_equal(np.asarray(out), want)

    ct = jnp.asarray(np.random.default_rng(1).normal(size=out.shape),
                     jnp.float32)
    got = vjp(ct)
    _, dense_vjp = jax.vjp(
        lambda q, k, v: _dense_attention(q, k, v, q_pos, k_pos, causal,
                                         window), q, k, v)
    ref = dense_vjp(ct)
    for g, r, what in zip(got, ref, ("dq", "dk", "dv")):
        assert g.shape == r.shape and g.dtype == r.dtype, what
        err = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
        assert err <= TOL[score_dtype], (what, err)


def test_padded_query_rows_have_finite_lse_and_no_gradient():
    """Rows padded up to a whole Q chunk sit at position -1, see no causal
    key, and take p = 1 on every masked key: their LSE is finite. Their
    cotangent is zero (sliced off), so the gradient is the dense one."""
    B, Lq, H, Kv, D, qc, kc = 1, 20, 4, 2, 8, 16, 8
    q, k, v, q_pos, k_pos = _inputs((B, Lq, Lq, H, Kv, D, True, None, qc, kc,
                                     None))
    pad = (-Lq) % qc
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    kp = jnp.pad(k, ((0, 0), (0, 4), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 4), (0, 0), (0, 0)))
    kpos = jnp.pad(k_pos, (0, 4), constant_values=2**30)
    _, lse = attention._attention_fwd(qp, kp, vp, pos, kpos, True, None, qc,
                                      kc, jnp.float32)
    lse = np.asarray(jnp.moveaxis(lse, 0, 1).reshape(B, Lq + pad, -1))
    assert np.all(np.isfinite(lse))
    assert np.all(lse[:, Lq:] <= NEG_INF / 2)   # no valid key
    assert np.all(lse[:, :Lq] > NEG_INF / 2)

    ct = jnp.asarray(np.random.default_rng(2).normal(size=q.shape),
                     jnp.float32)
    _, vjp = jax.vjp(lambda q, k, v: chunked_attention(
        q, k, v, q_pos, k_pos, q_chunk=qc, kv_chunk=kc), q, k, v)
    _, dense_vjp = jax.vjp(lambda q, k, v: _dense_attention(
        q, k, v, q_pos, k_pos, True, None), q, k, v)
    for g, r in zip(vjp(ct), dense_vjp(ct)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=TOL[jnp.float32]
                                   * float(jnp.max(jnp.abs(r))))


def test_lm_loss_grad_under_remat_matches_autodiff_path(monkeypatch):
    """A 2-layer LM's loss gradient, with layer remat, through the custom
    backward against the same model differentiated through the scan."""
    from repro.configs import get_config
    from repro.core.precision import get_policy
    from repro.models import build_model
    from repro.models.lm import LMCallOptions

    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), n_layers=2)
    model = build_model(cfg, get_policy("fp32"), LMCallOptions(
        remat=True, q_chunk=8, kv_chunk=8))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 28)),
                            jnp.int32) for k in ("tokens", "labels")}

    def grads():
        return jax.jit(jax.grad(lambda p: model.loss(p, batch)[0]))(params)

    new = grads()
    monkeypatch.setattr(attention, "chunked_attention",
                        _autodiff_chunked_attention)
    old = grads()
    leaves = jax.tree_util.tree_leaves_with_path(old)
    assert len(leaves) > 10
    for (path, o), n in zip(leaves, jax.tree_util.tree_leaves(new)):
        o, n = np.asarray(o, np.float64), np.asarray(n, np.float64)
        rel = np.linalg.norm(n - o) / max(np.linalg.norm(o), 1e-30)
        assert rel <= 1e-5, (jax.tree_util.keystr(path), rel)
