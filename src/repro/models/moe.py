"""Mixture-of-Experts layer: top-k routing, capacity-bounded dispatch,
expert-parallel execution.

Dispatch is the classic fixed-capacity scheme (t5x/flaxformer style): each
expert owns a ``(C, d)`` buffer; tokens are scattered into their expert's
buffer in routing-priority order and tokens beyond capacity are dropped
(capacity_factor controls slack). The buffers are sharded over the ``model``
mesh axis on the expert dim when E >= TP (qwen3-moe) or TP-sharded on d_ff
when E < TP (mixtral) — see parallel/sharding.py; the scatter/gather pair is
what shows up as all-to-all traffic in the collective roofline.

Expert FFN GEMMs run under the Mirage policy (vmapped over experts). The
router stays FP32 (small and precision-critical — same spirit as the paper
keeping nonlinearities digital).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.precision import MiragePolicy
from repro.models import common
from repro.obs import health as obs_health


def moe_init(key, d_model: int, n_experts: int, d_ff: int):
    ks = jax.random.split(key, 4)
    std_in = 1.0 / jnp.sqrt(d_model)
    std_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": common.dense_init(ks[0], d_model, n_experts),
        "gate": jax.random.normal(ks[1], (n_experts, d_model, d_ff)) * std_in,
        "up": jax.random.normal(ks[2], (n_experts, d_model, d_ff)) * std_in,
        "down": jax.random.normal(ks[3], (n_experts, d_ff, d_model)) * std_out,
    }


def _expert_ffn(gate_w, up_w, down_w, buf, policy: MiragePolicy):
    """buf: (C, d) for one expert."""
    from repro.core.gemm import mirage_matmul_auto
    h = (jax.nn.silu(mirage_matmul_auto(buf, gate_w, policy))
         * mirage_matmul_auto(buf, up_w, policy))
    return mirage_matmul_auto(h, down_w, policy)


def _expert_ffn_vmapped(gate_w, up_w, down_w, buffers, policy):
    """Expert FFNs vmapped over E. Health records inside the vmap body are
    batch tracers that cannot reach the enclosing scope, so when one is
    open they leave the body as per-expert outputs and their sums are
    re-recorded one level up (same lift as ``obs_health.lifting_scan``)."""
    if not obs_health.active():
        return jax.vmap(_expert_ffn, in_axes=(0, 0, 0, 0, None))(
            gate_w, up_w, down_w, buffers, policy)

    def one(gw, uw, dw, buf):
        with obs_health.collect() as hc:
            out = _expert_ffn(gw, uw, dw, buf, policy)
        return out, dict(hc.values)

    out, h = jax.vmap(one, in_axes=(0, 0, 0, 0))(gate_w, up_w, down_w,
                                                 buffers)
    for name, v in h.items():
        obs_health.record(name, jnp.sum(v, axis=0))
    return out


def moe_apply(p, x, policy: MiragePolicy, *, n_experts: int,
              experts_per_token: int, capacity_factor: float = 1.25,
              min_capacity: int = 4, opt=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, L, d) -> (out (B, L, d), aux_loss scalar)."""
    Bt, L, d = x.shape
    T = Bt * L
    E, K = n_experts, experts_per_token
    xf = x.reshape(T, d)
    xf = common.constrain(xf, opt, ("dp", None))
    # expert-parallel buffers when E divides TP, else capacity over dp
    tp = opt.axis_size(opt.act_tp) if (opt and opt.act_tp) else 1
    ep_ok = tp > 1 and E % tp == 0
    buf_roles = ("tp", None, None) if ep_ok else (None, "dp", None)

    logits = jnp.matmul(xf.astype(jnp.float32), p["router"]["w"])  # (T, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)                # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    C = max(min_capacity, int(capacity_factor * T * K / E))

    # --- position of each (token, slot) inside its expert's buffer ---
    # processed slot-major so slot 0 (highest gate) gets priority.
    positions = []
    fill = jnp.zeros((E,), jnp.int32)
    for j in range(K):
        oh = jax.nn.one_hot(expert_ids[:, j], E, dtype=jnp.int32)  # (T, E)
        pos_within = jnp.cumsum(oh, axis=0) - 1                    # rank among slot-j picks
        pos = jnp.take_along_axis(pos_within, expert_ids[:, j:j+1], axis=1)[:, 0]
        pos = pos + fill[expert_ids[:, j]]
        fill = fill + jnp.sum(oh, axis=0)
        positions.append(pos)
    positions = jnp.stack(positions, axis=1)                       # (T, K)
    keep = positions < C                                           # overflow -> drop

    # --- dispatch: scatter tokens into (E, C, d) buffers ---
    e_flat = expert_ids.reshape(-1)
    pos_flat = jnp.where(keep, positions, C).reshape(-1)           # C = trash slot
    src = jnp.repeat(xf[:, None, :], K, axis=1).reshape(-1, d)
    buffers = jnp.zeros((E, C + 1, d), xf.dtype)
    buffers = buffers.at[e_flat, pos_flat].set(src)
    buffers = buffers[:, :C, :]
    buffers = common.constrain(buffers, opt, buf_roles)   # EP all-to-all here

    # --- expert FFNs (vmapped over E; Mirage GEMMs inside) ---
    out_buffers = _expert_ffn_vmapped(
        p["gate"], p["up"], p["down"], buffers, policy)            # (E, C, d)
    out_buffers = common.constrain(out_buffers, opt, buf_roles)

    # --- combine: gather each token's K results, weight by gates ---
    out_buffers = jnp.concatenate(
        [out_buffers, jnp.zeros((E, 1, d), out_buffers.dtype)], axis=1)
    gathered = out_buffers[e_flat, pos_flat].reshape(T, K, d)
    w = (gate_vals * keep).astype(gathered.dtype)
    out = jnp.einsum("tkd,tk->td", gathered, w)

    # --- load-balancing aux loss (Switch-style) ---
    me = jnp.mean(probs, axis=0)                                   # (E,)
    oh_top1 = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(oh_top1, axis=0)
    aux = E * jnp.sum(me * ce)

    return out.reshape(Bt, L, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf MoE structural fix)
# ---------------------------------------------------------------------------
#
# The GSPMD scatter-dispatch above lowers to scatter + all-reduce + gather +
# all-reduce chains against the model-sharded capacity buffers (measured:
# ~55% of the MoE train collective term). This path exploits that activations
# are REPLICATED across the model axis under our sharding plan: inside
# shard_map each model-rank routes its data-shard's tokens to ITS OWN E/tp
# experts entirely locally, and a single psum over 'model' combines the
# partial outputs — per layer the MoE communication collapses to one
# all-reduce of (tokens_local, d).

def _moe_local(xf, router_w, gate_w, up_w, down_w, *, E, K, C, model_axis,
               dp_axes, policy):
    """Per-device body. xf: (T_loc, d) local tokens (replicated over model);
    expert weights are the local (E_loc, ...) shard."""
    E_loc = gate_w.shape[0]
    m_idx = jax.lax.axis_index(model_axis)
    first = m_idx * E_loc

    logits = jnp.matmul(xf.astype(jnp.float32), router_w)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    local_ids = expert_ids - first                                  # (T, K)
    is_mine = (local_ids >= 0) & (local_ids < E_loc)
    safe_ids = jnp.where(is_mine, local_ids, 0)

    # slot-major positions within each LOCAL expert buffer
    positions = []
    fill = jnp.zeros((E_loc,), jnp.int32)
    for j in range(K):
        oh = jax.nn.one_hot(safe_ids[:, j], E_loc, dtype=jnp.int32)
        oh = oh * is_mine[:, j:j + 1].astype(jnp.int32)
        pos_within = jnp.cumsum(oh, axis=0) - 1
        pos = jnp.take_along_axis(pos_within, safe_ids[:, j:j + 1], axis=1)[:, 0]
        pos = pos + fill[safe_ids[:, j]]
        fill = fill + jnp.sum(oh, axis=0)
        positions.append(pos)
    positions = jnp.stack(positions, axis=1)
    keep = is_mine & (positions < C)

    d = xf.shape[-1]
    e_flat = safe_ids.reshape(-1)
    pos_flat = jnp.where(keep, positions, C).reshape(-1)
    src = jnp.repeat(xf[:, None, :], K, axis=1).reshape(-1, d)
    buffers = jnp.zeros((E_loc, C + 1, d), xf.dtype)
    buffers = buffers.at[e_flat, pos_flat].set(src)[:, :C, :]

    out_buffers = _expert_ffn_vmapped(gate_w, up_w, down_w, buffers, policy)
    out_buffers = jnp.concatenate(
        [out_buffers, jnp.zeros((E_loc, 1, d), out_buffers.dtype)], axis=1)
    gathered = out_buffers[e_flat, pos_flat].reshape(-1, K, d)
    w = (gate_vals * keep).astype(gathered.dtype)
    partial = jnp.einsum("tkd,tk->td", gathered, w)
    out = jax.lax.psum(partial, model_axis)                         # combine

    # global-batch statistics: pmean the per-shard means BEFORE the product
    # (aux is nonlinear in the means — per-shard aux averaged would differ)
    me = jax.lax.pmean(jnp.mean(probs, axis=0), dp_axes)
    oh_top1 = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = jax.lax.pmean(jnp.mean(oh_top1, axis=0), dp_axes)
    aux = E * jnp.sum(me * ce)
    return out, aux


def moe_apply_ep(p, x, policy: MiragePolicy, *, n_experts: int,
                 experts_per_token: int, capacity_factor: float = 1.25,
                 min_capacity: int = 4, opt=None):
    """shard_map expert-parallel MoE. Requires E % tp == 0 and an activation
    sharding plan (opt.act_dp/act_tp); falls back to moe_apply otherwise."""
    from jax.sharding import PartitionSpec as P

    tp = opt.axis_size(opt.act_tp) if (opt and opt.act_tp) else 1
    if tp <= 1 or n_experts % tp != 0:
        return moe_apply(p, x, policy, n_experts=n_experts,
                         experts_per_token=experts_per_token,
                         capacity_factor=capacity_factor,
                         min_capacity=min_capacity, opt=opt)

    Bt, L, d = x.shape
    dp_total = opt.axis_size(opt.act_dp)
    T_loc = max((Bt // max(dp_total, 1)) * L, L)
    E, K = n_experts, experts_per_token
    C = max(min_capacity, int(capacity_factor * T_loc * K / E))

    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh   # the `with mesh:` context
    if mesh.empty:
        return moe_apply(p, x, policy, n_experts=n_experts,
                         experts_per_token=experts_per_token,
                         capacity_factor=capacity_factor,
                         min_capacity=min_capacity, opt=opt)
    dp, tp_ax = opt.act_dp, opt.act_tp
    xf = x.reshape(Bt * L, d)

    fn = functools.partial(_moe_local, E=E, K=K, C=C, model_axis=tp_ax,
                           dp_axes=dp, policy=policy)

    def fn_no_health(*args):
        # shard_map body tracers cannot reach the enclosing health scope
        # (same wall as lax.cond branches) — suppress rather than leak
        with obs_health.suppressed():
            return fn(*args)

    out, aux = jax.shard_map(
        fn_no_health, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), P(tp_ax, None, None),
                  P(tp_ax, None, None), P(tp_ax, None, None)),
        out_specs=(P(dp, None), P()),
        check_vma=False,
    )(xf, p["router"]["w"], p["gate"], p["up"], p["down"])
    return out.reshape(Bt, L, d), aux
