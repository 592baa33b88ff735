"""Unified decoder-only LM covering the dense / moe / ssm / hybrid / vlm
architecture families. Layers are stacked (vmapped init) and executed with
``lax.scan`` so the traced HLO is one layer deep regardless of depth — this
keeps 64-layer 104B dry-run compiles tractable and is also the remat boundary.

Family mapping:
  dense  : pre-norm GQA attention + (Sw/Gelu)MLP; optional parallel block
           (command-r), QKV bias (qwen2), qk_norm (qwen3), SWA (mixtral).
  moe    : attention + MoE FFN (qwen3-moe, mixtral).
  ssm    : Mamba2 (SSD) blocks only (mamba2-2.7b).
  hybrid : Mamba2 stack + one SHARED attention/MLP block applied every
           ``attn_every`` layers on concat(hidden, embeddings) (zamba2).
  vlm    : dense LM consuming [projected patch embeddings; text tokens].
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import gemm
from repro.core.precision import MiragePolicy
from repro.models import attention, common, mamba2, moe
from repro.obs import health as obs_health


@dataclasses.dataclass(frozen=True)
class LMCallOptions:
    """Mesh/runtime-dependent knobs that don't change parameters."""
    kv_repeat: int = 1          # repeat kv heads so TP divides them
    q_chunk: int = 1024
    kv_chunk: int = 1024
    remat: bool = False
    carry_dtype: str = "float32"  # scan-carry activations (bf16 at scale)
    ce_chunk: int = 0             # chunked CE loss (0 = unchunked)
    # Activation sharding constraints (None = let GSPMD propagate freely).
    # act_dp/act_tp name mesh axes; mesh_sizes carries their sizes so the
    # constraint helper can fall back to replication on non-divisible dims.
    attn_dtype: str = "float32"   # bf16 scores halve attention HBM traffic
    # parallel-block projection merge (command-r): one row-sharded GEMM for
    # [attn_ctx ; mlp_hidden] -> d, i.e. ONE TP all-reduce per layer not two
    merge_parallel_proj: bool = False
    moe_impl: str = "gspmd"       # gspmd | ep_shard_map (§Perf MoE fix)
    use_flash_kernel: bool = False  # Pallas flash attention (TPU; §Perf FA)
    act_dp: Optional[Tuple[str, ...]] = None
    act_tp: Optional[str] = None
    mesh_sizes: Tuple[Tuple[str, int], ...] = ()

    @property
    def carry(self):
        return jnp.bfloat16 if self.carry_dtype == "bfloat16" else jnp.float32

    def axis_size(self, ax) -> int:
        sizes = dict(self.mesh_sizes)
        if isinstance(ax, tuple):
            n = 1
            for a in ax:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(ax, 1)


def _layer_noise_scoped(body):
    """Wrap a layer-scan body so stochastic GEMMs under an ambient
    ``gemm.noise_key_scope`` fold the TRACED layer index (the last element
    of ``xs``) into their keys. The scan body is traced once, so the
    scope's per-call-site counter alone would hand every layer the same
    noise realization per GEMM site; folding the index restores per-layer
    independent draws. No-op when no scope is open (training, deterministic
    serving).

    Also lifts analog-health records (``repro.obs.health``) out of the
    body as extra stacked outputs — a scan body's tracers cannot reach the
    enclosing scope directly — so every scan over a body wrapped here MUST
    run through ``obs_health.lifting_scan``, which folds the stack back
    into the outer scope."""
    def wrapped(carry, xs):
        with gemm.fold_noise_scope(xs[-1]):
            return body(carry, xs)
    return obs_health.lifted(wrapped)


def _cond_suppressed(fn):
    """Run a ``lax.cond`` branch with health collection suppressed: a
    branch trace has no output channel a wrapper can widen (cond demands
    identical pytrees from both branches, and the identity branch records
    nothing), so GEMMs in the hybrid family's shared block go uncounted
    rather than leak branch tracers into the enclosing scope."""
    def wrapped(args):
        with obs_health.suppressed():
            return fn(args)
    return wrapped


def chunked_ce(h: jax.Array, labels: jax.Array, head_fn, chunk: int):
    """Cross-entropy without materializing (T, V) logits: scan over token
    chunks, recomputing each chunk's logits in the backward pass (checkpoint).

    h: (T, d) hidden states, labels: (T,). Returns mean CE."""
    T = h.shape[0]
    chunk = min(chunk, T) if chunk else T
    pad = (-T) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    nch = h.shape[0] // chunk
    hc = h.reshape(nch, chunk, -1)
    lc = labels.reshape(nch, chunk)

    def body(acc, xs):
        hh, ll = xs
        logits = head_fn(hh).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = ll >= 0
        ce = -jnp.sum(jnp.where(
            valid,
            jnp.take_along_axis(logp, jnp.maximum(ll, 0)[:, None],
                                axis=-1)[:, 0],
            0.0))
        return acc + ce, None

    # lift INSIDE the checkpoint: the head GEMM's health records must exit
    # through the remat's real output channel, not the thread-local
    body = jax.checkpoint(obs_health.lifted(body), prevent_cse=False)
    total, _ = obs_health.lifting_scan(body, jnp.zeros(()), (hc, lc))
    return total / T


class LM:
    def __init__(self, cfg: ModelConfig, policy: MiragePolicy,
                 options: LMCallOptions = LMCallOptions()):
        self.cfg = cfg
        self.policy = policy
        self.opt = options
        kinds = set(cfg.layer_kinds())
        assert len(kinds) == 1, kinds
        self.kind = kinds.pop()

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _layer_init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        ks = jax.random.split(key, 4)
        if self.kind == "mamba":
            return {"ln1": common.norm_init(cfg.d_model, cfg.norm_type),
                    "mamba": mamba2.mamba_init(ks[0], cfg)}
        p = {
            "ln1": common.norm_init(cfg.d_model, cfg.norm_type),
            "attn": attention.attn_init(
                ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                cfg.qkv_bias, cfg.qk_norm),
            "ln2": common.norm_init(cfg.d_model, cfg.norm_type),
        }
        if self.kind == "attn_moe":
            p["moe"] = moe.moe_init(ks[1], cfg.d_model, cfg.n_experts,
                                    cfg.moe_d_ff)
        else:
            p["mlp"] = common.mlp_init(ks[1], cfg.d_model, cfg.d_ff, "swiglu",
                                       cfg.qkv_bias and False)
        return p

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        layer_keys = jax.random.split(keys[0], cfg.n_layers)
        params: Dict[str, Any] = {
            "embed": common.embed_init(keys[1], cfg.vocab_size, cfg.d_model),
            "layers": jax.vmap(self._layer_init)(layer_keys),
            "final_norm": common.norm_init(cfg.d_model, cfg.norm_type),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = common.dense_init(
                keys[2], cfg.d_model, cfg.vocab_size, False, scale=0.02)
        if cfg.family == "hybrid":
            hd = cfg.resolved_head_dim
            sk = jax.random.split(keys[3], 4)
            params["shared"] = {
                "proj": common.dense_init(sk[0], 2 * cfg.d_model, cfg.d_model),
                "ln1": common.norm_init(cfg.d_model, cfg.norm_type),
                "attn": attention.attn_init(
                    sk[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                    False, False),
                "ln2": common.norm_init(cfg.d_model, cfg.norm_type),
                "mlp": common.mlp_init(sk[2], cfg.d_model, cfg.d_ff),
            }
        if cfg.frontend is not None:
            fk = jax.random.split(keys[4], 2)
            params["frontend_proj"] = {
                "fc1": common.dense_init(fk[0], cfg.frontend_dim, cfg.d_model),
                "fc2": common.dense_init(fk[1], cfg.d_model, cfg.d_model),
            }
        return params

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------

    def _embed_inputs(self, params, tokens, extra_embeds):
        h = common.embed(params["embed"], tokens)
        n_prefix = 0
        if extra_embeds is not None:
            proj = params["frontend_proj"]
            pe = common.dense(proj["fc2"],
                              jax.nn.gelu(common.dense(proj["fc1"], extra_embeds,
                                                       self.policy)),
                              self.policy)
            h = jnp.concatenate([pe, h], axis=1)
            n_prefix = extra_embeds.shape[1]
        return h, n_prefix

    def _head(self, params, h):
        with jax.named_scope("final_norm"):
            h = common.norm(params["final_norm"], h, self.cfg.norm_eps,
                            self.cfg.norm_type)
        if self.cfg.tie_embeddings:
            return common.unembed(params["embed"], h, self.policy)
        return common.dense(params["lm_head"], h, self.policy)

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------

    def _attn_mlp_block(self, lp, h, positions, aux):
        cfg, policy, opt = self.cfg, self.policy, self.opt
        hd = cfg.resolved_head_dim
        parallel = cfg.arch_id.startswith("command-r")
        merge = parallel and opt.merge_parallel_proj
        with jax.named_scope("attn_norm"):
            n1 = common.norm(lp["ln1"], h, cfg.norm_eps, cfg.norm_type)
        a, _ = attention.attn_apply(
            lp["attn"], n1, policy, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=hd, positions=positions,
            rope_theta=cfg.rope_theta, causal=True, window=cfg.sliding_window,
            qk_norm=cfg.qk_norm, kv_repeat=opt.kv_repeat,
            q_chunk=opt.q_chunk, kv_chunk=opt.kv_chunk, opt=opt,
            skip_o_proj=merge)
        if self.kind == "attn_moe":
            with jax.named_scope("attn_out"):
                h = h + a
            with jax.named_scope("mlp_norm"):
                n2 = common.norm(lp["ln2"], h, cfg.norm_eps, cfg.norm_type)
            moe_fn = (moe.moe_apply_ep if opt.moe_impl == "ep_shard_map"
                      else moe.moe_apply)
            with jax.named_scope("mlp"):
                m, aux_l = moe_fn(
                    lp["moe"], n2, policy, n_experts=cfg.n_experts,
                    experts_per_token=cfg.experts_per_token,
                    capacity_factor=cfg.capacity_factor, opt=self.opt)
                return h + m, aux + aux_l
        # command-r parallel block: attn and mlp both read ln1(h)
        if parallel:
            if merge:
                # §Perf iteration 3: merge the two row-sharded projections
                # (attn o-proj + mlp down-proj) into ONE GEMM -> one TP
                # all-reduce per layer instead of two. Identical math: the
                # concat dims align with g-groups and TP shard boundaries.
                hh = (jax.nn.silu(common.dense(lp["mlp"]["gate"], n1, policy))
                      * common.dense(lp["mlp"]["up"], n1, policy))
                hh = common.constrain(hh, opt, ("dp", None, "tp"))
                cat = jnp.concatenate([a, hh], axis=-1)
                w_cat = jnp.concatenate(
                    [lp["attn"]["o"]["w"], lp["mlp"]["down"]["w"]], axis=0)
                from repro.core.gemm import mirage_matmul_auto
                return h + mirage_matmul_auto(cat, w_cat, policy), aux
            with jax.named_scope("mlp"):
                m = common.mlp(lp["mlp"], n1, policy, opt=self.opt)
                return h + a + m, aux
        with jax.named_scope("attn_out"):
            h = h + a
        with jax.named_scope("mlp_norm"):
            n2 = common.norm(lp["ln2"], h, cfg.norm_eps, cfg.norm_type)
        with jax.named_scope("mlp"):
            return h + common.mlp(lp["mlp"], n2, policy, opt=self.opt), aux

    def _mamba_block(self, lp, h):
        cfg = self.cfg
        n1 = common.norm(lp["ln1"], h, cfg.norm_eps, cfg.norm_type)
        return h + mamba2.mamba_apply(lp["mamba"], n1, cfg, self.policy,
                                    opt=self.opt)

    def _post_attn_combine(self, lp, hh, n1, a, aux):
        """Residual + FFN tail shared by the full-prefill and chunked-prefill
        layer bodies (moe / command-r parallel / default pre-norm MLP)."""
        cfg = self.cfg
        if self.kind == "attn_moe":
            hh = hh + a
            n2 = common.norm(lp["ln2"], hh, cfg.norm_eps, cfg.norm_type)
            m, aux_l = moe.moe_apply(
                lp["moe"], n2, self.policy, n_experts=cfg.n_experts,
                experts_per_token=cfg.experts_per_token,
                capacity_factor=cfg.capacity_factor, opt=self.opt)
            return hh + m, aux + aux_l
        if cfg.arch_id.startswith("command-r"):
            return (hh + a + common.mlp(lp["mlp"], n1, self.policy,
                                        opt=self.opt), aux)
        hh = hh + a
        n2 = common.norm(lp["ln2"], hh, cfg.norm_eps, cfg.norm_type)
        return hh + common.mlp(lp["mlp"], n2, self.policy, opt=self.opt), aux

    def _shared_block(self, sp, h, emb0, positions):
        cfg, opt = self.cfg, self.opt
        hd = cfg.resolved_head_dim
        u = common.dense(sp["proj"], jnp.concatenate([h, emb0], axis=-1),
                         self.policy)
        n1 = common.norm(sp["ln1"], u, cfg.norm_eps, cfg.norm_type)
        a, _ = attention.attn_apply(
            sp["attn"], n1, self.policy, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=hd, positions=positions,
            rope_theta=cfg.rope_theta, causal=True,
            kv_repeat=opt.kv_repeat, q_chunk=opt.q_chunk,
            kv_chunk=opt.kv_chunk)
        u = u + a
        n2 = common.norm(sp["ln2"], u, cfg.norm_eps, cfg.norm_type)
        return h + u + common.mlp(sp["mlp"], n2, self.policy, opt=self.opt)

    # ------------------------------------------------------------------
    # forward (train / prefill logits over the full sequence)
    # ------------------------------------------------------------------

    def forward_hidden(self, params, tokens, extra_embeds=None):
        """Run the layer stack; returns (hidden, aux, n_prefix)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            h, n_prefix = self._embed_inputs(params, tokens, extra_embeds)
            h = h.astype(self.opt.carry)
            positions = jnp.arange(h.shape[1])
        emb0 = h
        aux0 = jnp.zeros((), jnp.float32)

        def body(carry, xs):
            hh, aux = carry
            lp, idx = xs
            if self.kind == "mamba":
                hh = self._mamba_block(lp, hh)
                if cfg.attn_every:
                    hh = jax.lax.cond(
                        (idx + 1) % cfg.attn_every == 0,
                        lambda v: self._shared_block(params["shared"], v,
                                                     emb0, positions),
                        lambda v: v, hh)
            else:
                hh, aux = self._attn_mlp_block(lp, hh, positions, aux)
            return (hh.astype(self.opt.carry), aux), None

        body = _layer_noise_scoped(body)
        if self.opt.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            (h, aux), _ = obs_health.lifting_scan(
                body, (h, aux0),
                (params["layers"], jnp.arange(cfg.n_layers)))
        return h, aux, n_prefix

    def forward(self, params, tokens, extra_embeds=None):
        h, aux, n_prefix = self.forward_hidden(params, tokens, extra_embeds)
        logits = self._head(params, h)
        return logits, aux, n_prefix

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        tokens = batch["tokens"]
        labels = batch["labels"]
        h, aux, n_prefix = self.forward_hidden(
            params, tokens, batch.get("patches"))
        with jax.named_scope("head_ce"):
            h = h[:, n_prefix:, :]
            B, L, d = h.shape
            if self.opt.ce_chunk:
                head_fn = lambda hh: self._head(params, hh)
                ce = chunked_ce(h.reshape(B * L, d), labels.reshape(B * L),
                                head_fn, self.opt.ce_chunk)
            else:
                logits = self._head(params, h)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                          axis=-1)
                ll = jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0]
                ce = -jnp.mean(ll)
            total = ce + (self.cfg.router_aux_loss * aux
                          / max(self.cfg.n_layers, 1))
            return total, {"ce": ce, "aux": aux,
                           "ppl": jnp.exp(jnp.minimum(ce, 20.0))}

    # ------------------------------------------------------------------
    # serving: prefill + single-token decode with caches
    # ------------------------------------------------------------------

    def cache_spec(self, batch: int, cap: int,
                   per_slot_idx: bool = False, layout: str = "dense",
                   block_size: int = 16,
                   n_blocks: Optional[int] = None) -> Dict[str, Any]:
        """Abstract cache shapes (used by init_cache and the dry-run specs).

        ``per_slot_idx=True`` is the continuous-batching layout: ``idx`` is a
        ``(batch,)`` vector (each serving slot decodes at its own position)
        instead of one scalar shared by the whole batch.

        ``layout="paged"`` (implies per-slot idx) replaces the per-slot KV
        rings with global page pools plus per-slot block tables:

          * ``kp``/``vp`` (or ``shared_kp``/``shared_vp`` for the hybrid
            family): ``(n_layers, n_blocks, block_size, kv_eff, hd)`` — ONE
            pool shared by every slot, sized by the live-token budget
            (default ``batch * ceil(cap / block_size)`` blocks = no saving
            but always safe; servers pass a smaller pool to realize the
            paged-memory win);
          * ``bt``: ``(batch, ceil(cap / block_size))`` int32 block table,
            unmapped entries hold the OOB sentinel ``n_blocks``.

        Addressing is linear (logical position p -> table entry ``p //
        block_size``), no ring wrap: sliding windows are applied through the
        attention validity mask instead, so paged SWA capacity is ``cap``
        positions rather than ``min(cap, window)``. SSM recurrent state
        (``ssm``/``conv``) is O(1) per slot and stays dense under both
        layouts.

        Under a mesh (``parallel.sharding.cache_spec``) the pools shard on
        the BLOCK dim over the data axis, so the page gathers in
        ``attention.attn_decode_step`` / ``attn_chunk_step`` /
        ``attn_verify_step`` (``cache_k[block_tables]``) cross shards
        whenever a slot's table points at a block homed on another data
        shard — GSPMD inserts the collective. The host-side
        ``runtime.paging.BlockAllocator`` keeps those gathers local by
        preferring blocks from the slot's home shard (``shard_of_block = b
        // per_shard``, matching XLA's contiguous-chunk layout); its
        ``remote_fraction()`` gauge is the observable for how often the
        gather actually crosses shards."""
        if layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache layout {layout!r}")
        paged = layout == "paged"
        if paged:
            per_slot_idx = True
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        nl = cfg.n_layers
        from repro.runtime.paging import blocks_for
        mb = blocks_for(cap, block_size)
        nb = n_blocks if n_blocks is not None else batch * mb
        spec: Dict[str, Any] = {
            "idx": (((batch,) if per_slot_idx else ()), jnp.int32)}
        if self.kind == "mamba":
            H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
            conv_dim = cfg.d_inner + 2 * N
            spec["ssm"] = ((nl, batch, H, P, N), jnp.float32)
            spec["conv"] = ((nl, batch, cfg.ssm_conv - 1, conv_dim), jnp.float32)
            if cfg.attn_every:
                napp = cfg.n_layers // cfg.attn_every
                kv_eff = cfg.n_kv_heads * self.opt.kv_repeat
                if paged:
                    spec["shared_kp"] = ((napp, nb, block_size, kv_eff, hd),
                                         jnp.float32)
                    spec["shared_vp"] = ((napp, nb, block_size, kv_eff, hd),
                                         jnp.float32)
                    spec["bt"] = ((batch, mb), jnp.int32)
                else:
                    cache_len = min(cap, cfg.sliding_window or cap)
                    spec["shared_k"] = ((napp, batch, cache_len, kv_eff, hd),
                                        jnp.float32)
                    spec["shared_v"] = ((napp, batch, cache_len, kv_eff, hd),
                                        jnp.float32)
        else:
            kv_eff = cfg.n_kv_heads * self.opt.kv_repeat
            if paged:
                spec["kp"] = ((nl, nb, block_size, kv_eff, hd), jnp.float32)
                spec["vp"] = ((nl, nb, block_size, kv_eff, hd), jnp.float32)
                spec["bt"] = ((batch, mb), jnp.int32)
            else:
                cache_len = min(cap, cfg.sliding_window or cap)
                spec["k"] = ((nl, batch, cache_len, kv_eff, hd), jnp.float32)
                spec["v"] = ((nl, batch, cache_len, kv_eff, hd), jnp.float32)
        return spec

    def init_cache(self, batch: int, cap: int,
                   per_slot_idx: bool = False, layout: str = "dense",
                   block_size: int = 16,
                   n_blocks: Optional[int] = None) -> Dict[str, Any]:
        spec = self.cache_spec(batch, cap, per_slot_idx, layout=layout,
                               block_size=block_size, n_blocks=n_blocks)
        cache = {k: jnp.zeros(s, d) for k, (s, d) in spec.items()}
        if "bt" in cache:
            # unmapped table entries carry the OOB sentinel (= pool size):
            # scatter-writes drop, gathers clamp + get masked
            pool = spec.get("kp", spec.get("shared_kp"))
            cache["bt"] = jnp.full(spec["bt"][0], pool[0][1], jnp.int32)
        return cache

    def prefill(self, params, tokens, cap: int, extra_embeds=None, lens=None):
        """Run the prompt, build the cache, return last-position logits.

        ``lens``: optional ``(B,)`` true prompt lengths for right-padded
        batched prefill (continuous-batching buckets). When given, the
        returned logits are gathered at each row's last REAL token, and the
        cache carries a per-slot ``(B,)`` ``idx`` = ``lens`` — decode then
        overwrites the padded garbage positions one token at a time while the
        attention validity mask (slots at positions >= idx) hides them.
        Requires the padded length to fit the cache (no ring wrap during
        prefill). Right-padding is exact for attention families (causal mask:
        real positions never read padded ones); SSM/hybrid recurrences carry
        state *through* padded steps, so callers there must pad to the exact
        length (``lens == L``) — the server's bucketer does exactly that.
        """
        cfg = self.cfg
        h, n_prefix = self._embed_inputs(params, tokens, extra_embeds)
        B, L = h.shape[0], h.shape[1]
        if lens is not None:
            cache_len_chk = min(cap, cfg.sliding_window or cap)
            if L > cache_len_chk:
                raise ValueError(
                    f"padded prefill length {L} exceeds cache capacity "
                    f"{cache_len_chk}; raise cap or shrink the bucket")
        positions = jnp.arange(L)
        emb0 = h
        cache = self.init_cache(B, cap)
        cache_len = min(cap, cfg.sliding_window or cap)
        aux0 = jnp.zeros((), jnp.float32)

        if self.kind == "mamba":
            def body(carry, xs):
                hh, aux, shk, shv = carry
                lp, idx = xs
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                o, (st, cv) = mamba2.mamba_apply(
                    lp["mamba"], n1, cfg, self.policy, return_cache=True,
                    opt=self.opt)
                hh = hh + o
                if cfg.attn_every:
                    napp = cfg.n_layers // cfg.attn_every
                    app = (idx + 1) // cfg.attn_every - 1

                    def do_shared(args):
                        v, shk_, shv_ = args
                        hd = cfg.resolved_head_dim
                        u = common.dense(
                            params["shared"]["proj"],
                            jnp.concatenate([v, emb0], axis=-1), self.policy)
                        n = common.norm(params["shared"]["ln1"], u,
                                        cfg.norm_eps, cfg.norm_type)
                        a, (kk, vv) = attention.attn_apply(
                            params["shared"]["attn"], n, self.policy,
                            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                            head_dim=hd, positions=positions,
                            rope_theta=cfg.rope_theta, causal=True,
                            kv_repeat=self.opt.kv_repeat,
                            q_chunk=self.opt.q_chunk, kv_chunk=self.opt.kv_chunk, opt=self.opt)
                        u = u + a
                        n2 = common.norm(params["shared"]["ln2"], u,
                                         cfg.norm_eps, cfg.norm_type)
                        v = v + u + common.mlp(params["shared"]["mlp"], n2,
                                               self.policy, opt=self.opt)
                        kk = kk[:, -cache_len:]
                        vv = vv[:, -cache_len:]
                        pk = jnp.pad(kk, ((0, 0), (0, cache_len - kk.shape[1]),
                                          (0, 0), (0, 0)))
                        pv = jnp.pad(vv, ((0, 0), (0, cache_len - vv.shape[1]),
                                          (0, 0), (0, 0)))
                        shk_ = jax.lax.dynamic_update_index_in_dim(
                            shk_, pk, jnp.maximum(app, 0), 0)
                        shv_ = jax.lax.dynamic_update_index_in_dim(
                            shv_, pv, jnp.maximum(app, 0), 0)
                        return v, shk_, shv_

                    hh, shk, shv = jax.lax.cond(
                        (idx + 1) % cfg.attn_every == 0, _cond_suppressed(do_shared),
                        lambda args: args, (hh, shk, shv))
                return (hh, aux, shk, shv), (st, cv)

            shk = cache.get("shared_k", jnp.zeros((1,), jnp.float32))
            shv = cache.get("shared_v", jnp.zeros((1,), jnp.float32))
            (h, aux, shk, shv), (ssm, conv) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, aux0, shk, shv),
                (params["layers"], jnp.arange(cfg.n_layers)))
            cache["ssm"], cache["conv"] = ssm, conv
            if cfg.attn_every:
                cache["shared_k"], cache["shared_v"] = shk, shv
        else:
            def body(carry, xs):
                hh, aux = carry
                lp, idx = xs
                hd = cfg.resolved_head_dim
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                a, (kk, vv) = attention.attn_apply(
                    lp["attn"], n1, self.policy, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=hd,
                    positions=positions, rope_theta=cfg.rope_theta,
                    causal=True, window=cfg.sliding_window,
                    qk_norm=cfg.qk_norm, kv_repeat=self.opt.kv_repeat,
                    q_chunk=self.opt.q_chunk, kv_chunk=self.opt.kv_chunk, opt=self.opt)
                hh, aux = self._post_attn_combine(lp, hh, n1, a, aux)
                # keep the last cache_len positions (ring layout: pos % cache_len)
                kk = kk[:, -cache_len:]
                vv = vv[:, -cache_len:]
                start = jnp.maximum(L - cache_len, 0)
                roll = jnp.mod(start, cache_len)
                kk = jnp.roll(kk, roll, axis=1)
                vv = jnp.roll(vv, roll, axis=1)
                pad_n = cache_len - kk.shape[1]
                if pad_n:
                    kk = jnp.pad(kk, ((0, 0), (0, pad_n), (0, 0), (0, 0)))
                    vv = jnp.pad(vv, ((0, 0), (0, pad_n), (0, 0), (0, 0)))
                return (hh, aux), (kk, vv)

            (h, aux), (ks, vs) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, aux0),
                (params["layers"], jnp.arange(cfg.n_layers)))
            cache["k"], cache["v"] = ks, vs

        if lens is None:
            cache["idx"] = jnp.asarray(L, jnp.int32)
            h_last = h[:, -1:, :]
        else:
            lens = jnp.asarray(lens, jnp.int32)
            cache["idx"] = lens
            h_last = jnp.take_along_axis(
                h, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)
        logits = self._head(params, h_last)
        return logits, cache

    def decode_step(self, params, cache, tokens):
        """tokens: (B, 1). Returns (logits (B, 1, V), new cache).

        Layout is inferred from the cache keys: a ``bt`` leaf selects the
        paged path (page-pool leaves ``kp``/``vp`` or ``shared_kp``/
        ``shared_vp``; block tables shared across layers), otherwise the
        dense per-slot rings."""
        cfg = self.cfg
        h = common.embed(params["embed"], tokens)
        emb0 = h
        idx = cache["idx"]
        bt = cache.get("bt")
        k_key, v_key = ("kp", "vp") if "kp" in cache else ("k", "v")
        shk_key, shv_key = (("shared_kp", "shared_vp")
                            if "shared_kp" in cache
                            else ("shared_k", "shared_v"))

        if self.kind == "mamba":
            def body(carry, xs):
                hh, shk, shv = carry
                lp, ssm_st, conv_st, li = xs
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                o, ssm_st, conv_st = mamba2.mamba_decode_step(
                    lp["mamba"], n1, cfg, self.policy, ssm_st, conv_st)
                hh = hh + o
                if cfg.attn_every:
                    app = (li + 1) // cfg.attn_every - 1

                    def do_shared(args):
                        v, shk_, shv_ = args
                        hd = cfg.resolved_head_dim
                        u = common.dense(
                            params["shared"]["proj"],
                            jnp.concatenate([v, emb0], axis=-1), self.policy)
                        n = common.norm(params["shared"]["ln1"], u,
                                        cfg.norm_eps, cfg.norm_type)
                        ck = shk_[jnp.maximum(app, 0)]
                        cv = shv_[jnp.maximum(app, 0)]
                        a, ck, cv = attention.attn_decode_step(
                            params["shared"]["attn"], n, ck, cv, idx,
                            self.policy, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads, head_dim=hd,
                            rope_theta=cfg.rope_theta,
                            kv_repeat=self.opt.kv_repeat,
                            block_tables=bt)
                        shk_ = jax.lax.dynamic_update_index_in_dim(
                            shk_, ck, jnp.maximum(app, 0), 0)
                        shv_ = jax.lax.dynamic_update_index_in_dim(
                            shv_, cv, jnp.maximum(app, 0), 0)
                        u = u + a
                        n2 = common.norm(params["shared"]["ln2"], u,
                                         cfg.norm_eps, cfg.norm_type)
                        return (v + u + common.mlp(params["shared"]["mlp"], n2,
                                                   self.policy, opt=self.opt), shk_, shv_)

                    hh, shk, shv = jax.lax.cond(
                        (li + 1) % cfg.attn_every == 0, _cond_suppressed(do_shared),
                        lambda args: args, (hh, shk, shv))
                return (hh, shk, shv), (ssm_st, conv_st)

            shk = cache.get(shk_key, jnp.zeros((1,), jnp.float32))
            shv = cache.get(shv_key, jnp.zeros((1,), jnp.float32))
            (h, shk, shv), (ssm, conv) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, shk, shv),
                (params["layers"], cache["ssm"], cache["conv"],
                 jnp.arange(cfg.n_layers)))
            cache = dict(cache, ssm=ssm, conv=conv)
            if cfg.attn_every:
                cache[shk_key], cache[shv_key] = shk, shv
        else:
            def body(hh, xs):
                lp, ck, cv, _li = xs
                hd = cfg.resolved_head_dim
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                a, ck, cv = attention.attn_decode_step(
                    lp["attn"], n1, ck, cv, idx, self.policy,
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=hd, rope_theta=cfg.rope_theta,
                    window=cfg.sliding_window, qk_norm=cfg.qk_norm,
                    kv_repeat=self.opt.kv_repeat, block_tables=bt)
                hh, _ = self._post_attn_combine(
                    lp, hh, n1, a, jnp.zeros((), jnp.float32))
                return hh, (ck, cv)

            h, (ks, vs) = obs_health.lifting_scan(
                _layer_noise_scoped(body), h,
                (params["layers"], cache[k_key], cache[v_key],
                 jnp.arange(cfg.n_layers)))
            cache = dict(cache, **{k_key: ks, v_key: vs})

        cache["idx"] = idx + 1
        logits = self._head(params, h)
        return logits, cache

    def verify_step(self, params, cache, tokens):
        """Speculative-decoding verify: score ``T`` tokens per slot in ONE
        step over a stacked PAGED cache.

        tokens: ``(S, T)`` — per slot, ``[current token ; T-1 draft
        tokens]`` occupying positions ``idx[s] .. idx[s]+T-1``. Returns
        ``(logits (S, T, V), new cache, steps)``. ``cache["idx"]`` is NOT
        advanced — the caller accepts a per-slot count ``a`` and commits
        ``idx += a`` itself (rejected tails need no KV rollback: the next
        verify tick's writes land on exactly those positions before any
        gather reads them — see :func:`attention.attn_verify_step`).

        ``steps`` is ``None`` for pure-attention families. For SSM/hybrid
        families it is ``{"ssm": (nl, T, S, H, P, N), "conv": (nl, T, S,
        K-1, C)}`` — the recurrent state AFTER each of the ``T`` tokens, so
        the caller can roll back to the state at the accepted position
        (``steps[...][:, a-1]``); the returned cache's ``ssm``/``conv``
        leaves hold the full-T state and must be overwritten from
        ``steps``. Token-exact vs. one-token decode under greedy: the
        per-token recurrence is the same ``mamba_decode_step`` scan.
        """
        cfg = self.cfg
        h = common.embed(params["embed"], tokens)      # (S, T, d)
        emb0 = h
        idx = cache["idx"]
        bt = cache.get("bt")

        if self.kind == "mamba":
            def body(carry, xs):
                hh, shk, shv = carry
                lp, ssm_st, conv_st, li = xs
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)

                def tok_step(st, x_t):
                    ssm, conv = st
                    o, ssm, conv = mamba2.mamba_decode_step(
                        lp["mamba"], x_t[:, None], cfg, self.policy, ssm,
                        conv)
                    return (ssm, conv), (o[:, 0], ssm, conv)

                (_, _), (o_seq, ssm_steps, conv_steps) = \
                    obs_health.lifting_scan(
                        obs_health.lifted(tok_step), (ssm_st, conv_st),
                        jnp.moveaxis(n1, 1, 0))
                hh = hh + jnp.moveaxis(o_seq, 0, 1)
                if cfg.attn_every:
                    app = (li + 1) // cfg.attn_every - 1

                    def do_shared(args):
                        v, shk_, shv_ = args
                        hd = cfg.resolved_head_dim
                        u = common.dense(
                            params["shared"]["proj"],
                            jnp.concatenate([v, emb0], axis=-1), self.policy)
                        n = common.norm(params["shared"]["ln1"], u,
                                        cfg.norm_eps, cfg.norm_type)
                        ck = shk_[jnp.maximum(app, 0)]
                        cv = shv_[jnp.maximum(app, 0)]
                        a, ck, cv = attention.attn_verify_step(
                            params["shared"]["attn"], n, ck, cv, idx,
                            self.policy, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads,
                            head_dim=hd, rope_theta=cfg.rope_theta,
                            kv_repeat=self.opt.kv_repeat, block_tables=bt)
                        shk_ = jax.lax.dynamic_update_index_in_dim(
                            shk_, ck, jnp.maximum(app, 0), 0)
                        shv_ = jax.lax.dynamic_update_index_in_dim(
                            shv_, cv, jnp.maximum(app, 0), 0)
                        u = u + a
                        n2 = common.norm(params["shared"]["ln2"], u,
                                         cfg.norm_eps, cfg.norm_type)
                        return (v + u + common.mlp(params["shared"]["mlp"],
                                                   n2, self.policy,
                                                   opt=self.opt), shk_, shv_)

                    hh, shk, shv = jax.lax.cond(
                        (li + 1) % cfg.attn_every == 0, _cond_suppressed(do_shared),
                        lambda args: args, (hh, shk, shv))
                return (hh, shk, shv), (ssm_steps, conv_steps)

            shk = cache.get("shared_kp", jnp.zeros((1,), jnp.float32))
            shv = cache.get("shared_vp", jnp.zeros((1,), jnp.float32))
            (h, shk, shv), (ssm_steps, conv_steps) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, shk, shv),
                (params["layers"], cache["ssm"], cache["conv"],
                 jnp.arange(cfg.n_layers)))
            # (nl, T, S, ...): per-token states for the caller's rollback;
            # cache keeps the full-T state as a placeholder
            cache = dict(cache, ssm=ssm_steps[:, -1], conv=conv_steps[:, -1])
            if cfg.attn_every:
                cache["shared_kp"], cache["shared_vp"] = shk, shv
            steps = {"ssm": ssm_steps, "conv": conv_steps}
        else:
            def body(hh, xs):
                lp, ck, cv, _li = xs
                hd = cfg.resolved_head_dim
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                a, ck, cv = attention.attn_verify_step(
                    lp["attn"], n1, ck, cv, idx, self.policy,
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=hd, rope_theta=cfg.rope_theta,
                    window=cfg.sliding_window, qk_norm=cfg.qk_norm,
                    kv_repeat=self.opt.kv_repeat, block_tables=bt)
                hh, _ = self._post_attn_combine(
                    lp, hh, n1, a, jnp.zeros((), jnp.float32))
                return hh, (ck, cv)

            h, (ks, vs) = obs_health.lifting_scan(
                _layer_noise_scoped(body), h,
                (params["layers"], cache["kp"], cache["vp"],
                 jnp.arange(cfg.n_layers)))
            cache = dict(cache, kp=ks, vp=vs)
            steps = None

        logits = self._head(params, h)
        return logits, cache, steps

    def prefill_chunk(self, params, cache, tokens, slot, pos0, true_len):
        """Process one prompt chunk for ONE slot of a stacked PAGED cache.

        tokens: ``(1, C)`` — the slot's next chunk, starting at absolute
        position ``pos0`` (traced; ``slot``/``true_len`` traced too, so one
        compile serves every chunk of every request). ``true_len <= C`` is
        the real token count: attention families may right-pad the final
        chunk (pads are dropped at the page write and masked in attention);
        SSM/hybrid recurrences carry state through EVERY step, so callers
        there must send exact-length chunks (``true_len == C``) — the
        server's chunker does exactly that, mirroring its exact-length
        prefill bucketing.

        Chunk k/v scatter straight into the global page pools through the
        slot's block table (blocks must already be allocated for positions
        ``< pos0 + true_len``); SSM state is read from / written back to the
        slot's row, with ``pos0 == 0`` resetting it (a reused slot's stale
        state must not leak into a new request). Returns ``(logits (1, 1, V)
        at the chunk's last real token, new cache)`` and advances
        ``idx[slot]`` to ``pos0 + true_len``.
        """
        cfg = self.cfg
        h, _ = self._embed_inputs(params, tokens, None)
        h = h.astype(self.opt.carry)
        C = h.shape[1]
        emb0 = h
        positions = pos0 + jnp.arange(C)
        bt_row = cache["bt"][slot] if "bt" in cache else None
        aux0 = jnp.zeros((), jnp.float32)

        if self.kind == "mamba":
            fresh = (pos0 == 0)
            ssm0 = jnp.where(fresh, 0.0, cache["ssm"][:, slot])
            conv0 = jnp.where(fresh, 0.0, cache["conv"][:, slot])

            def body(carry, xs):
                hh, shk, shv = carry
                lp, st, cv, li = xs
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                o, (st2, cv2) = mamba2.mamba_apply(
                    lp["mamba"], n1, cfg, self.policy, init_state=st[None],
                    conv_state=cv[None], return_cache=True, opt=self.opt)
                hh = hh + o
                if cfg.attn_every:
                    app = (li + 1) // cfg.attn_every - 1

                    def do_shared(args):
                        v, shk_, shv_ = args
                        hd = cfg.resolved_head_dim
                        u = common.dense(
                            params["shared"]["proj"],
                            jnp.concatenate([v, emb0], axis=-1), self.policy)
                        n = common.norm(params["shared"]["ln1"], u,
                                        cfg.norm_eps, cfg.norm_type)
                        ckp = shk_[jnp.maximum(app, 0)]
                        cvp = shv_[jnp.maximum(app, 0)]
                        a, ckp, cvp = attention.attn_chunk_step(
                            params["shared"]["attn"], n, ckp, cvp, bt_row,
                            pos0, true_len, self.policy,
                            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                            head_dim=hd, rope_theta=cfg.rope_theta,
                            kv_repeat=self.opt.kv_repeat,
                            q_chunk=self.opt.q_chunk,
                            kv_chunk=self.opt.kv_chunk)
                        shk_ = jax.lax.dynamic_update_index_in_dim(
                            shk_, ckp, jnp.maximum(app, 0), 0)
                        shv_ = jax.lax.dynamic_update_index_in_dim(
                            shv_, cvp, jnp.maximum(app, 0), 0)
                        u = u + a
                        n2 = common.norm(params["shared"]["ln2"], u,
                                         cfg.norm_eps, cfg.norm_type)
                        return (v + u + common.mlp(params["shared"]["mlp"],
                                                   n2, self.policy,
                                                   opt=self.opt), shk_, shv_)

                    hh, shk, shv = jax.lax.cond(
                        (li + 1) % cfg.attn_every == 0, _cond_suppressed(do_shared),
                        lambda args: args, (hh, shk, shv))
                return (hh, shk, shv), (st2[0], cv2[0])

            shk = cache.get("shared_kp", jnp.zeros((1,), jnp.float32))
            shv = cache.get("shared_vp", jnp.zeros((1,), jnp.float32))
            (h, shk, shv), (ssm, conv) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, shk, shv),
                (params["layers"], ssm0, conv0, jnp.arange(cfg.n_layers)))
            cache = dict(cache,
                         ssm=cache["ssm"].at[:, slot].set(ssm),
                         conv=cache["conv"].at[:, slot].set(conv))
            if cfg.attn_every:
                cache["shared_kp"], cache["shared_vp"] = shk, shv
        else:
            def body(carry, xs):
                hh, aux = carry
                lp, kp, vp, _li = xs
                hd = cfg.resolved_head_dim
                n1 = common.norm(lp["ln1"], hh, cfg.norm_eps, cfg.norm_type)
                a, kp, vp = attention.attn_chunk_step(
                    lp["attn"], n1, kp, vp, bt_row, pos0, true_len,
                    self.policy, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=hd,
                    rope_theta=cfg.rope_theta, window=cfg.sliding_window,
                    qk_norm=cfg.qk_norm, kv_repeat=self.opt.kv_repeat,
                    q_chunk=self.opt.q_chunk, kv_chunk=self.opt.kv_chunk)
                hh, aux = self._post_attn_combine(lp, hh, n1, a, aux)
                return (hh.astype(self.opt.carry), aux), (kp, vp)

            (h, _), (kps, vps) = obs_health.lifting_scan(
                _layer_noise_scoped(body), (h, aux0),
                (params["layers"], cache["kp"], cache["vp"],
                 jnp.arange(cfg.n_layers)))
            cache = dict(cache, kp=kps, vp=vps)

        cache["idx"] = cache["idx"].at[slot].set(
            jnp.asarray(pos0 + true_len, jnp.int32))
        h_last = jnp.take_along_axis(
            h, jnp.reshape(jnp.maximum(true_len - 1, 0), (1, 1, 1)), axis=1)
        logits = self._head(params, h_last)
        return logits, cache


# --------------------------------------------------------------------------
# Stacked-cache helpers (continuous-batching serving; runtime/server.py and
# runtime/elastic.py). A "stacked" cache is a normal cache pytree whose batch
# dimension is the slot dimension and whose "idx" is a per-slot vector
# (``cache_spec(..., per_slot_idx=True)``). The paged layout additionally
# carries global page pools (``kp``/``vp``/``shared_kp``/``shared_vp`` — NOT
# per-slot) and a per-slot ``bt`` block table.
# --------------------------------------------------------------------------

PAGE_POOL_LEAVES = ("kp", "vp", "shared_kp", "shared_vp")
# paged-pool leaf -> the dense prefill leaf whose rows scatter into it
_POOL_SRC = {"kp": "k", "vp": "v", "shared_kp": "shared_k",
             "shared_vp": "shared_v"}


def cache_slot_axis(name: str) -> int:
    """Axis of the serving-slot dimension for a PER-SLOT cache leaf. Every
    such leaf is layer-stacked with batch at axis 1, except the ``idx``
    vector and the ``bt`` block table (slot-major). Page-pool leaves
    (:data:`PAGE_POOL_LEAVES`) have no slot axis at all — callers must
    route them separately."""
    return 0 if name in ("idx", "bt") else 1


def cache_slot_count(cache: Dict[str, Any]) -> int:
    return cache["idx"].shape[0]


def _scatter_pages(pages: jax.Array, dense: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """Scatter dense prefill KV rows into a page pool.

    pages: ``(nl, n_blocks, bs, kv, hd)``; dense: ``(nl, B, L, kv, hd)``
    (linear positions 0..L-1 — serving prefill never ring-wraps); rows: the
    ``(B, max_blocks)`` destination block tables. Table entries carrying
    the OOB sentinel (unallocated blocks / dropped admission rows) make the
    scatter drop on device."""
    B, L = dense.shape[1], dense.shape[2]
    nb, bs, mb = pages.shape[1], pages.shape[2], rows.shape[1]
    pos = jnp.arange(L)
    # positions beyond the table's linear capacity route to the sentinel
    # (drop), matching the decode/chunk write contract
    db = jnp.where(pos < mb * bs,
                   rows[:, jnp.minimum(pos // bs, mb - 1)], nb)   # (B, L)
    off = jnp.broadcast_to(jnp.mod(pos, bs), (B, L))
    return pages.at[:, db, off].set(dense, mode="drop")


def cache_insert(live: Dict[str, Any], new: Dict[str, Any],
                 slots: jax.Array) -> Dict[str, Any]:
    """Scatter a (batched) prefill cache into the live stacked cache.

    ``new`` is a DENSE-layout prefill cache carrying ``B_new`` slots' worth
    of state; ``slots`` is the ``(B_new,)`` destination slot index per row.
    Jit-safe (one scatter per leaf, no per-slot Python); rows whose slot is
    out of bounds (the ``>= n_slots`` sentinel used to pad admission groups
    to a fixed batch) are dropped on device.

    When ``live`` is PAGED, per-slot leaves scatter as usual while the
    dense ``k``/``v`` (and ``shared_k``/``shared_v``) rows scatter through
    the live block tables into the page pools — blocks for each row's
    positions must already be allocated (the server's admission path does
    this); OOB slot rows get all-sentinel tables so they still drop.
    """
    out = {}
    bt = live.get("bt")
    rows = None
    if bt is not None:
        nb = live[next(k for k in PAGE_POOL_LEAVES if k in live)].shape[1]
        S = bt.shape[0]
        rows = jnp.where((slots < S)[:, None],
                         bt[jnp.minimum(slots, S - 1)], nb)
    for k, v in live.items():
        if k == "bt":
            out[k] = v
        elif k in PAGE_POOL_LEAVES:
            out[k] = _scatter_pages(v, new[_POOL_SRC[k]], rows)
        elif cache_slot_axis(k) == 0:
            out[k] = v.at[slots].set(new[k], mode="drop")
        else:
            out[k] = v.at[:, slots].set(new[k], mode="drop")
    return out


def cache_extract(cache: Dict[str, Any], slots) -> Dict[str, Any]:
    """Gather the given slots out of a stacked cache (elastic resize /
    debugging). ``slots`` may be any integer index array. Page-pool leaves
    are global (block ids are stable across slot compaction) and pass
    through untouched; the ``bt`` rows carry the per-slot mapping."""
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for k, v in cache.items():
        if k in PAGE_POOL_LEAVES:
            out[k] = v
        elif cache_slot_axis(k) == 0:
            out[k] = v[slots]
        else:
            out[k] = v[:, slots]
    return out
