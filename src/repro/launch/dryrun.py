import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
cell on 512 placeholder host devices and extract the roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out dryrun.json

Nothing is allocated: inputs and train state are ShapeDtypeStructs and the
cell is judged by ``.lower().compile()`` succeeding, plus memory_analysis()
(fits per-chip HBM) and cost_analysis() (FLOPs/bytes for the roofline).
"""

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ARCHS, SHAPES, cell_is_skipped, get_config,  # noqa: E402
                           get_shape)
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core.precision import get_policy  # noqa: E402
from repro.launch import hlo_analysis as ha  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.models import build_model, input_specs  # noqa: E402
from repro.models.lm import LMCallOptions  # noqa: E402
from repro.parallel import sharding as sh  # noqa: E402
from repro.runtime.trainer import abstract_train_state, make_train_step  # noqa: E402


def options_for(cfg, shape, mesh, *, perf_level: int = 0,
                moe_impl: str = None) -> LMCallOptions:
    """Per-cell call options. perf_level selects hillclimb variants (see
    EXPERIMENTS.md Section Perf); 0 = baseline."""
    mesh_sizes = tuple((name, int(mesh.shape[name])) for name in mesh.axis_names)
    return LMCallOptions(
        kv_repeat=sh.kv_repeat_for(cfg, mesh),
        q_chunk=2048 if shape.seq_len >= 32768 else 1024,
        kv_chunk=2048 if shape.seq_len >= 32768 else 1024,
        remat=(shape.kind == "train"),
        carry_dtype="bfloat16" if shape.kind == "train" else "float32",
        ce_chunk=4096 if shape.kind == "train" else 0,
        # attn bf16 scores: REFUTED (convert boundaries added traffic;
        # see EXPERIMENTS.md §Perf iteration 2b) — kept off.
        merge_parallel_proj=perf_level >= 3,
        moe_impl=(moe_impl if moe_impl is not None else
                  ("ep_shard_map" if perf_level >= 5 else "gspmd")),
        act_dp=sh.dp_axes(mesh),
        act_tp="model",
        mesh_sizes=mesh_sizes,
    )


def train_cfg_for(cfg, shape, mesh, policy, perf_level: int = 0) -> TrainConfig:
    dp_total = 1
    for ax in sh.dp_axes(mesh):
        dp_total *= mesh.shape[ax]
    per_dev_batch = max(shape.global_batch // dp_total, 1)
    # microbatch so one microbatch holds ~1 sequence per device for big models
    nmb = per_dev_batch if cfg.d_model >= 8192 else (
        max(per_dev_batch // 4, 1) if cfg.d_model >= 2048 else 1)
    if perf_level >= 3 and cfg.d_model >= 8192:
        nmb = max(per_dev_batch // 2, 1)   # iteration 3: fewer weight passes
    # microbatch count must divide the global batch
    while shape.global_batch % nmb:
        nmb -= 1
    return TrainConfig(
        policy=policy, optimizer="adamw", microbatches=nmb,
        weight_stationary_quant=perf_level >= 1,
        quant_param_dtype="bfloat16" if perf_level >= 2 else "float32")


def policy_for(policy_name: str, shape, perf_level: int):
    """Perf-level ladder (EXPERIMENTS.md §Perf):
      0: paper-faithful baseline — per-GEMM BFP quantization, f32 folded ops
      1: weight-stationary quantization (quantize W once/step; grid reused
         across microbatches, remat, and the transposed dX read)
      2: + bf16 storage/compute for the folded operands (value-identical:
         BFP(b_m<=6) grid values are exact in bfloat16)
      3: + schedule/structural tuning (microbatches; MoE capacity 1.0;
         SSD chunk 128; merged parallel-block projection)
      4: + mesh aspect (data=32, model=8) for single-pod cells
      5: + shard_map expert-parallel MoE dispatch; SSD chunk 64
      6: SSD chunk 32
    """
    from repro.core import backends

    policy = get_policy(policy_name)
    ws_capable = backends.resolve(policy).supports_weight_stationary
    if perf_level >= 1 and ws_capable:
        policy = policy.replace(assume_quantized_weights=(shape.kind == "train"))
    if perf_level >= 2 and ws_capable:
        policy = policy.replace(compute_dtype="bfloat16")
    return policy


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool,
               policy_name: str = "mirage", perf_level: int = 0,
               moe_impl: str = None, mesh_override: str = None):
    import dataclasses as _dc
    cfg = get_config(arch_id)
    if perf_level >= 3:
        # per-family structural moves (EXPERIMENTS.md Perf iteration 3):
        #   moe: capacity 1.25 -> 1.0 (dispatch buffers + combine wire -20%)
        #   ssm: SSD chunk 256 -> 128 (L-matrix traffic ~ B*H*L*Q halves)
        if cfg.n_experts:
            cfg = _dc.replace(cfg, capacity_factor=1.0)
        if cfg.ssm_state:
            cfg = _dc.replace(cfg, ssm_chunk={5: 64, 6: 32}.get(perf_level, 128) if perf_level >= 5 else 128)
    shape = get_shape(shape_name)
    if mesh_override == "16x16":
        mesh = make_production_mesh(multi_pod=False)
    elif (perf_level >= 4 and not multi_pod) or mesh_override == "32x8":
        # iteration 4: mesh aspect ratio. Same 256 chips as (data=16,model=16)
        # but (data=32, model=8): FSDP all-gather wire per device is
        # (G-1)/G * N/tp and N/tp doubles DOWN as tp halves -> weight-gather
        # volume ~halves; TP all-reduce payload changes only by (7/8)/(15/16).
        mesh = make_mesh((32, 8), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    policy = policy_for(policy_name, shape, perf_level)
    opts = options_for(cfg, shape, mesh, perf_level=perf_level,
                       moe_impl=moe_impl)
    model = build_model(cfg, policy, opts)
    specs = input_specs(cfg, shape, opts)

    with mesh:
        if shape.kind == "train":
            tc = train_cfg_for(cfg, shape, mesh, policy, perf_level)
            state = abstract_train_state(model, tc)
            state_sh = sh.train_state_shardings(mesh, cfg, state)
            batch_sh = sh.batch_shardings(mesh, cfg, specs)
            step = make_train_step(model, tc)
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,))
            lowered = jitted.lower(state, specs)
        elif shape.kind == "prefill":
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            params_sh = sh.param_shardings(mesh, cfg, params)
            batch_sh = sh.batch_shardings(mesh, cfg, specs)
            cap = shape.seq_len + 64 if not cfg.is_encdec else \
                max(shape.seq_len // 8, 16) + 64

            if cfg.is_encdec:
                def prefill_step(p, batch):
                    return model.prefill(p, batch["frames"], batch["tokens"],
                                         cap)
            elif cfg.frontend == "vit_stub":
                def prefill_step(p, batch):
                    return model.prefill(p, batch["tokens"], cap,
                                         extra_embeds=batch["patches"])
            else:
                def prefill_step(p, batch):
                    return model.prefill(p, batch["tokens"], cap)

            jitted = jax.jit(prefill_step,
                             in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params, specs)
        else:  # decode
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            params_sh = sh.param_shardings(mesh, cfg, params)
            cache_sh = sh.batch_shardings(mesh, cfg, specs["cache"])
            tok_sh = sh.batch_shardings(
                mesh, cfg, {"tokens": specs["tokens"]})["tokens"]

            def serve_step(p, cache, tokens):
                return model.decode_step(p, cache, tokens)

            jitted = jax.jit(
                serve_step,
                in_shardings=(params_sh, cache_sh, tok_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,))
            lowered = jitted.lower(params, specs["cache"], specs["tokens"])

        compiled = lowered.compile()
    return cfg, shape, mesh, model, lowered, compiled


def analyze_cell(arch_id: str, shape_name: str, multi_pod: bool,
                 policy_name: str = "mirage", perf_level: int = 0,
                 keep_hlo: bool = False):
    t0 = time.time()
    cfg, shape, mesh, model, lowered, compiled = lower_cell(
        arch_id, shape_name, multi_pod, policy_name, perf_level)
    chips = mesh.size

    # cost_analysis counts while bodies ONCE (verified; see EXPERIMENTS.md) —
    # kept as auxiliary evidence. Primary numbers come from the loop-aware
    # HLO analyzer (launch/hlo_analysis.py) over the compiled text.
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax: list of per-program dicts
        cost = cost[0] if cost else {}
    ca_flops = float(cost.get("flops", 0.0))
    ca_bytes = float(cost.get("bytes accessed", 0.0))

    try:
        mem = compiled.memory_analysis()
        peak = float(getattr(mem, "temp_size_in_bytes", 0) +
                     getattr(mem, "argument_size_in_bytes", 0) +
                     getattr(mem, "output_size_in_bytes", 0) -
                     getattr(mem, "alias_size_in_bytes", 0))
        mem_str = str(mem)
    except Exception as e:  # CPU backend may not implement it
        peak, mem_str = 0.0, f"unavailable: {e}"

    hlo = compiled.as_text()
    hc = ha.analyze_hlo(hlo, default_group=chips)
    stats = rl.CollectiveStats(
        counts={k: int(v) for k, v in hc.coll_counts.items()},
        raw_bytes={k: int(v) for k, v in hc.coll_raw_bytes.items()},
        wire_bytes=dict(hc.coll_wire_bytes))

    params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_params = rl.count_params(params_abs)
    mflops = rl.model_flops_estimate(cfg, shape, n_params)

    roof = rl.Roofline(
        arch=arch_id, shape=shape_name,
        mesh="multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        chips=chips, per_device_flops=hc.flops, per_device_bytes=hc.hbm_bytes,
        collectives=stats, model_flops=mflops, peak_memory_bytes=peak)
    out = roof.to_dict()
    out.update(n_params=n_params, policy=policy_name,
               compile_seconds=round(time.time() - t0, 1),
               cost_analysis_flops=ca_flops, cost_analysis_bytes=ca_bytes,
               n_while=hc.n_while, max_trip=hc.max_trip,
               memory_analysis=mem_str[:2000],
               hlo_bytes=len(hlo))
    if keep_hlo:
        out["hlo_text"] = hlo
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="mirage")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--perf-level", type=int, default=0)
    args = ap.parse_args(argv)

    archs = sorted(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shp in shapes:
            skip = cell_is_skipped(arch, shp)
            if skip:
                results.append({"arch": arch, "shape": shp, "status": "skipped",
                                "reason": skip})
                print(f"[skip] {arch} x {shp}: {skip}", flush=True)
                continue
            for mp in meshes:
                tag = f"{arch} x {shp} x {'multi' if mp else 'single'}"
                try:
                    r = analyze_cell(arch, shp, mp, args.policy,
                                     args.perf_level)
                    r["status"] = "ok"
                    results.append(r)
                    print(f"[ok]   {tag}: compute={r['compute_s']:.4f}s "
                          f"memory={r['memory_s']:.4f}s "
                          f"collective={r['collective_s']:.4f}s "
                          f"dominant={r['dominant']} "
                          f"(compile {r['compile_seconds']}s)", flush=True)
                except Exception as e:
                    results.append({"arch": arch, "shape": shp,
                                    "mesh": "multi" if mp else "single",
                                    "status": "error", "error": str(e)[:2000]})
                    print(f"[FAIL] {tag}: {e}", flush=True)
                    traceback.print_exc()
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1, default=str)
    n_err = sum(1 for r in results if r.get("status") == "error")
    print(f"done: {len(results)} cells, {n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
