import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-moe-a2.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]

Per cell this prints & records:
  * compiled.memory_analysis()  -> bytes/device (proves fit)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes for the roofline
  * collective wire bytes per device, split by mesh axis (parsed HLO)
  * the three roofline terms + dominant bottleneck
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Dict, Optional

import jax

from repro.configs import ARCH_IDS, applicable_cells, get_config, get_shape_cell
from repro.configs.base import ModelConfig, ShapeCell
from repro.core import hlo_cost
from repro.launch import mesh as mesh_lib
from repro.launch.steps import (
    batch_struct,
    decode_struct,
    jit_decode_step,
    jit_prefill,
    jit_train_step,
    param_struct,
)
from repro.models import build_model
from repro.optim import AdamW
from repro.sharding import ShardingPlan, default_plan, opt_state_specs, param_specs
from repro.launch.steps import named


# gradient-accumulation steps per arch for train_4k: sized so the saved
# scan-carry residuals (+ transients) fit the 16 GiB HBM budget
TRAIN_ACCUM = {
    "nemotron-4-340b": 16,
    "deepseek-coder-33b": 4,
    "jamba-v0.1-52b": 4,
    "whisper-large-v3": 4,
    "minicpm3-4b": 2,
    "moonshot-v1-16b-a3b": 2,
    "mamba2-370m": 2,
}


def plan_for_cell(cfg: ModelConfig, cell: ShapeCell, multi_pod: bool,
                  overrides: Optional[Dict] = None,
                  profile: str = "baseline") -> ShardingPlan:
    plan = default_plan(multi_pod)
    if cell.kind == "train" and cfg.family in ("dense", "moe", "vlm", "encdec"):
        # Megatron-style sequence parallelism for the residual carry.
        # SSM/hybrid scan over the (sharded) chunk dim, so SP is off there.
        plan = plan.with_(sequence_parallel=True)
    n_devices = 512 if multi_pod else 256
    if (profile == "optimized" and cell.kind == "train"
            and cfg.param_count() < 1e9
            and cell.global_batch % n_devices == 0):
        # §Perf iteration A1: sub-1B models waste the model axis on TP
        # (104 GB/step of partial-sum all-reduce for mamba2-370m) — use it
        # for data parallelism instead (pure DP-256 + 2-axis FSDP)
        axes = (("pod", "data", "model") if multi_pod
                else ("data", "model"))
        plan = plan.with_(tp_axis=None, ep_axis=None, batch_axes=axes,
                          fsdp_axes=axes, sequence_parallel=False)
    if cell.kind in ("decode", "prefill"):
        # KV caches shard the sequence dim (flash-decoding style)
        if cell.global_batch == 1:
            # long-context decode: batch unshardable -> context-parallel KV
            # over every available axis
            axes = ("pod", "data", "model") if multi_pod else ("data", "model")
            plan = plan.with_(seq_axis=axes)
        else:
            plan = plan.with_(seq_axis="model")
    if overrides:
        plan = plan.with_(**overrides)
    return plan


def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               plan_overrides: Optional[Dict] = None,
               loss_chunk: Optional[int] = 2048,
               remat_policy: Optional[str] = "nothing",
               opt_state_dtype: Optional[str] = "bfloat16",
               accum_steps: Optional[int] = None,
               cfg_patch: Optional[Dict] = None,
               moe_patch: Optional[Dict] = None,
               ssm_patch: Optional[Dict] = None,
               cache_dtype: str = "bfloat16",
               grad_reduce_dtype: Optional[str] = None,
               shard_grads: bool = True,
               profile: str = "baseline"):
    """Lower + compile one cell. Returns (record dict, compiled).

    The *_patch / cache_dtype knobs are the §Perf hillclimbing levers:
    e.g. moe_patch={"capacity_factor": 0.5}, ssm_patch={"chunk_size": 128},
    cache_dtype="float8_e4m3fn" (fp8 KV cache).
    """
    cfg = get_config(arch)
    if cfg_patch:
        cfg = dataclasses.replace(cfg, **cfg_patch)
    if moe_patch and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe_patch))
    if ssm_patch and cfg.ssm is not None:
        cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, **ssm_patch))
    cell = get_shape_cell(shape)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    plan = plan_for_cell(cfg, cell, multi_pod, plan_overrides, profile)
    model = build_model(cfg, loss_chunk=loss_chunk, remat_policy=remat_policy)
    if accum_steps is None:
        # NB: lookup by canonical dashed name (cfg.name), not the CLI arg
        accum_steps = (TRAIN_ACCUM.get(cfg.name, 1)
                       if cell.kind == "train" else 1)
        if plan.tp_axis is None and cell.kind == "train":
            # pure-DP plans shard the batch over every axis — microbatches
            # must still cover all devices (§Perf iteration A1 lesson)
            accum_steps = max(1, cell.global_batch // int(mesh.devices.size))
            accum_steps = min(accum_steps,
                              cell.global_batch // int(mesh.devices.size) or 1)

    t0 = time.time()
    if cell.kind == "train":
        optimizer = AdamW(lr=3e-4, state_dtype=opt_state_dtype)
        step = jit_train_step(model, optimizer, mesh, plan, cell, accum_steps,
                              grad_reduce_dtype, shard_grads)
        params = param_struct(model, cell)
        opt_state = jax.eval_shape(optimizer.init, params)
        batch = batch_struct(cfg, cell)
        lowered = step.lower(params, opt_state, batch)
    elif cell.kind == "prefill":
        step = jit_prefill(model, mesh, plan, cell)
        params = param_struct(model, cell)
        batch = batch_struct(cfg, cell)
        lowered = step.lower(params, batch)
    else:  # decode
        step = jit_decode_step(model, mesh, plan, cell)
        params = param_struct(model, cell)
        import jax.numpy as _jnp
        tokens, cache, pos = decode_struct(model, cell, cache_dtype=_jnp.dtype(cache_dtype))
        lowered = step.lower(params, tokens, cache, pos)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware cost model (XLA's cost_analysis counts while bodies
    # once — useless for scan-over-layers; see repro.core.hlo_cost)
    csum = hlo_cost.analyze(hlo, mesh.devices.shape, mesh.axis_names)

    n_chips = int(mesh.devices.size)
    flops_total = float(csum["flops"])
    bytes_total = float(csum["bytes"])
    compute_s = flops_total / mesh_lib.PEAK_FLOPS_BF16
    memory_s = bytes_total / mesh_lib.HBM_BW
    wire = csum["wire_bytes_per_device"]
    # split wire bytes by link class: ICI within a pod, DCN across pods
    dcn_bytes = csum["wire_bytes_by_axis"].get("pod", 0.0)
    ici_bytes = wire - dcn_bytes
    collective_s = ici_bytes / mesh_lib.ICI_BW + dcn_bytes / mesh_lib.DCN_BW

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    # model-FLOPs utilisation proxy
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens_proc = cell.global_batch * cell.seq_len
        model_flops = 6 * n_active * tokens_proc
    elif cell.kind == "prefill":
        tokens_proc = cell.global_batch * cell.seq_len
        model_flops = 2 * n_active * tokens_proc
    else:
        tokens_proc = cell.global_batch
        model_flops = 2 * n_active * tokens_proc
    hlo_flops_all = flops_total * n_chips
    useful_ratio = model_flops / hlo_flops_all if hlo_flops_all else 0.0

    record = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "accum_steps": accum_steps,
        "plan": {k: v for k, v in dataclasses.asdict(plan).items()},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
            "hbm_capacity": mesh_lib.HBM_BYTES,
            "fits": (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                    <= mesh_lib.HBM_BYTES,
        },
        "cost": {
            "hlo_flops_per_device": flops_total,
            "hlo_bytes_per_device": bytes_total,
            "transcendentals": float(csum["transcendentals"]),
            "xla_cost_analysis_flops": float(xla_cost.get("flops", 0.0)),
        },
        "collectives": {
            "n": csum["n_collective_ops"],
            "by_kind": csum["collectives_by_kind"],
            "wire_bytes_by_axis": csum["wire_bytes_by_axis"],
            "wire_bytes_per_device": wire,
            "ici_bytes": ici_bytes,
            "dcn_bytes": dcn_bytes,
        },
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "bottleneck": bottleneck,
            "model_flops": model_flops,
            "hlo_flops_all_chips": hlo_flops_all,
            "useful_flops_ratio": useful_ratio,
            "step_time_lower_bound_s": max(terms.values()),
            "roofline_fraction": (
                compute_s / max(max(terms.values()), 1e-30)),
        },
        "params": {"total": n_params, "active": n_active},
    }
    return record, compiled


# dry-run profiles: the paper-faithful conservative configuration vs the
# beyond-paper optimized defaults (§Perf winners)
PROFILES = {
    "baseline": dict(shard_grads=False, grad_reduce_dtype=None,
                     profile="baseline"),
    "optimized": dict(shard_grads=True, grad_reduce_dtype="bfloat16",
                      cache_dtype="float8_e4m3fn",   # §Perf C1: fp8 KV cache
                      profile="optimized"),
}


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             plan_overrides: Optional[Dict] = None, tag: str = "",
             **lower_kwargs) -> Dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    name = f"{arch}__{shape}__{mesh_name}{('__' + tag) if tag else ''}"
    try:
        record, compiled = lower_cell(arch, shape, multi_pod=multi_pod,
                                      plan_overrides=plan_overrides,
                                      **lower_kwargs)
        record["status"] = "ok"
        print(f"[dryrun] {name}: OK compile={record['compile_s']}s "
              f"peak={record['memory']['peak_bytes']/2**30:.2f}GiB "
              f"bottleneck={record['roofline']['bottleneck']} "
              f"rf={record['roofline']['roofline_fraction']:.3f}")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        record = {"arch": arch, "shape": shape, "mesh": mesh_name,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
        print(f"[dryrun] {name}: FAIL {type(e).__name__}: {e}")
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{name}.json").write_text(json.dumps(record, indent=1, default=str))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--profile", default="baseline", choices=sorted(PROFILES))
    args = ap.parse_args()
    out_dir = Path(args.out)
    profile_kwargs = PROFILES[args.profile]

    jobs = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for cell in applicable_cells(cfg):
                jobs.append((arch, cell.name))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        jobs.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch, shape in jobs:
        for mp in meshes:
            results.append(run_cell(arch, shape, mp, out_dir,
                                    **profile_kwargs))

    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"[dryrun] {ok}/{len(results)} cells compiled")
    if ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
