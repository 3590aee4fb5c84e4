import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
# on the production meshes and extract memory/cost/collective statistics.
#
# The two lines above MUST run before any other import (jax locks the device
# count at first initialisation), which is why this module has no docstring.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
#       --shape train_4k [--multi-pod] [--sync-mode sync] [--out results/]
#   PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
#
# Each cell writes results/<arch>__<shape>__<mesh>__<mode>.json with:
#   memory_analysis (per-device bytes), cost_analysis (XLA's once-per-while),
#   trip-count-corrected flops / hbm bytes / ICI+DCN collective bytes
#   (launch/hloparse.py), and the collective inventory.

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config, shape_cells, RunConfig
from repro.launch import hloparse
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    build_decode_step,
    build_encode_step,
    build_prefill_step,
    build_train_step,
    init_train_state,
    train_state_specs,
)
from repro.models import Model, input_specs, param_count
from repro.models.specs import is_spec
from repro.sharding import batch_pspec

POD_SIZE = 256


# Per-arch production knobs (from the §Perf napkin math: activation bytes
# per chip ≈ L·(B_loc/mb)·T·D·2; target ≤ ~8 GB with params+optimizer).
PRODUCTION_RUN = {
    "llama3.2-1b": dict(microbatches=2),
    "llama3-8b": dict(microbatches=8),
    "glm4-9b": dict(microbatches=8),
    "codeqwen1.5-7b": dict(microbatches=8),
    "hubert-xlarge": dict(microbatches=4),
    "internvl2-76b": dict(microbatches=16, optimizer_state_dtype="bfloat16"),
    "recurrentgemma-9b": dict(microbatches=8),
    # xlstm + MoE archs use fully-manual shard_map islands (sLSTM cell, EP
    # a2a), which do not compose with the vmap-over-pod "sync" lowering —
    # their multi-pod cells run the flat GSPMD schedule instead (see §Perf).
    "xlstm-1.3b": dict(microbatches=8, _flat_multipod=True),
    "deepseek-v2-236b": dict(microbatches=4, optimizer_state_dtype="bfloat16",
                             _flat_multipod=True),
    "deepseek-v3-671b": dict(microbatches=4, optimizer_state_dtype="bfloat16",
                             _flat_multipod=True),
}

# Expert-weight layout per MoE arch (§Perf iteration: the baseline fsdp_d
# moves expert weights over the fabric every layer).
EXPERT_SHARDING = {
    "deepseek-v2-236b": "ep_a2a",   # EP over model axis + weight FSDP gather
    "deepseek-v3-671b": "ep_a2a",   # E=256 → one expert per chip, manual a2a
}


def production_config(arch: str, expert_sharding: str = None,
                      microbatches: int = None):
    """Full config with launcher overrides for the production mesh."""
    cfg = get_config(arch)
    if cfg.moe is not None:
        # group-local dispatch: one group per data shard
        cfg = cfg.with_overrides(
            moe=dataclasses.replace(
                cfg.moe,
                groups=16,
                expert_sharding=expert_sharding
                or EXPERT_SHARDING.get(arch, cfg.moe.expert_sharding),
            )
        )
    return cfg


def production_run(arch: str, sync_mode: str, microbatches: int = None,
                   multi_pod: bool = False) -> RunConfig:
    kw = dict(PRODUCTION_RUN.get(arch, {}))
    if kw.pop("_flat_multipod", False) and multi_pod and sync_mode == "sync":
        sync_mode = "flat"
    if microbatches is not None:
        kw["microbatches"] = microbatches
    return RunConfig(sync_mode=sync_mode, **kw)


def _routed_expert_fraction(cfg) -> float:
    """Fraction of params that are routed experts (for active-param count)."""
    if cfg.moe is None:
        return 0.0
    from repro.models.moe import moe_spec
    from repro.models.specs import param_count as pc
    spec = moe_spec(cfg)
    routed = pc({"wi": spec["wi"], "wo": spec["wo"]})
    return routed


def model_flops_estimate(cfg, shape, n_params: int) -> float:
    """MODEL_FLOPS: 6·N·D (train, dense) / 6·N_active·D (MoE) / 2·N·D (fwd)."""
    model = Model(cfg)
    n_total = n_params
    if cfg.moe is not None:
        plan = model.plan
        n_moe_layers = plan.n_scan * len(plan.pattern) + len(plan.tail)
        routed_per_layer = _routed_expert_fraction(cfg)
        routed_total = routed_per_layer * n_moe_layers
        active = n_total - routed_total * (1 - cfg.moe.top_k / cfg.moe.num_experts)
    else:
        active = n_total
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * active * tokens


def lower_cell(arch: str, shape_name: str, multi_pod: bool, sync_mode: str,
               expert_sharding: str = None, microbatches: int = None):
    cfg = production_config(arch, expert_sharding=expert_sharding)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    run = production_run(arch, sync_mode, microbatches, multi_pod=multi_pod)
    # Cap microbatches so each microbatch still fills every data shard --
    # otherwise XLA pads rows and every chip burns flops on padding
    # (measured: internvl2 2-pod at mb=16 ran the FULL batch per pod).
    npods = 2 if multi_pod else 1
    mb_cap = max(1, shape.global_batch // (npods * 16))
    if run.microbatches > mb_cap:
        run = dataclasses.replace(run, microbatches=mb_cap)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, state_shapes, state_sh, batch_sh = build_train_step(
                model, run, mesh, shape
            )
            batch = input_specs(cfg, shape)
            npods = 2 if multi_pod else 1
            state = jax.tree.map(
                lambda s: s, state_shapes,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            lowered = step.lower(state, batch)
        elif shape.kind == "prefill":
            if not cfg.causal:
                # Encoder-only: "prefill" is a plain forward (no cache).
                step = build_encode_step(model, mesh, shape)
            else:
                step, cache_spec, _ = build_prefill_step(
                    model, mesh, shape, max_len=shape.seq_len
                )
            batch = input_specs(cfg, shape)
            lowered = step.lower(model.param_shapes(), batch)
        else:  # decode
            step, cache_spec, _ = build_decode_step(
                model, mesh, shape, max_len=shape.seq_len
            )
            tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            lowered = step.lower(model.param_shapes(), cache_spec, tokens)
        compiled = lowered.compile()
    return cfg, shape, mesh, lowered, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool, sync_mode: str,
             out_dir: str, skip_existing: bool = True, tag: str = "",
             expert_sharding: str = None, microbatches: int = None):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}__{sync_mode}" + (
        f"__{tag}" if tag else "")
    out_path = os.path.join(out_dir, cell_id + ".json")
    if skip_existing and os.path.exists(out_path):
        print(f"[dryrun] {cell_id}: cached")
        return json.load(open(out_path))

    for shp, skip in shape_cells(arch):
        if shp.name == shape_name and skip:
            rec = {"cell": cell_id, "skipped": skip}
            os.makedirs(out_dir, exist_ok=True)
            json.dump(rec, open(out_path, "w"), indent=1)
            print(f"[dryrun] {cell_id}: SKIP ({skip})")
            return rec

    t0 = time.time()
    print(f"[dryrun] {cell_id}: lowering...", flush=True)
    cfg, shape, mesh, lowered, compiled = lower_cell(
        arch, shape_name, multi_pod, sync_mode,
        expert_sharding=expert_sharding, microbatches=microbatches,
    )
    t1 = time.time()
    print(f"[dryrun] {cell_id}: compiled in {t1 - t0:.1f}s; analyzing...",
          flush=True)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    ndev = 512 if multi_pod else 256
    stats = hloparse.analyze(text, num_devices=ndev, pod_size=POD_SIZE)

    n_params = param_count(Model(cfg).specs())
    mf = model_flops_estimate(cfg, shape, n_params)

    coll_summary = {}
    for c in stats.collectives:
        key = f"{c.kind}{'@dcn' if c.crosses_pod else '@ici'}"
        agg = coll_summary.setdefault(
            key, {"instances": 0.0, "wire_bytes_per_chip": 0.0}
        )
        agg["instances"] += c.count
        agg["wire_bytes_per_chip"] += c.wire_bytes_per_chip() * c.count

    rec = {
        "cell": cell_id,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "sync_mode": sync_mode,
        "num_devices": ndev,
        "compile_seconds": round(t1 - t0, 1),
        "params": n_params,
        "model_flops": mf,
        "memory_analysis": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "peak_estimate_bytes_per_device": (
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
            ),
        },
        "cost_analysis_once": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "parsed": {
            "flops_per_device": stats.flops,
            "hbm_bytes_per_device": stats.hbm_bytes,
            "ici_wire_bytes_per_chip": stats.ici_bytes,
            "dcn_wire_bytes_per_chip": stats.dcn_bytes,
            "ici_wire_bytes_per_chip_raw": stats.ici_bytes_raw,
            "dcn_wire_bytes_per_chip_raw": stats.dcn_bytes_raw,
        },
        "collectives": coll_summary,
        "top_collectives": stats.top_collectives(8),
    }
    os.makedirs(out_dir, exist_ok=True)
    json.dump(rec, open(out_path, "w"), indent=1)
    print(f"[dryrun] {cell_id}: done ({time.time() - t0:.1f}s total)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--sync-mode", default="sync",
                    choices=("flat", "sync", "local"))
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell")
    ap.add_argument("--out", default="results")
    ap.add_argument("--no-skip", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--expert-sharding", default=None,
                    choices=(None, "fsdp_d", "fsdp_f", "ep2d"))
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    failures = []
    if args.all:
        for arch in ARCHS:
            for shape_name in SHAPES:
                for multi_pod in (False, True):
                    try:
                        run_cell(arch, shape_name, multi_pod, args.sync_mode,
                                 args.out, not args.no_skip)
                    except Exception as e:
                        traceback.print_exc()
                        failures.append((arch, shape_name, multi_pod, str(e)))
    else:
        meshes = []
        if args.multi_pod or not args.single_pod:
            meshes.append(True)
        if args.single_pod or not args.multi_pod:
            meshes.append(False)
        for mp in sorted(set(meshes)):
            run_cell(args.arch, args.shape, mp, args.sync_mode, args.out,
                     not args.no_skip, tag=args.tag,
                     expert_sharding=args.expert_sharding,
                     microbatches=args.microbatches)
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
