import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
# For each cell this proves the sharding config is coherent at 256/512 chips
# (compile succeeds), that it fits (memory_analysis), and extracts the
# roofline inputs (cost_analysis flops/bytes + collective bytes from HLO).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, cache_len_for, input_specs, shape_applies
from repro.distributed.constraints import axis_rules
from repro.distributed.sharding import serve_rules, shardings_for, train_rules
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import lm
from repro.optim import AdamWConfig, opt_state_specs

LM_ARCHS = tuple(a for a in ARCH_IDS if a != "e2afs-fp16")

# v5e hardware constants (roofline); flops/BW from the shared ChipModel
from repro.core.hw_model import TPU_V5E as _V5E  # noqa: E402

PEAK_FLOPS = _V5E.peak_flops  # bf16 / chip
HBM_BW = _V5E.hbm_bw  # B/s / chip
ICI_BW = 50e9  # B/s / link


def _batch_shardings(batch_specs, mesh, rules):
    from repro.distributed.constraints import logical_to_spec
    from repro.distributed.sharding import divisible_spec
    from jax.sharding import NamedSharding

    def spec_for(name, arr):
        if name in ("tokens", "labels", "loss_mask"):
            axes = ("batch", "seq")
        elif name in ("vision", "audio"):
            axes = ("batch", "seq", None)
        else:
            raise KeyError(name)
        spec = logical_to_spec(axes[: arr.ndim], rules)
        return NamedSharding(mesh, divisible_spec(spec, arr.shape, mesh))

    return {k: spec_for(k, v) for k, v in batch_specs.items()}


def _decode_hbm_estimate_gib(cfg, case, mesh) -> float:
    """bf16 KV cache + bf16 params per device (decode fit policy)."""
    from repro.distributed.sharding import _param_gib

    data = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if "kv" in mesh.axis_names:
        model = mesh.shape["kv"] * mesh.shape["qg"]
        kv_local = cfg.n_kv_heads / mesh.shape["kv"]
    else:
        model = mesh.shape["model"]
        kv_local = cfg.n_kv_heads / model if cfg.n_kv_heads % model == 0 else cfg.n_kv_heads
    b_local = max(1, case.global_batch // data)
    cache = 0.0
    for blk in cfg.blocks:
        if blk == "global":
            t = case.seq_len
        elif blk == "window":
            t = min(case.seq_len, cfg.window)
        else:
            continue  # state blocks are small
        cache += b_local * t * kv_local * cfg.d_head * 2 * 2
    return (cache + _param_gib(cfg) * 2**30 / model) / 2**30


def lower_cell(arch: str, shape_name: str, mesh_kind: str, *, quantized_kv=None,
               sqrt_unit="e2afs", microbatches=1, seq_parallel=False,
               extra_overrides=None, smoke=False):
    """Lower + compile one cell; returns the result record (dict).

    quantized_kv=None -> policy: quantize the KV cache (int8, the framework's
    approximate-computing feature) when the bf16 cache + params would not fit
    16 GiB/chip.  ``smoke`` uses reduced configs/shapes on a (2,2[,2]) mesh —
    the CI-scale version of the same lowering path."""
    from repro.configs import get_smoke_config
    from repro.configs.shapes import SMOKE_SHAPES
    from repro.launch.mesh import make_mesh_for

    case = (SMOKE_SHAPES if smoke else SHAPES)[shape_name]
    getter = get_smoke_config if smoke else get_config
    cfg = getter(arch, sqrt_unit=sqrt_unit, **(extra_overrides or {}))
    skip = shape_applies(cfg, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": skip}

    if smoke:
        mesh = (
            make_mesh_for((2, 2, 2), ("pod", "data", "model"))
            if mesh_kind == "multi"
            else make_mesh_for((2, 2), ("data", "model"))
        )
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    t0 = time.time()

    # lm.init gives serving weights in the activation dtype
    params_s, specs = lm.init(cfg, jax.random.key(0), abstract=True)

    if case.kind == "train":
        # training steps fp32 master weights (launch/train.py)
        params_s = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_s
        )
        rules = train_rules(cfg, mesh, seq_parallel=seq_parallel)
        p_sh = shardings_for(specs, mesh, rules, params_s)
        opt_s = {
            "m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_s),
            "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_s),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        o_sh = shardings_for(opt_state_specs(specs), mesh, rules, opt_s)
        batch_s = input_specs(cfg, case)
        b_sh = _batch_shardings(batch_s, mesh, rules)
        step = make_train_step(
            cfg, AdamWConfig(sqrt_unit=sqrt_unit), microbatches=microbatches
        )
        with axis_rules(mesh, rules):
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_s, opt_s, batch_s)
    elif case.kind == "prefill":
        rules = serve_rules(cfg, mesh)
        p_sh = shardings_for(specs, mesh, rules, params_s)
        batch_s = input_specs(cfg, case)
        b_sh = _batch_shardings(batch_s, mesh, rules)
        step = make_prefill_step(cfg)
        with axis_rules(mesh, rules):
            lowered = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(params_s, batch_s)
    else:  # decode
        # reshape 'model' into (kv, qg) when kv_heads divides it: the cache
        # then lives kv-head-sharded across steps (no per-step re-replication
        # collectives — §Perf deepseek-67b decode study)
        model_size = mesh.shape["model"]
        kvh = cfg.n_kv_heads
        if (not smoke) and 1 < kvh < model_size and model_size % kvh == 0 and any(
            b in ("global", "window") for b in cfg.blocks
        ):
            if mesh_kind == "multi":
                mesh = make_mesh_for(
                    (2, 16, kvh, model_size // kvh), ("pod", "data", "kv", "qg")
                )
            else:
                mesh = make_mesh_for((16, kvh, model_size // kvh), ("data", "kv", "qg"))
        seq_shard = case.global_batch < mesh.shape["data"]
        rules = serve_rules(cfg, mesh, seq_shard_kv=seq_shard)
        if quantized_kv is None:
            quantized_kv = _decode_hbm_estimate_gib(cfg, case, mesh) > 14.0
        p_sh = shardings_for(specs, mesh, rules, params_s)
        cache_s, cache_specs = lm.init_cache(
            cfg, case.global_batch, cache_len_for(cfg, case),
            quantized=quantized_kv, abstract=True,
        )
        c_sh = shardings_for(cache_specs, mesh, rules, cache_s)
        tok_s = input_specs(cfg, case)["tokens"]
        from jax.sharding import NamedSharding
        from repro.distributed.constraints import logical_to_spec

        t_sh = NamedSharding(mesh, logical_to_spec(("batch", None), rules))
        pos_s = jax.ShapeDtypeStruct((), jnp.int32)
        with_cross = cfg.kind == "encdec"
        step = make_serve_step(cfg, with_cross=with_cross)
        args = [params_s, cache_s, tok_s, pos_s]
        in_sh = [p_sh, c_sh, t_sh, None]
        if with_cross:
            ck_s = {
                "ck": jax.ShapeDtypeStruct(
                    (cfg.n_layers, case.global_batch, cfg.encoder.n_ctx, cfg.n_kv_heads, cfg.d_head),
                    jnp.dtype(cfg.act_dtype),
                ),
                "cv": jax.ShapeDtypeStruct(
                    (cfg.n_layers, case.global_batch, cfg.encoder.n_ctx, cfg.n_kv_heads, cfg.d_head),
                    jnp.dtype(cfg.act_dtype),
                ),
            }
            ck_sh = shardings_for(lm.cross_kv_specs(), mesh, rules, ck_s)
            args.append(ck_s)
            in_sh.append(ck_sh)
        with axis_rules(mesh, rules):
            jitted = jax.jit(
                step,
                in_shardings=tuple(in_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(*args)

    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    # trip-count-aware analysis (XLA's cost_analysis counts while bodies once)
    cost = analyze_hlo(hlo_text)

    flops = float(cost.flops)
    bytes_acc = float(cost.bytes)
    coll_bytes = float(cost.collective_bytes)
    colls = dict(cost.collectives)
    colls["total"] = {
        "count": sum(v["count"] for v in cost.collectives.values()),
        "bytes": coll_bytes,
    }

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "n_chips": n_chips,
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collectives": colls,
        "roofline": {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
        },
        "quantized_kv": quantized_kv,
        "microbatches": microbatches,
        "seq_parallel": seq_parallel,
    }
    dom = max(rec["roofline"], key=rec["roofline"].get)
    rec["roofline"]["dominant"] = dom
    return rec


def main():
    """CLI over :func:`lower_cell`: lower one (arch, shape, mesh) cell or
    ``--all``, writing one JSON record per cell to ``--out`` (cached by
    tag; delete the file to re-lower).  ``--smoke`` shrinks to CI scale."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=LM_ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--quantized-kv", default=None,
        type=lambda s: {"true": True, "false": False}[s.lower()],
        help="force int8 KV on/off; default: auto policy (fit 16GiB)",
    )
    ap.add_argument("--sqrt-unit", default="e2afs")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--smoke", action="store_true", help="reduced configs on a 2x2[x2] mesh")
    ap.add_argument("--remat", default=None, choices=("none", "block", "minimal"))
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    cells = (
        [(a, s) for a in LM_ARCHS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )

    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            tag = f"{arch}_{shape}_{mesh_kind}" + ("_qkv" if args.quantized_kv is True else "")
            if args.tag:
                tag += f"_{args.tag}"
            path = outdir / f"{tag}.json"
            if path.exists():
                print(f"[skip-cached] {tag}")
                continue
            try:
                rec = lower_cell(
                    arch, shape, mesh_kind, quantized_kv=args.quantized_kv,
                    sqrt_unit=args.sqrt_unit, microbatches=args.microbatches,
                    seq_parallel=args.seq_parallel, smoke=args.smoke,
                    extra_overrides={"remat": args.remat} if args.remat else None,
                )
            except Exception as e:  # noqa: BLE001 — record the failure and move on
                rec = {
                    "arch": arch, "shape": shape, "mesh": mesh_kind,
                    "status": f"FAIL: {type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                failures += 1
            path.write_text(json.dumps(rec, indent=2))
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (
                    f" compile={rec['compile_s']}s dom={r['dominant']}"
                    f" c={r['compute_s']:.4f} m={r['memory_s']:.4f} x={r['collective_s']:.4f}"
                )
            print(f"[{status[:60]}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
