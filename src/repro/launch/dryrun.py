import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each supported cell this lowers the right step (train / prefill / decode)
with explicit in/out shardings on the production mesh, compiles it, and
records:  memory_analysis (fits-per-device proof), cost_analysis, and the
loop-trip-corrected HLO summary (dot FLOPs, HBM bytes, collective wire bytes)
that EXPERIMENTS.md §Roofline consumes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, get_config
from ..core.peaks import attached_peaks
from ..models import RunOpts, Transformer
from ..optim.adamw import AdamWConfig
from ..runtime import serve_lib, train_lib
from . import hlo_analysis
from .mesh import make_production_mesh


def input_specs(cfg, shape, kind: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
    elif kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    else:  # decode: just the new tokens; cache specs come from the model
        return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
    if cfg.is_encoder_decoder:
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    return specs


def run_opts_for(shape, args) -> RunOpts:
    return RunOpts(
        attention_impl=args.attn_impl,
        attn_chunk=args.attn_chunk,
        loss_impl=args.loss_impl,
        loss_chunk=args.loss_chunk,
        softmax_dtype=args.softmax_dtype,
        cp_attention=args.cp_attention,
        moe_grouped=args.moe_grouped,
        sp_residual=args.sp_residual,
        ssd_shard_p=args.ssd_shard_p,
    )


def lower_cell(arch: str, shape_name: str, mesh, args):
    """Returns (lowered, meta) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    opts = run_opts_for(shape, args)
    model = Transformer(cfg, opts)
    kind = shape.kind
    meta = {"arch": arch, "shape": shape_name, "kind": kind,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "device_kind": attached_peaks().device_kind}

    if kind == "train":
        acfg = AdamWConfig()
        topts = train_lib.TrainOpts(microbatches=args.microbatches,
                                    remat=not args.no_remat)
        batch_sds = input_specs(cfg, shape, kind)
        step, _ = train_lib.build_train_step(model, mesh, acfg, topts,
                                             batch_sds=batch_sds)
        state_sds = train_lib.abstract_state(model, acfg, topts)
        lowered = step.lower(state_sds, batch_sds)
    elif kind == "prefill":
        batch_sds = input_specs(cfg, shape, kind)
        step = serve_lib.build_prefill_step(model, mesh, batch_sds=batch_sds,
                                            max_len=shape.seq_len)
        params_sds = model.abstract()
        lowered = step.lower(params_sds, batch_sds)
    else:  # decode
        b, s = shape.global_batch, shape.seq_len
        step = serve_lib.build_decode_step(model, mesh, batch=b, max_len=s,
                                           shard_cache_len=args.shard_cache_len)
        params_sds = model.abstract()
        cache_sds = model.cache_spec(b, s)
        tok_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
        lowered = step.lower(params_sds, cache_sds, tok_sds)
    return lowered, meta


def analyze_cell(lowered, meta, args) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    meta["compile_s"] = round(time.time() - t0, 2)

    try:
        ma = compiled.memory_analysis()
        meta["memory_analysis"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        meta["memory_analysis"] = {"error": str(e)[:200]}
    try:
        ca = compiled.cost_analysis()
        meta["cost_analysis"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        meta["cost_analysis"] = {"error": str(e)[:200]}

    hlo = compiled.as_text()
    meta["hlo_chars"] = len(hlo)
    summary = hlo_analysis.analyze(hlo)
    meta["hlo"] = {
        "dot_flops": summary.dot_flops,
        "hbm_bytes": summary.hbm_bytes,
        "coll_bytes": summary.coll_bytes,
        "coll_bytes_by_kind": summary.coll_bytes_by_kind,
        "coll_counts": summary.coll_counts,
        "n_while": summary.n_while,
        "trips": summary.trips,
    }
    if args.save_hlo:
        path = os.path.join(args.out, "hlo",
                            f"{meta['arch']}__{meta['shape']}__{meta['mesh_tag']}.txt")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(hlo)
    return meta


def supported(arch: str, shape_name: str) -> bool:
    return get_config(arch).supports_shape(SHAPES[shape_name])


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--attn-impl", default="auto")
    p.add_argument("--attn-chunk", type=int, default=1024)
    p.add_argument("--loss-impl", default="full")
    p.add_argument("--loss-chunk", type=int, default=512)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--softmax-dtype", default="float32")
    p.add_argument("--cp-attention", action="store_true")
    p.add_argument("--moe-grouped", action="store_true")
    p.add_argument("--shard-cache-len", action="store_true")
    p.add_argument("--sp-residual", action="store_true")
    p.add_argument("--ssd-shard-p", action="store_true")
    p.add_argument("--save-hlo", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument("--list", action="store_true")
    args = p.parse_args()

    archs = ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    cells = [(a, s, mp) for a in archs for s in shapes for mp in meshes]
    if args.list:
        for a, s, mp in cells:
            ok = supported(a, s)
            print(f"{a:24s} {s:12s} {'multi' if mp else 'single':6s} "
                  f"{'RUN' if ok else 'SKIP (DESIGN.md §4)'}")
        return

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape_name, multi_pod in cells:
        mesh_tag = "multi" if multi_pod else "single"
        tag = f"{arch}__{shape_name}__{mesh_tag}"
        out_path = os.path.join(args.out, tag + (args.tag and f"__{args.tag}") + ".json")
        if not supported(arch, shape_name):
            n_skip += 1
            print(f"[skip] {tag} (full attention at 500k — DESIGN.md §4)")
            continue
        try:
            t0 = time.time()
            mesh = make_production_mesh(multi_pod=multi_pod)
            lowered, meta = lower_cell(arch, shape_name, mesh, args)
            meta["mesh_tag"] = mesh_tag
            meta["lower_s"] = round(time.time() - t0, 2)
            meta = analyze_cell(lowered, meta, args)
            meta["status"] = "ok"
            with open(out_path, "w") as f:
                json.dump(meta, f, indent=1)
            h = meta["hlo"]
            print(f"[ok]   {tag} lower={meta['lower_s']}s "
                  f"compile={meta['compile_s']}s "
                  f"flops={h['dot_flops']:.3g} hbm={h['hbm_bytes']:.3g} "
                  f"coll={h['coll_bytes']:.3g}")
            n_ok += 1
        except Exception as e:
            n_fail += 1
            err = {"status": "fail", "arch": arch, "shape": shape_name,
                   "mesh_tag": mesh_tag, "error": str(e)[:2000],
                   "traceback": traceback.format_exc()[-4000:]}
            with open(out_path, "w") as f:
                json.dump(err, f, indent=1)
            print(f"[FAIL] {tag}: {str(e)[:300]}")
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
