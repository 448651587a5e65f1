"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (and persists under artifacts/dryrun/):
  * compiled.memory_analysis()  — per-device bytes (the "fits?" proof),
  * compiled.cost_analysis()    — HLO flops/bytes for the roofline terms,
  * the collective-bytes table parsed from the optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
     collective-permute operand sizes — cost_analysis does not report them).

Shape semantics per the assignment: train_4k lowers train_step;
prefill_32k lowers the full-sequence prefill; decode_32k / long_500k lower
serve_step (ONE new token against a seq_len KV cache).
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, arch_shapes, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.runtime import force_host_devices
from repro.launch.specs import (prefill_specs, serve_specs,
                                train_specs)
from repro.models.config import shape_by_name
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import (make_serve_step,
                                    make_train_step)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    # lines look like:  %x = bf16[4,128]{1,0} all-gather(...), replica_groups=
    pat = re.compile(
        r"=\s*(?:\(?)([a-z0-9]+)\[([0-9,]*)\][^=]*?\b(" +
        "|".join(_COLLECTIVES) + r")\b")
    for m in pat.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dt, 4)
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        out[kind] += n * nbytes
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def lower_cell(mesh, arch: str, shape_name: str,
               serve_sharding: str = "fsdp", kv_dtype: str = "compute",
               serve_dtype: str | None = None):
    """Returns (lowered, kind). Lowering is cheap; compile happens later.

    serve_sharding: 'fsdp' (baseline — weights DP-sharded, gathered per
    token) or 'replicated' (§Perf iteration 1 — weights replicated over DP,
    TP-only; no per-token parameter collectives).
    """
    cfg = get_config(arch)
    if kv_dtype != "compute":
        cfg = cfg.scaled(kv_cache_dtype=kv_dtype)
    if serve_dtype is not None:
        cfg = cfg.scaled(param_dtype=serve_dtype)
    shape = shape_by_name(shape_name)
    if shape.kind == "train":
        state_specs, batch_specs = train_specs(mesh, cfg, shape)
        step = make_train_step(cfg, OptimizerConfig())
        with jax.set_mesh(mesh):
            lowered = jax.jit(step).lower(state_specs, batch_specs)
        return lowered, "train_step"
    if shape.kind == "prefill":
        param_specs, batch_specs = prefill_specs(mesh, cfg, shape)
        from repro.train.train_step import make_prefill
        pf = make_prefill(cfg)
        with jax.set_mesh(mesh):
            if cfg.encoder_decoder:
                lowered = jax.jit(pf).lower(param_specs,
                                            batch_specs["tokens"],
                                            batch_specs["embeds"])
            else:
                lowered = jax.jit(pf).lower(param_specs,
                                            batch_specs["tokens"])
        return lowered, "prefill"
    # decode
    param_specs, token_specs, state_specs = serve_specs(
        mesh, cfg, shape, fsdp_params=(serve_sharding == "fsdp"))
    serve = make_serve_step(cfg)
    with jax.set_mesh(mesh):
        lowered = jax.jit(serve).lower(param_specs, token_specs, state_specs)
    return lowered, "serve_step"


def run_cell(mesh, mesh_name: str, arch: str, shape_name: str,
             outdir: str, compile_: bool = True,
             serve_sharding: str = "fsdp", kv_dtype: str = "compute",
             serve_dtype: str | None = None) -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok", "serve_sharding": serve_sharding,
           "kv_dtype": kv_dtype, "serve_dtype": serve_dtype}
    try:
        lowered, kind = lower_cell(mesh, arch, shape_name,
                                   serve_sharding=serve_sharding,
                                   kv_dtype=kv_dtype,
                                   serve_dtype=serve_dtype)
        rec["kind"] = kind
        rec["t_lower_s"] = round(time.time() - t0, 2)
        if compile_:
            t1 = time.time()
            compiled = lowered.compile()
            rec["t_compile_s"] = round(time.time() - t1, 2)
            # collectives exist only AFTER SPMD partitioning -> compiled HLO
            rec["collectives"] = parse_collective_bytes(compiled.as_text())
            from repro.analysis.roofline import cost_analysis_dict
            ca = cost_analysis_dict(compiled)
            rec["cost_analysis"] = {
                "flops": float(ca.get("flops", -1.0)),
                "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
                "transcendentals": float(ca.get("transcendentals", -1.0)),
            }
            ma = compiled.memory_analysis()
            if ma is not None:
                rec["memory_analysis"] = {
                    k: int(getattr(ma, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(ma, k)
                }
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["t_total_s"] = round(time.time() - t0, 2)
    os.makedirs(outdir, exist_ok=True)
    safe = f"{arch}_{shape_name}_{mesh_name}".replace("/", "_")
    with open(os.path.join(outdir, safe + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="artifacts/dryrun")
    ap.add_argument("--no-compile", action="store_true",
                    help="lower + parse HLO only (fast pass)")
    ap.add_argument("--serve-sharding", default="fsdp",
                    choices=["fsdp", "replicated"])
    ap.add_argument("--kv-dtype", default="compute",
                    choices=["compute", "int8"])
    ap.add_argument("--serve-dtype", default=None,
                    choices=[None, "bfloat16"])
    args = ap.parse_args()
    # 512 host devices let make_production_mesh build the production meshes
    force_host_devices(512)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))

    n_fail = 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            shapes = [s.name for s in arch_shapes(arch)]
            if args.shape != "all":
                if args.shape not in shapes:
                    continue
                shapes = [args.shape]
            for shape_name in shapes:
                rec = run_cell(mesh, mesh_name, arch, shape_name,
                               args.outdir, compile_=not args.no_compile,
                               serve_sharding=args.serve_sharding,
                               kv_dtype=args.kv_dtype,
                               serve_dtype=args.serve_dtype)
                flops = rec.get("cost_analysis", {}).get("flops", -1)
                coll = rec.get("collectives", {}).get("total_bytes", -1)
                print(f"[{rec['status']:4s}] {mesh_name:12s} {arch:22s} "
                      f"{shape_name:12s} kind={rec.get('kind', '?'):10s} "
                      f"lower={rec.get('t_lower_s', 0):7.1f}s "
                      f"compile={rec.get('t_compile_s', 0):7.1f}s "
                      f"flops={flops:.3e} coll_bytes={coll:.3e}"
                      if rec["status"] == "ok" else
                      f"[FAIL] {mesh_name} {arch} {shape_name}: "
                      f"{rec.get('error', '')[:200]}", flush=True)
                if rec["status"] != "ok":
                    n_fail += 1
    print(f"dry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
