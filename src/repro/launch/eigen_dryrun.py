"""Eigensolver-side multi-pod dry-run: lowers the PAPER's pipelines on the
production meshes (the LM dry-run lives in dryrun.py).

Stages lowered, mirroring Table 1 of the paper:
  GS1  dist_cholesky          (block-row, one broadcast per panel)
  GS2  dist_trsm_left_t x2    (the paper's preferred two-TRSM path)
  KE1  dist_symv              (the Krylov hot loop, 2D-sharded C)
  BT1  dist_trsm              (back-transform)
Artifacts (cost/memory/collectives) feed §Roofline for the paper-side rows.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.lanczos import lanczos_solve_jit
from repro.core.operators import ExplicitC
from repro.dist.sharded_la import (dist_cholesky, dist_gemm,
                                   dist_gemm_rs, dist_symv, dist_symv_rs,
                                   dist_trsm_left_t)
from repro.launch.dryrun import parse_collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.runtime import force_host_devices


def run(mesh, mesh_name: str, n: int, s: int, outdir: str,
        dtype=jnp.float32) -> list[dict]:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else dp[0]
    rowsh = NamedSharding(mesh, P(dp_spec, None))
    sh2d = NamedSharding(mesh, P(dp_spec, "model"))
    vsh = NamedSharding(mesh, P("model"))

    B_spec = jax.ShapeDtypeStruct((n, n), dtype, sharding=rowsh)
    A2d_spec = jax.ShapeDtypeStruct((n, n), dtype, sharding=sh2d)
    x_spec = jax.ShapeDtypeStruct((n,), dtype, sharding=vsh)
    Y_spec = jax.ShapeDtypeStruct((n, s), dtype, sharding=rowsh)

    stages = {
        "GS1_dist_cholesky": (lambda Bm: dist_cholesky(mesh, Bm), [B_spec]),
        "GS2_dist_trsm": (lambda U, A: dist_trsm_left_t(mesh, U, A),
                          [B_spec, B_spec]),
        "KE1_dist_symv": (lambda C, x: dist_symv(mesh, C, x),
                          [A2d_spec, x_spec]),
        "KE1_dist_symv_rs": (lambda C, x: dist_symv_rs(mesh, C, x),
                             [A2d_spec, x_spec]),
        "TT4_dist_gemm": (lambda Q, Z: dist_gemm(mesh, Q, Z),
                          [A2d_spec, jax.ShapeDtypeStruct(
                              (n, s), dtype,
                              sharding=NamedSharding(mesh, P("model", None)))]),
        "TT4_dist_gemm_rs": (lambda Q, Z: dist_gemm_rs(mesh, Q, Z),
                             [A2d_spec, jax.ShapeDtypeStruct(
                                 (n, s), dtype,
                                 sharding=NamedSharding(mesh,
                                                        P("model", None)))]),
        "BT1_dist_trsm": (lambda U, Y: dist_trsm_left_t(mesh, U, Y),
                          [B_spec, Y_spec]),
        # the WHOLE thick-restart Lanczos solver (lax.while_loop driver) on
        # the 2D-sharded operator: proves the paper's iterative method —
        # not just its matvec — compiles for the production mesh.
        "KE_full_solver_jit": (
            lambda C, v0: lanczos_solve_jit(ExplicitC(C), v0, s=16, m=48,
                                            which="SA", max_restarts=8),
            [A2d_spec, jax.ShapeDtypeStruct(
                (n,), dtype, sharding=NamedSharding(mesh, P()))]),
    }

    recs = []
    for name, (fn, specs) in stages.items():
        t0 = time.time()
        rec = {"stage": name, "mesh": mesh_name, "n": n, "s": s,
               "status": "ok"}
        try:
            with jax.set_mesh(mesh):
                lowered = jax.jit(fn).lower(*specs)
            compiled = lowered.compile()
            from repro.analysis.roofline import cost_analysis_dict
            ca = cost_analysis_dict(compiled)
            rec["cost_analysis"] = {
                "flops": float(ca.get("flops", -1.0)),
                "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            }
            rec["collectives"] = parse_collective_bytes(compiled.as_text())
            ma = compiled.memory_analysis()
            if ma is not None:
                rec["memory_analysis"] = {
                    k: int(getattr(ma, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes", "temp_size_in_bytes")
                    if hasattr(ma, k)}
        except Exception as e:  # noqa: BLE001
            rec["status"] = "FAIL"
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t_total_s"] = round(time.time() - t0, 2)
        recs.append(rec)
        coll = rec.get("collectives", {}).get("total_bytes", -1)
        print(f"[{rec['status']:4s}] {mesh_name:12s} {name:20s} "
              f"t={rec['t_total_s']:6.1f}s "
              f"flops={rec.get('cost_analysis', {}).get('flops', -1):.3e} "
              f"coll={coll:.3e} "
              f"{rec.get('error', '')[:120]}", flush=True)
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"eigen_{mesh_name}_n{n}.json"), "w") as f:
        json.dump(recs, f, indent=1)
    return recs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16_384,
                    help="problem size (paper: 9,997 and 17,243; default is "
                         "the DFT scale rounded to the mesh)")
    ap.add_argument("--s", type=int, default=448)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="artifacts/eigen_dryrun")
    args = ap.parse_args()
    force_host_devices(512)      # the production meshes, on host devices

    n_fail = 0
    if args.mesh in ("single", "both"):
        mesh = make_production_mesh(multi_pod=False)
        n_fail += sum(r["status"] != "ok"
                      for r in run(mesh, "pod16x16", args.n, args.s,
                                   args.outdir))
    if args.mesh in ("multi", "both"):
        mesh = make_production_mesh(multi_pod=True)
        n_fail += sum(r["status"] != "ok"
                      for r in run(mesh, "pods2x16x16", args.n, args.s,
                                   args.outdir))
    print(f"eigen dry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
