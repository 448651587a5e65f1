"""Distributed-KE benchmark: single device vs an 8-host-device mesh.

Runs ``repro.dist.eigensolver.solve_ke_distributed`` on the MD-like
problem twice — on a degenerate (1, 1) mesh and on the (4, 2)
data x model mesh over 8 forced host-platform devices — at the settings
that actually converge (the paper's inverse-pair trick + tol=1e-9 +
block size p=4), and records wall-clock per stage, Lanczos counters,
and the host dispatch count. The Krylov stage is the
communication-avoiding block Lanczos: ONE fused shard_map program per
thick restart, two collectives per p-column block step.

Reading the numbers: on a multi-core host the 8-device run should match
or beat the single device; when the container pins all 8 virtual
devices to fewer physical cores (``cores`` in the artifact), the ratio
measures time-sharing overhead, not the algorithm — the
hardware-independent invariants (convergence, dispatch budget, absolute
wall-clock) are what ``--quick`` gates on unconditionally.

Standalone (sets its own XLA flags, so run it directly, not via run.py):

    PYTHONPATH=src python -m benchmarks.bench_dist_ke [--n 128 --s 4]
    PYTHONPATH=src python -m benchmarks.bench_dist_ke --quick  # CI gate

Emits ``artifacts/BENCH_dist_ke.json`` next to the other benchmark tables
and prints the usual ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.dist.partitioning import make_mesh
from repro.launch.runtime import force_host_devices

jax.config.update("jax_enable_x64", True)

#: absolute wall-clock ceiling for the 8-device quick gate (seconds). The
#: pre-rework solver (unconverged at 300 restarts, 3 dispatches/restart)
#: took ~23s here; the fused block driver converges in a few restarts and
#: finishes in well under a second even on a single-core container.
QUICK_WALL_CEILING_S = 5.0


def bench_mesh(mesh_shape, n: int, s: int, m: int, p: int,
               filter_degree: int, tol: float, repeats: int) -> dict:
    from repro.data.problems import md_like
    from repro.dist import eigensolver as de

    mesh = make_mesh(mesh_shape, ("data", "model"))
    prob = md_like(n)
    label = "x".join(str(d) for d in mesh_shape)

    def run():
        # the paper's MD trick: solve the inverse pair (B, A) for its
        # largest (well-separated) eigenpairs — the setting at which the
        # log-spaced MD spectrum converges in a handful of restarts
        return de.solve_ke_distributed(
            mesh, prob.A, prob.B, s, m=m, tol=tol, max_restarts=300,
            p=p, filter_degree=filter_degree, invert=True,
            return_info=True)

    evals, X, info = run()   # warmup compiles every stage
    walls, dispatches = [], []
    for _ in range(repeats):
        de.reset_dispatch_count()
        t0 = time.perf_counter()
        evals, X, info = run()
        walls.append(time.perf_counter() - t0)
        dispatches.append(de.dispatch_count())
    err = float(np.max(np.abs(np.asarray(evals)
                              - np.asarray(prob.exact_evals[:s]))))
    return {
        "mesh": label,
        "n_devices": int(np.prod(mesh_shape)),
        "n": n, "s": s, "m": m,
        "krylov_block": int(info["p"]),
        "filter_degree": int(info["filter_degree"]),
        "invert": True,
        "tol": tol,
        "wall_s_median": sorted(walls)[len(walls) // 2],
        "wall_s_all": walls,
        "stage_times_s": {k: round(v, 5)
                          for k, v in info["stage_times"].items()},
        "n_matvec": info["n_matvec"],
        "n_restart": info["n_restart"],
        "n_dispatch": max(dispatches),
        "converged": info["converged"],
        "max_abs_eval_error": err,
    }


def quick_gate(recs: list, cores: int) -> None:
    """The CI acceptance gate: hardware-independent invariants always, the
    strict 8-device >= 1-device throughput only when the host actually has
    a core per device (a single-core container time-shares the mesh, so a
    wall-clock speedup there is physically impossible — the artifact
    records ``cores`` and ``t8_over_t1`` so the regression is auditable
    either way)."""
    for r in recs:
        assert r["converged"], f"KE did not converge on mesh {r['mesh']}: {r}"
        assert r["max_abs_eval_error"] < 1e-8, r
        # fused dispatch discipline: one program per restart (+ prep)
        assert r["n_dispatch"] <= r["n_restart"] + 2, r
    t1 = next(r for r in recs if r["n_devices"] == 1)["wall_s_median"]
    t8 = next(r for r in recs if r["n_devices"] > 1)["wall_s_median"]
    assert t8 < QUICK_WALL_CEILING_S, (
        f"8-device KE took {t8:.2f}s (> {QUICK_WALL_CEILING_S}s ceiling)")
    n_dev = max(r["n_devices"] for r in recs)
    if cores >= n_dev:
        assert t8 <= t1, (
            f"8-device run slower than single device on a "
            f"{cores}-core host: t8={t8:.3f}s t1={t1:.3f}s")
    print(f"quick gate OK (cores={cores}, t8/t1={t8 / t1:.2f})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--s", type=int, default=4)
    ap.add_argument("--m", type=int, default=24)
    ap.add_argument("--p", type=int, default=4,
                    help="Lanczos block size (s-step width)")
    ap.add_argument("--filter-degree", type=int, default=0)
    ap.add_argument("--tol", type=float, default=1e-9)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="assert the CI acceptance gate after measuring")
    ap.add_argument("--outdir", default="artifacts")
    args = ap.parse_args()
    force_host_devices(8)        # the 8-device host mesh

    recs = [bench_mesh((1, 1), args.n, args.s, args.m, args.p,
                       args.filter_degree, args.tol, args.repeats),
            bench_mesh((4, 2), args.n, args.s, args.m, args.p,
                       args.filter_degree, args.tol, args.repeats)]
    cores = os.cpu_count() or 1
    t1 = next(r for r in recs if r["n_devices"] == 1)["wall_s_median"]
    t8 = next(r for r in recs if r["n_devices"] > 1)["wall_s_median"]

    print("name,us_per_call,derived")
    for r in recs:
        print(f"bench_dist_ke_{r['mesh']},{r['wall_s_median'] * 1e6:.1f},"
              f"n_matvec={r['n_matvec']};n_restart={r['n_restart']};"
              f"n_dispatch={r['n_dispatch']};"
              f"converged={r['converged']};"
              f"eval_err={r['max_abs_eval_error']:.3e}")

    os.makedirs(args.outdir, exist_ok=True)
    out = os.path.join(args.outdir, "BENCH_dist_ke.json")
    payload = {"records": recs, "cores": cores,
               "t8_over_t1": t8 / t1}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}")

    if args.quick:
        quick_gate(recs, cores)


if __name__ == "__main__":
    main()
