"""CLI for the paper's eigensolvers:

    PYTHONPATH=src python -m repro.launch.eigsolve \
        --problem md --n 512 --s 8 --variant KE --invert

Distributed execution (KE and TT): ``--mesh DxM`` lays a (data=D, model=M)
mesh over the visible devices and routes the solve through
``repro.dist`` (core.solve's ``mesh=`` dispatch); ``--devices N`` forces N
host-platform devices for CPU testing, e.g.

    PYTHONPATH=src python -m repro.launch.eigsolve \
        --problem md --n 64 --s 4 --variant TT --devices 8 --mesh 4x2

``--variant auto`` defers the choice to the flop/bandwidth cost model in
``repro.analysis.variant_model`` (the decision and its predicted-time
table are printed in the payload under ``router``).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.core import solve
from repro.core.residuals import accuracy_report
from repro.data.problems import dft_like, md_like
from repro.dist.partitioning import make_mesh
from repro.launch.runtime import enable_compile_cache, force_host_devices

jax.config.update("jax_enable_x64", True)


def _parse_mesh(spec: str | None):
    """'4x2' -> Mesh((4, 2), ('data', 'model')); None -> single device."""
    if not spec:
        return None
    dims = tuple(int(x) for x in spec.lower().split("x"))
    if len(dims) != 2:
        raise SystemExit(f"--mesh wants DATAxMODEL, e.g. 4x2; got {spec!r}")
    return make_mesh(dims, ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", choices=["md", "dft"], default="md")
    ap.add_argument("--n", type=int, default=384)
    ap.add_argument("--s", type=int, default=8)
    ap.add_argument("--variant", choices=["TD", "TT", "KE", "KI", "auto"],
                    default="KE")
    ap.add_argument("--which", choices=["smallest", "largest"],
                    default="smallest")
    ap.add_argument("--invert", action="store_true",
                    help="the paper's MD trick (requires A SPD)")
    ap.add_argument("--gs2", choices=["trsm", "sygst"], default="trsm")
    ap.add_argument("--td1", choices=["unblocked", "blocked"],
                    default="unblocked")
    ap.add_argument("--band-width", type=int, default=8)
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--max-restarts", type=int, default=300)
    ap.add_argument("--p", type=int, default=None, dest="krylov_block",
                    help="Lanczos block size (s-step width); default: 4 "
                         "on a mesh, 1 locally")
    ap.add_argument("--filter-degree", type=int, default=None,
                    help="Chebyshev start-filter degree (KE/KI); default: "
                         "16 on clustered spectra, else off; 0 forces off")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="Lanczos residual tolerance (0 = machine-eps "
                         "criterion; 1e-9 is the converging setting on "
                         "the paper's spectra)")
    ap.add_argument("--precision", choices=["fp64", "mixed", "fast"],
                    default="fp64",
                    help="compute dtype of the GEMM-heavy stages (mixed = "
                         "fp32, fast = bf16/fp32-acc); non-fp64 runs the "
                         "fp64 refinement epilogue and reports its "
                         "trajectory")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL mesh (e.g. 4x2): run the KE or TT "
                         "variant (or --variant auto, restricted to those "
                         "two) through the repro.dist distributed pipeline")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host-platform devices (CPU only; pairs "
                         "with --mesh)")
    ap.add_argument("--on-failure", choices=["recover", "warn", "ignore"],
                    default="warn",
                    help="degradation-ladder policy (resilience.recovery): "
                         "'warn' diagnoses failures, 'recover' additionally "
                         "retries/escalates/falls back, 'ignore' restores "
                         "the pre-resilience behavior")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="transient-failure retries under "
                         "--on-failure recover")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    force_host_devices(args.devices)
    enable_compile_cache()

    mesh = _parse_mesh(args.mesh)
    if mesh is not None and args.variant not in ("KE", "TT", "auto"):
        raise SystemExit("--mesh is only implemented for --variant "
                         "KE, TT, or auto")

    prob = (md_like if args.problem == "md" else dft_like)(args.n)
    res = solve(prob.A, prob.B, args.s, variant=args.variant,
                which=args.which, invert=args.invert, gs2=args.gs2,
                td1=args.td1, band_width=args.band_width, m=args.m,
                max_restarts=args.max_restarts, mesh=mesh, tol=args.tol,
                krylov_block=args.krylov_block, filter=args.filter_degree,
                precision=args.precision,
                on_failure=args.on_failure, max_retries=args.max_retries,
                # the router's clustered-spectrum hint: the DFT generator's
                # low end is the paper's slow-Lanczos regime
                clustered=(args.problem == "dft"
                           and args.which == "smallest"))
    acc = accuracy_report(prob.A, prob.B, res.X, res.evals)
    exact = np.asarray(prob.exact_evals)
    want = exact[:args.s] if args.which == "smallest" else exact[-args.s:]
    err = float(np.max(np.abs(np.asarray(res.evals) - want)))
    payload = {
        "variant": res.info["variant"],
        "requested_variant": args.variant,
        "n": args.n, "s": args.s,
        "mesh": args.mesh or "single",
        "n_devices": jax.device_count(),
        "evals": [float(x) for x in res.evals],
        "stage_times_s": {k: round(v, 4) for k, v in res.stage_times.items()},
        "b_orthogonality": float(acc.b_orthogonality),
        "relative_residual": float(acc.relative_residual),
        "max_abs_eval_error": err,
        "n_matvec": int(res.info.get("n_matvec", 0)),
        "health": res.info["health"],
        "recovery": res.info["recovery"],
    }
    if "warnings" in res.info:
        payload["warnings"] = res.info["warnings"]
    if "router" in res.info:
        payload["router"] = res.info["router"]
    if "refinement" in res.info:
        rinfo = res.info["refinement"]
        payload["precision"] = args.precision
        payload["refinement"] = {
            "steps": int(rinfo["steps"]),
            "converged": bool(rinfo["converged"]),
            "relative_residual": [float(x)
                                  for x in rinfo["relative_residual"]],
            "b_orthogonality": [float(x)
                                for x in rinfo["b_orthogonality"]],
        }
    if args.json:
        print(json.dumps(payload, indent=1))
    else:
        for k, v in payload.items():
            print(f"{k}: {v}")


if __name__ == "__main__":
    main()
