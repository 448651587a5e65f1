"""TT3 benchmark: scan baseline vs fused batched path vs sharded TT3.

Three executions of the same tridiagonal eigensolve, raced per
``(n, s)`` cell on random tridiagonals:

  scan     — the legacy two-program baseline (``method='scan'``:
             bisection jit + inverse-iteration jit, unroll=1 Sturm scans)
  batched  — ONE fused program with the Sturm scans unrolled
             (``kernels.tridiag_eig.tridiag_eig_batched``, the default
             every pipeline runs); bitwise-identical values, the per-step
             scan overhead amortized over ``SCAN_UNROLL`` rows
  sharded  — the spectrum-partitioned TT3 over an 8-host-device (4, 2)
             mesh (``dist.eigensolver.dist_tridiag_eig``: per-device
             contiguous index slices, 1 + iters collectives), raced
             against the replicated batched path on the same host

Reading the numbers: ``batched`` vs ``scan`` is a pure dispatch/loop-
overhead race on identical arithmetic — the artifact records the bitwise
check alongside the speedup. The sharded row time-shares 8 virtual
devices over however many cores the container grants (recorded as
``cores``), so its wall clock measures oversubscription, not the
algorithm; the hardware-independent invariants — bitwise eigenvalues and
ulp-level eigenvectors vs the replicated path — are what ``--quick``
gates on, plus the batched-beats-scan margin at the largest cell
(n=2048, s=64).

Standalone (sets its own XLA flags, so run it directly, not via run.py):

    PYTHONPATH=src python -m benchmarks.bench_tridiag
    PYTHONPATH=src python -m benchmarks.bench_tridiag --quick  # CI gate

Emits ``artifacts/BENCH_tridiag.json`` and the usual
``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.partitioning import make_mesh
from repro.launch.runtime import force_host_devices

jax.config.update("jax_enable_x64", True)

#: full-run cells; ``--quick`` keeps only the gated largest cell plus one
#: small one (compile time, not solve time, dominates the small cells)
CELLS = [(512, 8), (512, 64), (2048, 8), (2048, 64)]
#: the acceptance cell: the fused batched path must beat the scan
#: baseline here (it is the cell where the Sturm scan's per-step overhead
#: is the whole stage)
GATE_CELL = (2048, 64)


def _problem(n: int, seed: int = 0):
    kd, ke = jax.random.split(jax.random.PRNGKey(seed))
    d = jax.random.normal(kd, (n,), jnp.float64)
    e = jax.random.normal(ke, (n - 1,), jnp.float64)
    return d, e


def _time_median(fn, repeats: int) -> float:
    fn()  # warmup: compile
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t0)
    return sorted(walls)[len(walls) // 2]


def bench_cell(n: int, s: int, repeats: int) -> dict:
    from repro.core.tridiag_eig import eigh_tridiag_selected

    d, e = _problem(n)
    ks = jnp.arange(s)
    key = jax.random.PRNGKey(1)
    t_scan = _time_median(
        lambda: eigh_tridiag_selected(d, e, ks, key, method="scan"), repeats)
    t_batched = _time_median(
        lambda: eigh_tridiag_selected(d, e, ks, key, method="batched"),
        repeats)
    lam_s, Z_s = eigh_tridiag_selected(d, e, ks, key, method="scan")
    lam_b, Z_b = eigh_tridiag_selected(d, e, ks, key, method="batched")
    bitwise = bool(np.array_equal(np.asarray(lam_s), np.asarray(lam_b))
                   and np.array_equal(np.asarray(Z_s), np.asarray(Z_b)))
    return {"n": n, "s": s,
            "scan_s_median": t_scan,
            "batched_s_median": t_batched,
            "speedup_batched_over_scan": t_scan / t_batched,
            "bitwise_batched_eq_scan": bitwise}


def bench_sharded(mesh, n: int, s: int, repeats: int) -> dict:
    from repro.core.tridiag_eig import eigh_tridiag_selected
    from repro.dist.eigensolver import dist_tridiag_eig

    d, e = _problem(n)
    ks = jnp.arange(s)
    key = jax.random.PRNGKey(1)
    t_rep = _time_median(
        lambda: eigh_tridiag_selected(d, e, ks, key, method="batched"),
        repeats)
    t_sh = _time_median(
        lambda: dist_tridiag_eig(mesh, d, e, ks, key), repeats)
    lam_r, Z_r = eigh_tridiag_selected(d, e, ks, key, method="batched")
    lam_d, Z_d = dist_tridiag_eig(mesh, d, e, ks, key)
    # lam is bitwise (independent lanes); Z only up to the vector-width
    # reassociation of the column-norm reduction (ulp-level)
    lam_bitwise = bool(np.array_equal(np.asarray(lam_r), np.asarray(lam_d)))
    z_err = float(np.abs(np.asarray(Z_r) - np.asarray(Z_d)).max())
    return {"n": n, "s": s, "n_devices": int(mesh.devices.size),
            "replicated_s_median": t_rep,
            "sharded_s_median": t_sh,
            "lam_bitwise_sharded_eq_replicated": lam_bitwise,
            "z_max_abs_err_vs_replicated": z_err}


def quick_gate(cells: list, sharded: list) -> None:
    """CI acceptance: values first (bitwise both ways), then the one
    hardware-robust perf claim — the fused batched path beats the scan
    baseline at the gate cell, where the race is pure loop overhead on
    identical arithmetic (a single-core container slows both sides
    equally, so the ratio survives time-sharing)."""
    for r in cells:
        assert r["bitwise_batched_eq_scan"], r
    for r in sharded:
        assert r["lam_bitwise_sharded_eq_replicated"], r
        assert r["z_max_abs_err_vs_replicated"] <= 1e-12, r
    g = next(r for r in cells if (r["n"], r["s"]) == GATE_CELL)
    assert g["batched_s_median"] < g["scan_s_median"], (
        f"fused batched TT3 lost to the scan baseline at n={g['n']}, "
        f"s={g['s']}: {g['batched_s_median']:.3f}s vs "
        f"{g['scan_s_median']:.3f}s")
    print(f"quick gate OK (gate cell speedup "
          f"{g['speedup_batched_over_scan']:.2f}x)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="gated cells only + assert the CI acceptance gate")
    ap.add_argument("--outdir", default="artifacts")
    args = ap.parse_args()
    force_host_devices(8)        # the 8-device host mesh

    cell_list = [(512, 8), GATE_CELL] if args.quick else CELLS
    cells = [bench_cell(n, s, args.repeats) for n, s in cell_list]

    mesh = make_mesh((4, 2), ("data", "model"))
    sharded_list = [(512, 64)] if args.quick else [(512, 64), (2048, 64)]
    sharded = [bench_sharded(mesh, n, s, args.repeats)
               for n, s in sharded_list]

    print("name,us_per_call,derived")
    for r in cells:
        print(f"bench_tridiag_n{r['n']}_s{r['s']},"
              f"{r['batched_s_median'] * 1e6:.1f},"
              f"scan_us={r['scan_s_median'] * 1e6:.1f};"
              f"speedup={r['speedup_batched_over_scan']:.2f};"
              f"bitwise={r['bitwise_batched_eq_scan']}")
    for r in sharded:
        print(f"bench_tridiag_sharded_n{r['n']}_s{r['s']},"
              f"{r['sharded_s_median'] * 1e6:.1f},"
              f"replicated_us={r['replicated_s_median'] * 1e6:.1f};"
              f"lam_bitwise={r['lam_bitwise_sharded_eq_replicated']};"
              f"z_err={r['z_max_abs_err_vs_replicated']:.1e}")

    os.makedirs(args.outdir, exist_ok=True)
    out = os.path.join(args.outdir, "BENCH_tridiag.json")
    payload = {"cells": cells, "sharded": sharded,
               "cores": os.cpu_count() or 1,
               "unroll": _scan_unroll()}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}")

    if args.quick:
        quick_gate(cells, sharded)


def _scan_unroll() -> int:
    from repro.kernels.tridiag_eig.ops import SCAN_UNROLL
    return int(SCAN_UNROLL)


if __name__ == "__main__":
    main()
