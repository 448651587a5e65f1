"""``closed``: one solve at a time, back to back, through
``repro.core.solve``, over the traffic's pencils in rotation.

The traffic file gives ``pencils``, the number of independent pencils;
pencil i is built from ``fold_in(key, i)``. A solve starts while the
time elapsed plus the last solve's time is within the window's seconds;
the first always starts and none is cut. With ``trace`` the window ends
after its first solve: one solve of the paper's size holds ~5 million
device op events, the profiler drops events past ~6 million, and
stopping it takes ~25 s a million.
"""
from __future__ import annotations

import time
import traceback

import jax


def _solve():
    from repro.core import solve
    return solve


def build(gen, cfg: dict, traffic: dict, key: jax.Array) -> list:
    """The traffic's pencils, on the device."""
    pencils = [gen.build(cfg["n"], jax.random.fold_in(key, i))
               for i in range(traffic["pencils"])]
    jax.block_until_ready(pencils)
    return pencils


def warm_up(pencils: list, s: int, call: dict, capped: dict) -> None:
    """One solve of the cell's call on the first pencil, capped as the
    cell's ``warmup`` says: the programs of the timed solve, compiled or
    loaded."""
    res = _solve()(pencils[0].A, pencils[0].B, s, **dict(call, **capped))
    jax.block_until_ready((res.evals, res.X))


def _record(i: int, seconds: float, res) -> dict:
    info = res.info
    return {"pencil": i, "seconds": seconds,
            "stage_times": {k: float(v) for k, v in res.stage_times.items()},
            "n_matvec": info.get("n_matvec"),
            "n_restart": info.get("n_restart"),
            "p": info.get("krylov", {}).get("p"),
            "unconverged": bool(info.get("warnings"))
            or not info.get("converged", True),
            "recovery": bool(info.get("recovery")),
            "unhealthy": not info.get("health", {}).get("healthy", False)}


def window(pencils: list, s: int, call: dict, seconds: float, trace: bool,
           report) -> tuple:
    """The timed solves: (outputs, records, errors, seconds taken), an
    output being (pencil index, eigenvalues, eigenvectors)."""
    solve = _solve()
    outputs, solves, errors = [], [], []
    t0 = time.perf_counter()
    last = 0.0
    while not outputs and not errors or not trace and (
            time.perf_counter() - t0 + last <= seconds):
        i = (len(outputs) + len(errors)) % len(pencils)
        ts = time.perf_counter()
        try:
            res = solve(pencils[i].A, pencils[i].B, s, **call)
            jax.block_until_ready((res.evals, res.X))
        except Exception:  # a failed solve is a result of the run
            errors.append(traceback.format_exc())
            report(errors[-1])
            last = time.perf_counter() - ts
            continue
        last = time.perf_counter() - ts
        outputs.append((i, res.evals, res.X))
        solves.append(_record(i, last, res))
        del res
    return outputs, solves, errors, time.perf_counter() - t0
