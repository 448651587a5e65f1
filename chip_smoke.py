#!/usr/bin/env python3
"""Run the eigensolver once on a TPU at the paper's MD size and check it.

    python3 chip_smoke.py               # one chip: local KE of the MD pencil
    python3 chip_smoke.py --mesh 2x2    # four chips: distributed KE only

The iMod normal-mode deployment of the paper (``paper_shapes()["md"]``:
n=9,997, the 100 smallest pairs) is built from a seed as a pencil with a
known spectrum, solved through ``repro.core.solve`` with the KE variant on
the inverse pair (B, A), and checked against that spectrum and the
Table-3 bars. Phases print as they start and end, with their seconds and
the XLA compile seconds spent inside them.

Exits non-zero, and prints no result, when JAX finds no TPU, when the
repository's ``src`` is missing, or when any check fails. The last line
of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import accuracy_report, solve  # noqa: E402
from repro.core.looped import looped  # noqa: E402
from repro.data.problems import md_like, paper_shapes  # noqa: E402
from repro.launch.runtime import enable_compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", True)

SEED = 9997
# the settings at which KE converges on the MD inverse pair: tol=1e-9 and a
# Lanczos block of 4 (the block/s-step Lanczos of core.lanczos): each
# block step is one pass over C for four vectors
TOL = 1e-9
KRYLOV_BLOCK = 4
MAX_RESTARTS = 300
# the Table-3 bars of tests/test_accuracy_harness.py, and the same bar on
# the largest eigenvalue error, normalized as Table 3 normalizes the
# residual: by max(||A||_F, ||B||_F). (Relative to the wanted eigenvalues
# themselves — 1e-2 against ||A||_F ~ 2.3e3 — the bar would ask the f64
# products that build the pencil for more than f64 holds: a v5e run
# measured 1.7e-8 that way, 8e-14 this way.)
BARS = {"relative_residual": 1e-12, "b_orthogonality": 1e-12,
        "eval_error": 1e-12}


class CompileLog:
    """Backend compile seconds per program name, attributed to the open
    phase (a ``jax.monitoring`` listener)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.phase = "set-up"
        self.events: list = []          # (phase, program, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.events.append((self.phase, str(kw.get("fun_name", "?")),
                                float(duration)))

    def seconds(self, phase: str) -> float:
        return sum(d for p, _, d in self.events if p == phase)

    def by_program(self, phase: str) -> dict:
        out: dict = defaultdict(float)
        for p, name, d in self.events:
            if p == phase:
                out[name] += d
        return {k: round(v, 3) for k, v in
                sorted(out.items(), key=lambda kv: -kv[1])}


def _is_tpu(device) -> bool:
    return device.platform == "tpu"


def _log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Prints start and end, with the wall and compile seconds inside."""

    def __init__(self, name: str, log: CompileLog):
        self.name, self.log = name, log

    def __enter__(self):
        self.log.phase = self.name
        self.t0 = time.perf_counter()
        _log(f"[{self.name}] start")
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self.t0
        status = "failed" if exc[0] is not None else "done"
        _log(f"[{self.name}] {status} in {seconds:.3f} s "
             f"(compile {self.log.seconds(self.name):.3f} s)")
        return False


def stage_paths(n: int) -> dict:
    """Which implementation each stage of the f64 local KE solve runs
    (``use_kernel=False``, so no Pallas kernel is on this path)."""
    dense = "looped" if looped(n) else "xla"
    return {"GS1": f"xla:{dense}", "GS2": f"xla:{dense}",
            "KE_iter": "xla:matmul_tiled" if looped(n) else "xla",
            "BT1": f"xla:{dense}"}


def check(name: str, ok: bool, detail: str, failures: list) -> None:
    _log(f"  check {name}: {'ok' if ok else 'FAIL'} ({detail})")
    if not ok:
        failures.append(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL mesh (2x2 on four chips): run the "
                         "distributed KE solve and nothing else")
    args = ap.parse_args(argv)
    log = CompileLog()
    failures: list = []

    with Phase("device", log):
        devs = jax.devices()
        d0 = devs[0]
        _log(f"  devices: {devs}")
        _log(f"  platform={d0.platform} kind={d0.device_kind} "
             f"count={len(devs)}")
        if not _is_tpu(d0):
            _log("  no TPU: this smoke runs on the chip only")
            return 2
        _log(f"  compile cache: {enable_compile_cache()}")
        mesh = None
        if args.mesh:
            from repro.dist.partitioning import make_mesh
            dims = tuple(int(x) for x in args.mesh.lower().split("x"))
            if int(np.prod(dims)) != len(devs):
                _log(f"  --mesh {args.mesh} needs {int(np.prod(dims))} "
                     f"devices, found {len(devs)}")
                return 2
            mesh = make_mesh(dims, ("data", "model"))
            _log(f"  mesh: {dict(mesh.shape)}")

    shape = paper_shapes()["md"]
    n, s = shape["n"], shape["s"]
    with Phase("pencil", log) as ph:
        prob = md_like(n, key=jax.random.PRNGKey(SEED))
        A, B = jax.block_until_ready((prob.A, prob.B))
        exact = np.asarray(prob.exact_evals)[:s]
        _log(f"  n={n} s={s} build_s={time.perf_counter() - ph.t0:.3f}")
        _log(f"  compile by program: {log.by_program('pencil')}")

    with Phase("solve", log):
        res = solve(A, B, s, variant="KE", invert=True, tol=TOL,
                    krylov_block=KRYLOV_BLOCK, max_restarts=MAX_RESTARTS,
                    mesh=mesh)
        jax.block_until_ready(res.X)
        info = res.info
        _log(f"  stage_times_s: "
             f"{ {k: round(v, 3) for k, v in res.stage_times.items()} }")
        _log(f"  n_restart={info.get('n_restart')} "
             f"n_matvec={info.get('n_matvec')} "
             f"converged={info.get('converged')}")
        _log(f"  compile by program: {log.by_program('solve')}")
        if mesh is None:
            _log(f"  paths: {stage_paths(n)}")
        else:
            _log(f"  distributed: p={info.get('p')} "
                 f"restart program={info.get('restart_program')}")

    with Phase("check", log):
        lam = np.asarray(res.evals)
        scale = max(float(jnp.linalg.norm(A)), float(jnp.linalg.norm(B)))
        err = float(np.max(np.abs(lam - exact))) / scale
        acc = accuracy_report(A, B, res.X, res.evals)
        rr = float(acc.relative_residual)
        bo = float(acc.b_orthogonality)
        check("eval_error", err <= BARS["eval_error"],
              f"max |lambda - exact| / max(||A||_F, ||B||_F) = {err:.3e} "
              f"<= {BARS['eval_error']:g}", failures)
        check("relative_residual", rr <= BARS["relative_residual"],
              f"{rr:.3e} <= {BARS['relative_residual']:g}", failures)
        check("b_orthogonality", bo <= BARS["b_orthogonality"],
              f"{bo:.3e} <= {BARS['b_orthogonality']:g}", failures)
        check("converged", not info.get("warnings"),
              f"warnings={info.get('warnings', [])}", failures)
        check("no_recovery", not info.get("recovery"),
              f"recovery={info.get('recovery')}", failures)
        check("healthy", bool(info["health"]["healthy"]),
              f"health={info['health']}", failures)

    if mesh is not None:
        with Phase("mesh-report", log):
            for d in devs:
                st = d.memory_stats() or {}
                _log(f"  {d}: peak_bytes_in_use="
                     f"{st.get('peak_bytes_in_use')} "
                     f"bytes_limit={st.get('bytes_limit')}")
            seen = collectives_seen(mesh, s, info["p"],
                                    **info["restart_program"])
            _log(f"  collectives in the KE restart program: {seen}")

    if failures:
        _log(f"FAILED: {failures}")
        return 1
    _log(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


def collectives_seen(mesh, s: int, p: int, n: int, m: int, keep: int,
                     which: str, dtype: str) -> dict:
    """Collective ops XLA placed in the compiled distributed KE restart
    program (each counted once, however often its loop runs)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.eigensolver import _row_spec, ke_restart_program
    prog = ke_restart_program(mesh, n, p, m, s, keep, which, dtype)
    rep = NamedSharding(mesh, P(None, None))
    wdt = jnp.float64 if dtype == "float64" else jnp.float32
    c_sharding = NamedSharding(mesh, P(_row_spec(mesh), "model"))
    args = (jax.ShapeDtypeStruct((n, n), jnp.dtype(dtype),
                                 sharding=c_sharding),
            jax.ShapeDtypeStruct((n, m + p), wdt, sharding=rep),
            jax.ShapeDtypeStruct((m + p, m + p), wdt, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.asarray(0).dtype),
            jax.ShapeDtypeStruct((), wdt))
    text = prog.lower(*args).compile().as_text()
    ops = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute", "all-to-all")
    return {op: text.count(f" {op}(") + text.count(f" {op}-start(")
            for op in ops}


if __name__ == "__main__":
    sys.exit(main())
