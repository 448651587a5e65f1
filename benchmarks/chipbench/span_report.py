#!/usr/bin/env python3
"""Device idle by the program's own spans, in one traced solve of a cell.

    python3 benchmarks/chipbench/span_report.py --workload md-ke.2x2 \\
        --seed 7

Sets the cell up as ``run.py`` does (its pencils, its warm-up), traces
one solve of the cell's call at host tracer level 1 inside the window
span, and prints the trace's event counts, the idle by span table of
``spans.py`` and, last, one JSON line: the cell's per-layer metrics and
the span metrics below, each read by its ``metrics/<name>.py``, with the
seconds of each phase.

The traced solve is checked against its pencil as ``run.py`` checks a
window's (``correct`` in the JSON line). The span metrics read what
``run.py`` does not hand its readers: each solve record's ``counters``
(the program's ``info["counters"]``) and ``trace["spans"]``
(``spans.reduce`` of the trace, every host line kept); this script
hands them both. It goes once ``run.py`` and ``loops/closed.py`` hand
them too. Off the chip it exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import run, spans, trace_reduce  # noqa: E402

SPAN_METRICS = ("host_syncs", "solve_compiles", "stage_idle_s.gs",
                "stage_idle_s.ke_iter", "ke_host_eigh_s")


def report(name: str, seed: int, cell: tuple, bench: dict, devices: list,
           root: Path = HERE, t_start: float = T_START) -> dict:
    """Set up, trace one solve and read the metrics of the cell ``name``
    (``run.load_cell``'s tuple) on ``devices``."""
    import jax

    wl, cfg, traffic, loop = cell
    if str(run.SRC) not in sys.path:
        sys.path.insert(0, str(run.SRC))
    from repro.core import solve

    _, per_layer = run.cell_metrics(bench, name)
    names = [m["name"] for m in per_layer]
    names += [n for n in SPAN_METRICS if n not in names]
    readers = {n: run.load_module("metrics", n, root) for n in names}
    peaks = run.load_json(root / "peaks.json")["devices"]
    call = dict(wl["entry"], which=cfg["which"])
    if cfg.get("mesh"):
        from repro.dist.partitioning import make_mesh
        call["mesh"] = make_mesh(cfg["mesh"]["shape"], cfg["mesh"]["axes"],
                                 devices=devices)
    gen = run.load_module("generators", cfg["generator"], root)
    pencils = loop.build(gen, cfg, traffic, jax.random.PRNGKey(seed))
    loop.warm_up(pencils, cfg["s"], call, wl["warmup"])
    phases = {"setup_s": time.perf_counter() - t_start}

    trace_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        t0 = time.perf_counter()
        res = solve(pencils[0].A, pencils[0].B, cfg["s"], **call)
        jax.block_until_ready((res.evals, res.X))
        phases["solve_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    phases["stop_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    xplane = trace_reduce.find_xplane(trace_dir)
    planes = trace_reduce.load(xplane)
    host = spans.load_host(xplane)
    shutil.rmtree(trace_dir, ignore_errors=True)
    phases["read_s"] = time.perf_counter() - t0
    events = trace_reduce.summary(planes)
    t0 = time.perf_counter()
    reduced = trace_reduce.reduce(planes)
    phases["reduce_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    reduced["spans"] = spans.reduce(dict(planes, **host))
    phases["spans_s"] = time.perf_counter() - t0
    del planes, host
    print(f"trace events: {json.dumps(events)}", flush=True)
    print(spans.table(reduced["spans"]), flush=True)

    rec = dict(loop._record(0, phases["solve_s"], res),
               counters=res.info.get("counters"))
    record = {"config": cfg, "workload": wl, "solves": [rec],
              "peaks": peaks.get(devices[0].device_kind, {})}
    metrics = {n: r.read(record, reduced) for n, r in readers.items()}
    checks, failed = run._check([(0, res.evals, res.X)], [rec], [], pencils,
                                cfg)
    phases["total_s"] = time.perf_counter() - t_start
    return {"correct": failed == 0, "checks": checks,
            "metrics": metrics, "solve": rec, "phases": phases,
            "events": events, "window_s": reduced["window_s"],
            "busy_s": reduced["busy_s"],
            "breakdown": {"device_ops": reduced["device_ops"],
                          "idle_gaps": reduced["idle_gaps"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.load_cell(args.workload)
    import jax
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    chips = cell[1]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chips, JAX found {devices}",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = report(args.workload, args.seed, cell, bench, devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
