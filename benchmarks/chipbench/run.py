#!/usr/bin/env python3
"""Chip benchmark of the eigensolver: one cell, one process.

    python3 benchmarks/chipbench/run.py --workload md-ke --seed 7 \\
        --seconds 50 --trace 0

A cell is ``workloads/<name>.json``; it names its configuration,
``configs/<config>.json``, whose generator is ``generators/<gen>.py``,
and its traffic, ``traffic/<traffic>.json``, whose ``loop`` is
``loops/<loop>.py``: the loop builds the traffic's inputs, warms up and
drives the window through the program's entry. The metrics a cell
reports are the entries of the repository's ``BENCHMARK.json`` that
list it; each per-layer metric is read by ``metrics/<metric>.py``.
Nothing here knows a cell, a traffic mix or an entry by name.

A run:

1. finds the chips (no TPU, or fewer chips than the cell asks for: exit
   non-zero, no result);
2. keeps JAX's compile cache at ``<checkout>/.jax_cache``;
3. builds the cell's inputs on the device from ``PRNGKey(seed)``, as
   its loop says;
4. warms up: one solve of the cell's call, capped as ``warmup`` says, so
   every program of the timed solve is compiled or loaded (set-up ends
   here, ``setup_s`` counting from process start);
5. measures for ``--seconds``, as the loop says; with ``--trace 1`` the
   profiler traces the window, which the loop may shorten;
6. checks every solve of the window against its pencil
   (``reference.py``), and prints each number compared beside its limit
   as the last lines of standard error;
7. prints one JSON line last on standard output: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the end-to-end ones with
   ``--trace 0``; with ``--trace 1`` the per-layer ones, read from the
   profiler's trace of the window and the program's stage times and
   counters), ``device``, ``breakdown`` (``--trace 1``) and ``checks``.

``--control`` runs the cell's ``control`` arguments on top of its call:
the program's own lower-precision path, which the check has to refuse.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
#: a fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))


def _out(msg: str) -> None:
    print(msg, flush=True)


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def load_cell(name: str, root: Path = HERE) -> tuple:
    """(workload, config, traffic, loop module) of the cell ``name``."""
    wl = load_json(root / "workloads" / f"{_checked(name)}.json")
    cfg = load_json(root / "configs" / f"{_checked(wl['config'])}.json")
    traffic = load_json(root / "traffic" / f"{_checked(wl['traffic'])}.json")
    return wl, cfg, traffic, load_module("loops", traffic["loop"], root)


def load_module(kind: str, name: str, root: Path = HERE):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / kind / f"{_checked(name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str) -> tuple:
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that the
    cell reports: an end-to-end metric with no ``workloads`` is every
    cell's; a per-layer one lists its cells."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    per_layer = [m for m in bench["per_layer"] if cell in m["workloads"]]
    return e2e, per_layer


def _peak(devices: list) -> int:
    """The allocator's peak bytes on the fullest of ``devices`` (0 where
    the backend keeps no count)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def _finite(x):
    """A JSON-safe number: non-finite floats as their names."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def _check(outputs, solves, errors, pencils, cfg: dict) -> tuple:
    """Every solve of the window against its pencil: (checks, failed),
    checks holding each number compared, the worst over the solves,
    beside its limit."""
    from chipbench import reference

    limits = cfg["guarantee"]
    worst = {k: 0.0 for k in limits}
    failed = len(errors)
    for (i, lam, X), rec in zip(outputs, solves):
        p = pencils[i]
        acc = reference.accuracy(p.A, p.B, X, lam, p.exact_evals,
                                 cfg["which"])
        bad = [k for k, lim in limits.items() if not acc[k] <= lim]
        bad += [k for k in ("unconverged", "recovery", "unhealthy")
                if rec[k]]
        failed += bool(bad)
        for k in limits:
            if not worst[k] >= acc[k]:      # NaN sticks
                worst[k] = acc[k]
        _out(f"check solve of pencil {i}: {acc} "
             f"{'FAIL ' + str(bad) if bad else 'ok'}")
    checks = {k: {"value": worst[k], "limit": lim}
              for k, lim in limits.items()}
    for k in ("unconverged", "recovery", "unhealthy"):
        checks[k] = {"value": sum(r[k] for r in solves), "limit": 0}
    checks["raised"] = {"value": len(errors), "limit": 0}
    return checks, failed


def _read_trace(trace_dir: str, per_layer: list, readers: dict,
                record: dict) -> tuple:
    """(per-layer metrics, busy and window seconds, breakdown) from the
    profiler's trace of the window."""
    from chipbench import trace_reduce

    t_read = time.perf_counter()
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    _out(f"trace: read in {time.perf_counter() - t_read} s: "
         f"{json.dumps(trace_reduce.summary(planes))}")
    reduced = trace_reduce.reduce(planes)
    del planes
    _out(f"trace: reduced in {time.perf_counter() - t_read} s; gaps "
         f"{reduced['gaps']}; programs by device seconds: " + json.dumps(
             {k: sorted(d["modules"].items(), key=lambda kv: -kv[1])[:12]
              for k, d in reduced["devices"].items()}))
    metrics = {}
    for m in per_layer:
        value = readers[m["name"]].read(record, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return (metrics, {"busy_s": reduced["busy_s"],
                      "window_s": reduced["window_s"]},
            {"device_ops": reduced["device_ops"],
             "idle_gaps": reduced["idle_gaps"]})


def run(name: str, seed: int, seconds: float, trace: bool, cell: tuple,
        bench: dict, devices: list, root: Path = HERE,
        control: bool = False, t_start: float = T_START) -> int:
    """Set up, measure, check and report one run of the cell ``name``
    (``load_cell``'s tuple) on ``devices``; returns the exit code."""
    import jax

    from chipbench.compile_log import CompileLog
    from chipbench.trace_reduce import WINDOW

    wl, cfg, traffic, loop = cell
    jax.config.update("jax_enable_x64", True)
    log = CompileLog()
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    e2e, per_layer = cell_metrics(bench, name)
    readers = {m["name"]: load_module("metrics", m["name"], root)
               for m in per_layer}
    kind = devices[0].device_kind
    peaks = load_json(root / "peaks.json")["devices"]
    if trace and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    mesh = None
    if cfg.get("mesh"):
        from repro.dist.partitioning import make_mesh
        mesh = make_mesh(cfg["mesh"]["shape"], cfg["mesh"]["axes"],
                         devices=devices)
    gen = load_module("generators", cfg["generator"], root)
    pencils = loop.build(gen, cfg, traffic, jax.random.PRNGKey(seed))
    t_pencils = time.perf_counter() - t_start
    peak_pencils = _peak(devices)
    call = dict(wl["entry"], which=cfg["which"])
    if control:
        call.update(wl["control"])
    if mesh is not None:
        call["mesh"] = mesh
    s = cfg["s"]
    try:
        loop.warm_up(pencils, s, call, wl["warmup"])
    except Exception:  # the window's solves raise it again, and count
        _err(f"warm-up raised:\n{traceback.format_exc()}")
    setup_s = time.perf_counter() - t_start
    _out(f"set-up: {setup_s} s (pencils ready at {t_pencils} s); "
         f"{log.count('set-up')} backend compiles, "
         f"{log.seconds('set-up')} s")

    log.phase = "window"
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace_dir:
        # host events of level 1 only (annotations, Python frames): the
        # runtime's level-2 events on a 2x2 mesh are ~3 million a solve
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(WINDOW):
        outputs, solves, errors, window_s = loop.window(
            pencils, s, call, seconds, trace, _err)
    if trace_dir:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        _out(f"trace: stopped in {time.perf_counter() - t_stop} s")
    log.phase = "check"
    _out(f"window: {len(outputs)} solves, {len(errors)} raised, in "
         f"{window_s} s; backend compiles in the window: "
         f"{log.count('window')} {log.by_program('window')}")
    for rec in solves:
        _out(f"solve: {json.dumps(rec)}")
    peak = _peak(devices)
    _out(f"memory: peak {peak} B on the fullest chip, {peak_pencils} B "
         f"when the inputs were built")
    checks, failed = _check(outputs, solves, errors, pencils, cfg)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": failed == 0 and bool(outputs),
              "attempted": len(outputs) + len(errors), "failed": failed}
    if trace_dir:
        record = {"config": cfg, "workload": wl, "solves": solves,
                  "peaks": peaks[kind]}
        metrics, busy, breakdown = _read_trace(trace_dir, per_layer,
                                               readers, record)
        device.update(busy)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = {"solve_s": window_s / max(len(outputs), 1),
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        unknown = [m["name"] for m in e2e if m["name"] not in values]
        if unknown:
            raise KeyError(f"end-to-end metrics this harness does not "
                           f"measure: {unknown}")
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]} for m in e2e},
                      device=device)
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]} for k, v in checks.items()}
    for k, v in checks.items():
        ok = v["value"] <= v["limit"]
        _err(f"check {k}: {v['value']} <= {v['limit']} "
             f"{'ok' if ok else 'FAIL'}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the cell's lower-precision control, which "
                         "the check has to refuse")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        _err(f"the program under test is missing: no {SRC / 'repro'}")
        return 2
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cell = load_cell(args.workload)
    except (OSError, ValueError, KeyError) as exc:
        _err(f"cannot load cell {args.workload!r}: {exc!r}")
        return 2
    import jax
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _err(f"no TPU: JAX found {devices}; this benchmark runs on the "
             f"chip only")
        return 2
    chips = cell[1]["chips"]
    if len(devices) < chips:
        _err(f"cell {args.workload!r} needs {chips} chips, JAX "
             f"found {len(devices)}")
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _out(f"devices: {devices[:chips]}; compile cache: {CACHE_DIR}")
    return run(args.workload, args.seed, args.seconds, bool(args.trace), cell,
               bench, devices[:chips], control=args.control)


if __name__ == "__main__":
    sys.exit(main())
