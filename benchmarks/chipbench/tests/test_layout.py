"""BENCHMARK.json and the files it names: every cell, configuration,
generator and metric is a file of its own, found by its name; a new one
needs only new files and entries; and the harness refuses to run off
the chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import run, trace_reduce
from chipbench.tests.conftest import HERE, REPO, benchmark, tiny_cell
from chipbench.tests.drive import drive

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_benchmark_json_shape():
    bench = benchmark()
    assert set(bench) == KEYS
    assert bench["command"] == ["python3", "benchmarks/chipbench/run.py"]
    assert bench["paths"] == ["benchmarks/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["traffic"] for w in bench["workloads"]]
    for c in bench["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names), names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_file_under_paths_is_named_from_name_characters():
    for path in HERE.rglob("*"):
        rel = path.relative_to(REPO).as_posix()
        if "__pycache__" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_cells_name_existing_configs_generators_and_metrics():
    bench = benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        assert c["file"] == f"benchmarks/chipbench/configs/{c['name']}.json"
    for w in bench["workloads"]:
        wl, cfg, traffic, loop = run.load_cell(w["name"])
        assert wl["config"] == w["config"] and w["config"] in configs
        assert wl["traffic"] == w["traffic"]
        assert loop.build and loop.warm_up and loop.window
        assert traffic["pencils"] >= 1
        assert cfg["chips"] == w["chips"]
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert run.load_module("generators", cfg["generator"]).build
        assert set(cfg["guarantee"]) == {"relative_residual",
                                         "b_orthogonality", "eval_error"}
    for m in bench["per_layer"]:
        assert run.load_module("metrics", m["name"]).read
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for cell in cells:
        e2e, per_layer = run.cell_metrics(bench, cell)
        assert {"setup_s", "solve_s"} <= {m["name"] for m in e2e}
        assert per_layer


def test_a_new_cell_and_metric_need_only_new_files(tmp_path, monkeypatch):
    """A dummy cell, configuration and per-layer metric, added as files
    and entries alone, are found by name and reported."""
    bench = tiny_cell(tmp_path, "dummy-ke", "md-ke")
    (tmp_path / "metrics" / "dummy.restarts.py").write_text(
        "def read(record, trace):\n"
        "    return sum(s['n_restart'] for s in record['solves'])\n")
    bench["per_layer"].append({
        "name": "dummy.restarts", "unit": "restarts", "better": "lower",
        "source": "program_counter", "layer": "Krylov solver",
        "moves": "solve_s", "workloads": ["dummy-ke"]})
    # no device plane on a CPU host: stand in a reduced trace
    monkeypatch.setattr(trace_reduce, "load", lambda path: {})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "summary", lambda planes: {})
    monkeypatch.setattr(trace_reduce, "reduce", lambda planes: {
        "window_s": 1.0, "busy_s": 0.5, "device_ops": [], "idle_gaps": [],
        "gaps": {"count": 0, "longest_s": 0.0},
        "devices": {0: {"busy_s": 0.5, "idle_share": 0.5,
                        "collective_s": 0.0, "modules": {}, "ops": {}}}})
    res = drive(tmp_path, bench, "dummy-ke", trace=True)
    assert res["correct"], res
    assert res["metrics"]["dummy.restarts"]["value"] >= 1
    assert {"stage_s.gs", "stage_s.ke_iter", "ke_block_steps",
            "device_idle", "dummy.restarts"} <= set(res["metrics"])
    # the roofline finds no segment in this trace and is left out
    assert "ke_segment.hbm_roofline" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] == 1.0


def _run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chipbench/run.py", *args],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


ARGS = ("--workload", "md-ke", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_exits_nonzero_without_a_tpu():
    proc = _run_cli(REPO, *ARGS)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "correct" not in proc.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


@pytest.mark.parametrize("name", ["../configs/imod-md", "a/b", ""])
def test_a_cell_name_cannot_leave_its_directory(name):
    with pytest.raises(ValueError):
        run.load_cell(name)


def test_a_loop_the_harness_lacks_is_refused(tmp_path):
    """A traffic file whose loop has no ``loops/<loop>.py`` is refused
    before a run starts, not run as another loop."""
    tiny_cell(tmp_path, "open-ke", "md-ke")
    (tmp_path / "traffic" / "open.poisson.json").write_text(
        json.dumps({"loop": "open", "pencils": 2}))
    wl = json.loads((tmp_path / "workloads" / "open-ke.json").read_text())
    wl["traffic"] = "open.poisson"
    (tmp_path / "workloads" / "open-ke.json").write_text(json.dumps(wl))
    with pytest.raises(FileNotFoundError):
        run.load_cell("open-ke", tmp_path)


def test_a_new_traffic_mix_and_loop_need_only_new_files(tmp_path):
    """A traffic file and the loop it names, added as files alone, drive
    the window: here a loop that solves each pencil once."""
    bench = tiny_cell(tmp_path, "once-ke", "md-ke")
    closed = (HERE / "loops" / "closed.py").read_text()
    (tmp_path / "loops" / "once.py").write_text(closed + (
        "\n\n_closed_window = window\n\n\n"
        "def window(pencils, s, call, seconds, trace, report):\n"
        "    return _closed_window(pencils, s, call, 0.0, True, report)\n"))
    (tmp_path / "traffic" / "once.3-pencils.json").write_text(
        json.dumps({"loop": "once", "pencils": 3}))
    wl = json.loads((tmp_path / "workloads" / "once-ke.json").read_text())
    wl["traffic"] = "once.3-pencils"
    (tmp_path / "workloads" / "once-ke.json").write_text(json.dumps(wl))
    res = drive(tmp_path, bench, "once-ke", seconds=60.0)
    assert res["correct"], res
    assert res["attempted"] == 1


def test_peaks_name_their_source_and_the_v5e():
    peaks = json.loads((HERE / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
