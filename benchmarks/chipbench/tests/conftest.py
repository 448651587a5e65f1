"""Fixtures of the benchmark's CPU tests: a tiny cell laid out as a
later change would add one, in a directory of its own."""
import json
import shutil
from pathlib import Path

import jax
import pytest

jax.config.update("jax_enable_x64", True)

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
#: the tiny cell's size: the paper's shapes are for the chip
TINY = {"n": 300, "s": 10}


def benchmark() -> dict:
    with open(REPO / "BENCHMARK.json") as f:
        return json.load(f)


def tiny_cell(root: Path, cell: str, like: str, **config) -> dict:
    """Write ``workloads/<cell>.json`` and its configuration
    ``configs/<cell>.json`` under root, copied from the cell ``like`` at
    the tiny size, and return a BENCHMARK.json in which ``cell`` reports
    what ``like`` reports."""
    for kind in ("generators", "metrics", "traffic", "loops"):
        if not (root / kind).exists():
            shutil.copytree(HERE / kind, root / kind)
    if not (root / "peaks.json").exists():
        # the host's CPU stands in for a chip in the traced CPU tests
        peaks = json.loads((HERE / "peaks.json").read_text())
        peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
        (root / "peaks.json").write_text(json.dumps(peaks))
    (root / "configs").mkdir(exist_ok=True)
    (root / "workloads").mkdir(exist_ok=True)
    with open(HERE / "workloads" / f"{like}.json") as f:
        wl = json.load(f)
    with open(HERE / "configs" / f"{wl['config']}.json") as f:
        cfg = json.load(f)
    cfg.update(TINY, **config)
    wl["config"] = cell
    (root / "configs" / f"{cell}.json").write_text(json.dumps(cfg))
    (root / "workloads" / f"{cell}.json").write_text(json.dumps(wl))
    bench = benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell)
    return bench


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("chipbench")
    return root, tiny_cell(root, "tiny-ke", "md-ke")
