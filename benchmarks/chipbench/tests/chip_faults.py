#!/usr/bin/env python3
"""Read a fault planted in the timed path, on the chip at a cell's own
size: the readings that set a check's upper end.

    python3 benchmarks/chipbench/tests/chip_faults.py --workload md-ke \\
        --fault altered --seeds 101 202 303

Each seed is one run of the harness (``run.run``, one solve in the
window) with the fault planted in the program under it, as the CPU
tests in ``test_correct.py`` plant it: ``altered`` multiplies the first
eigenvalue by 1 + 1e-6 where it is produced, ``half`` returns the first
half of the wanted pairs twice. Prints one ``FAULT`` JSON line a seed.
Needs the chip; not collected by the test run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=("altered", "half"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_enable_x64", True)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: this reading is for the chip", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(run.SRC))
    from repro.core import gsyeig

    from chipbench.tests.conftest import benchmark
    from chipbench.tests.drive import drive
    from chipbench.tests.test_correct import _finalize_fault

    gsyeig._finalize = _finalize_fault(args.fault)
    chips = run.load_cell(args.workload, run.HERE)[1]["chips"]
    for seed in args.seeds:
        res = drive(run.HERE, benchmark(), args.workload, seed=seed,
                    seconds=1.0, chips=chips)
        print("FAULT " + json.dumps({
            "fault": args.fault, "seed": seed, "correct": res["correct"],
            "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
