"""Static program audit CLI: prove every solver path has the shape we claim.

    PYTHONPATH=src python -m repro.launch.audit            # full audit
    PYTHONPATH=src python -m repro.launch.audit --quick    # CI fast lane
    PYTHONPATH=src python -m repro.launch.audit --entry dist/tt3_program

Lowers (never runs) every registered solver program — the fused TT1 panel
sweep, the bulge chase, the batched TT3, the distributed KE restart /
Chebyshev prep / spectrum-partitioned TT3 programs, the shape-bucketed
``solve_batched`` pipelines and every Pallas kernel wrapper — walks the
jaxpr/StableHLO into ProgramProfiles, and enforces the budget contracts
of ``analysis.static_audit.contracts``: dispatch counts, collectives per
block step, pinned static collective totals, loop-step structure, dtype
policy (no fp64->fp32/bf16 leaks), plus the Pallas BlockSpec/VMEM lint
and the StageCost cross-check against ``analysis.variant_model``.

Writes ``artifacts/AUDIT.json`` and exits nonzero on any budget, dtype
or cross-check violation (warnings don't fail). Defaults to 2 forced
host devices so the distributed contracts are audited with real
collectives; ``--devices 1`` skips the mesh entries. A CPU tool: it
lowers programs and never runs them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

from repro.analysis.static_audit import (
    AuditSpec, all_ok, check_all, check_entry, crosscheck_stagecosts,
    entries, errors, get_entry, lint_pallas_profiles, lint_reports,
    lint_signature_parity, register_all)
from repro.launch.runtime import force_host_devices

jax.config.update("jax_enable_x64", True)


def run_audit(quick: bool = False, entry: str | None = None,
              spec: AuditSpec | None = None) -> dict:
    """Audit everything (or one entry); returns the AUDIT.json payload."""
    spec = register_all(spec)
    have_mesh = jax.device_count() >= 2
    if entry:
        reports = [check_entry(get_entry(entry))]
    else:
        tags = ["quick"] if quick else None
        reports = check_all(tags=tags, have_mesh=have_mesh)
    rd = {r.name: r for r in reports}

    checks = crosscheck_stagecosts(rd, spec) if entry is None else []
    pallas = lint_pallas_profiles(rd)
    sigs = lint_signature_parity() if entry is None else []
    dtypes = lint_reports(rd)

    n_viol = sum(len(r.violations) for r in reports)
    n_lint_err = len(errors(pallas)) + len(errors(sigs))
    n_xfail = sum(1 for c in checks if not c.ok)
    ok = (n_viol == 0 and n_lint_err == 0 and n_xfail == 0
          and dtypes["ok"])
    # the resilience proof in one place: every sentinel-bearing contract
    # holds with ZERO extra dispatches (the allowance is pinned to 0), and
    # the required fused is_finite sites are present in the lowered traces
    sentinel_entries = [r for r in reports
                        if not r.skipped and r.contract.min_isfinite_sites]
    sentinels = {
        "entries": len(sentinel_entries),
        "isfinite_sites": sum(r.isfinite_sites for r in sentinel_entries),
        "extra_dispatches_allowed": max(
            (r.contract.sentinel_extra_dispatches
             for r in sentinel_entries), default=0),
        "ok": all(r.ok for r in sentinel_entries),
    }
    return {
        "schema": "repro/static-audit/v1",
        "jax_version": jax.__version__,
        "n_devices": jax.device_count(),
        "spec": spec.as_json_dict(),
        "ok": ok,
        "summary": {
            "entries": len(reports),
            "skipped": sum(1 for r in reports if r.skipped),
            "budget_violations": n_viol,
            "crosscheck_failures": n_xfail,
            "lint_errors": n_lint_err,
            "lint_warnings": (len(pallas) + len(sigs) - n_lint_err),
            "precision_leaks": len(dtypes["precision_leaks"]),
        },
        "sentinels": sentinels,
        "entries": [r.as_json_dict() for r in reports],
        "crosscheck": [c.as_json_dict() for c in checks],
        "pallas_lint": [f.as_json_dict() for f in pallas],
        "signature_lint": [f.as_json_dict() for f in sigs],
        "dtype_lint": dtypes,
    }


def _print_human(payload: dict) -> None:
    print(f"static audit: {payload['summary']['entries']} entries on "
          f"{payload['n_devices']} device(s), jax {payload['jax_version']}")
    for e in payload["entries"]:
        if e["skipped"]:
            print(f"  SKIP {e['name']} (needs a >= 2 device mesh)")
            continue
        mark = "ok  " if e["ok"] else "FAIL"
        print(f"  {mark} {e['name']}: {e['dispatches']} dispatch(es), "
              f"{e['total_collectives']} collective(s), "
              f"<= {e['max_collectives_per_step']}/step")
        for v in e["violations"]:
            print(f"       !! {v}")
    if payload["crosscheck"]:
        print("cost-model cross-check (StageCost vs counted):")
        for c in payload["crosscheck"]:
            mark = "ok  " if c["ok"] else "FAIL"
            print(f"  {mark} {c['stage']}.{c['field']}: model "
                  f"{c['model_value']:g} vs counted {c['counted_value']:g} "
                  f"({c['relation']})")
    for f in payload["pallas_lint"] + payload["signature_lint"]:
        tag = "!!" if f["severity"] == "error" else "--"
        print(f"  {tag} [{f['check']}] {f['kernel']}: {f['detail']}")
    leaks = payload["dtype_lint"]["precision_leaks"]
    for leak in leaks:
        print(f"  !! precision leak: {leak}")
    sen = payload.get("sentinels")
    if sen:
        mark = "ok  " if sen["ok"] else "FAIL"
        print(f"  {mark} health sentinels: {sen['isfinite_sites']} fused "
              f"is_finite site(s) across {sen['entries']} contract(s), "
              f"+{sen['extra_dispatches_allowed']} dispatches allowed")
    print("AUDIT " + ("PASSED" if payload["ok"] else "FAILED"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="static HLO/jaxpr budget audit of every solver path")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced host devices (>=2 audits the mesh "
                         "contracts; CPU only)")
    ap.add_argument("--quick", action="store_true",
                    help="only the 'quick'-tagged entries (CI fast lane)")
    ap.add_argument("--entry", default=None,
                    help="audit a single registry entry by name")
    ap.add_argument("--json", action="store_true",
                    help="print the payload as JSON instead of a summary")
    ap.add_argument("-o", "--out", default="artifacts/AUDIT.json",
                    help="artifact path ('' disables writing)")
    args = ap.parse_args(argv)
    force_host_devices(args.devices)

    payload = run_audit(quick=args.quick, entry=args.entry)
    if args.out and args.entry is None:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
    if args.json:
        print(json.dumps(payload, indent=1, sort_keys=True))
    else:
        _print_human(payload)
    return 0 if payload["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
