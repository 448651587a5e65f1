"""Seconds of the standard-form stages per solve: GS1 (Cholesky of B)
plus GS2 (C = U^-T A U^-1), from the program's own ``stage_times``
(host clock around each stage's ``block_until_ready``)."""


def read(record, trace):
    times = [s["stage_times"] for s in record["solves"]
             if "GS1" in s["stage_times"]]
    if not times:
        return None
    return sum(t["GS1"] + t.get("GS2", 0.0) for t in times) / len(times)
