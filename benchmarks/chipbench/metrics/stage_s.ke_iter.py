"""Seconds of the Krylov iteration per solve (``stage_times["KE_iter"]``:
host clock from the first segment to the last restart's result)."""


def read(record, trace):
    times = [s["stage_times"]["KE_iter"] for s in record["solves"]
             if "KE_iter" in s["stage_times"]]
    if not times:
        return None
    return sum(times) / len(times)
