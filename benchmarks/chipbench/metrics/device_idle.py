"""Share of the traced window in which no operation ran on the device:
1 - (union of op intervals) / window, the largest over the cell's
chips."""


def read(record, trace):
    if trace is None:
        return None
    return 100.0 * max(d["idle_share"] for d in trace["devices"].values())
