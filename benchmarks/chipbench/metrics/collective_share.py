"""Share of the traced window in which a collective (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all) ran on a
chip, the largest over the cell's chips."""


def read(record, trace):
    if trace is None:
        return None
    shares = [d["collective_s"] / trace["window_s"]
              for d in trace["devices"].values()]
    if not any(shares):
        return None
    return 100.0 * max(shares)
