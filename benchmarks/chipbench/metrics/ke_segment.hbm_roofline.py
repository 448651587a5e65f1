"""Share of the HBM roofline of the local Krylov segment program
(``jit(_lanczos_segment)``): each block step reads C, n^2 elements, at
least once, so the least time is steps * n^2 * itemsize over the chip's
peak bandwidth; the time is that program's device time in the trace.
The matvec is bound by bytes (p=4 columns per pass over C), and the
count is fixed by the pencil whatever implements the step."""
import numpy as np

SEGMENT = "_lanczos_segment"


def read(record, trace):
    if trace is None:
        return None
    seconds = sum(sec for dev in trace["devices"].values()
                  for name, sec in dev["modules"].items() if SEGMENT in name)
    steps = sum(s["n_matvec"] / s["p"] for s in record["solves"]
                if s.get("n_matvec") and s.get("p"))
    if seconds <= 0 or steps <= 0:
        return None
    n = record["config"]["n"]
    least = (steps * n * n * np.dtype(record["config"]["dtype"]).itemsize
             / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
