"""Block Lanczos steps per solve: ``n_matvec / p``, an exact count of the
passes over C that convergence took."""


def read(record, trace):
    steps = [s["n_matvec"] / s["p"] for s in record["solves"]
             if s.get("n_matvec") and s.get("p")]
    if not steps:
        return None
    return sum(steps) / len(steps)
