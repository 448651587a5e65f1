"""Device idle seconds inside the Krylov iteration per traced solve:
inside the program's span ``repro.KE_iter`` (``chipbench/spans.py``),
the largest over the cell's chips."""
STAGE = "repro.KE_iter"


def read(record, trace):
    spans = (trace or {}).get("spans")
    idle = spans["spans"].get(STAGE, {}).get("idle_s") if spans else None
    if not idle:
        return None
    return max(idle.values()) / spans["solves"]
