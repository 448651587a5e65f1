"""Device idle seconds inside the standard-form stages per traced
solve: inside the program's spans ``repro.GS1`` and ``repro.GS2``
(``chipbench/spans.py``), the largest over the cell's chips."""
STAGES = ("repro.GS1", "repro.GS2")


def read(record, trace):
    spans = (trace or {}).get("spans")
    if not spans or not any(n in spans["spans"] for n in STAGES):
        return None
    rows = [spans["spans"][n]["idle_s"] for n in STAGES
            if n in spans["spans"]]
    per_chip = [sum(r[k] for r in rows) for k in rows[0]]
    return max(per_chip) / spans["solves"] if per_chip else None
