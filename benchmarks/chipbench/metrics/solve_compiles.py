"""Programs lowered inside a solve, after the warm-up: each a backend
compile or a load from the persistent cache, the program's own count
(``info["counters"]["compiles"]``), mean over the solves."""


def read(record, trace):
    counts = [s["counters"]["compiles"] for s in record["solves"]
              if s.get("counters")]
    if not counts:
        return None
    return sum(counts) / len(counts)
