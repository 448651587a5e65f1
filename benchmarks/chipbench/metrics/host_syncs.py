"""Host syncs per solve: the times the calling thread waited on the
device (stage waits, verdict fetches, the final fetch), the program's
own count (``info["counters"]["host_syncs"]``), mean over the solves."""


def read(record, trace):
    counts = [s["counters"]["host_syncs"] for s in record["solves"]
              if s.get("counters")]
    if not counts:
        return None
    return sum(counts) / len(counts)
