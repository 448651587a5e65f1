"""Host seconds of the restarts' LAPACK ``eigh`` per traced solve: the
union of the program's spans ``repro.ke.host_eigh`` around each host
callback. Calls that overlap on several callback threads (on a mesh,
one per chip for each restart) count once, as the time the restarts
wait on the host."""
SPAN = "repro.ke.host_eigh"


def read(record, trace):
    spans = (trace or {}).get("spans")
    if not spans or SPAN not in spans["spans"]:
        return None
    return spans["spans"][SPAN]["wall_s"] / spans["solves"]
