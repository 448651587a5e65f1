"""The program's own spans in the profiler's trace: device idle by span.

The program opens a host span ``repro.<name>`` (``core/instrument.py``)
around each solve (``repro.solve``), each of its stages (``repro.GS1``,
``repro.GS2``, ``repro.KE_iter``, ``repro.BT1``, ``repro.finalize``, ...)
and each step of its Krylov loop (``repro.ke.*``), on the clock of the
device planes. ``reduce`` takes a trace as ``trace_reduce`` holds it
and gives, inside the window:

* for each span name, its count, host seconds summed over its
  intervals (``host_s``) and over their union (``wall_s``: spans that
  overlap on several threads count once), and the device idle seconds
  inside them, per chip;
* the idle per chip split over the stages (the spans whose parent on
  their host thread is ``repro.solve``), the part of ``repro.solve``
  outside any of them, and the part of the window outside any solve:
  rows that sum to the chip's idle in the window.

A trace without a ``repro.solve`` span inside the window (a program
that opens none) reduces to ``None``. ``trace_reduce.load`` keeps one
line per name, and a TPU host runs its callbacks (the restarts' host
``eigh``) on several threads of one name: ``load_host`` reads every
host line of the file, for ``reduce`` to see them all.
"""
from __future__ import annotations

import numpy as np

from chipbench import trace_reduce

PREFIX = "repro."
SOLVE = "repro.solve"
OUTSIDE_CHILDREN = "repro.solve outside any child"
OUTSIDE_SOLVE = "outside repro.solve"


def load_host(path) -> dict:
    """The host planes of the trace at ``path``, each of its lines kept
    (a line's key is its name and its index in the plane)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    return {plane.name: {f"{line.name}#{i}": trace_reduce.Line.of(
                line.events) for i, line in enumerate(plane.lines)}
            for plane in data.planes if plane.name.startswith("/host:")}


def _host_spans(planes: dict, t0: float, t1: float) -> list:
    """(name, start, end, parent name) of every ``repro.*`` host event
    inside the window; the parent is the innermost ``repro.*`` event of
    the same host line that holds it."""
    out = []
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for line in lines.values():
            ours = [i for i, n in enumerate(line.names)
                    if n.startswith(PREFIX)]
            if not ours:
                continue
            idx = np.flatnonzero(np.isin(line.ids, ours))
            idx = idx[(line.starts[idx] >= t0) & (line.ends[idx] <= t1)]
            order = idx[np.lexsort((-line.ends[idx], line.starts[idx]))]
            stack: list = []
            for k in order:
                s, e = float(line.starts[k]), float(line.ends[k])
                while stack and stack[-1][2] <= s:
                    stack.pop()
                name = line.names[line.ids[k]]
                out.append((name, s, e, stack[-1][0] if stack else None))
                stack.append((name, s, e))
    return out


def _busy(lines: dict, t0: float, t1: float) -> tuple:
    """A chip's busy time as disjoint sorted intervals inside the window,
    with the busy seconds before each interval's start."""
    ops = lines.get(trace_reduce.OPS_LINE)
    if ops is None:
        return np.zeros(0), np.zeros(0), np.zeros(1)
    bs, be = trace_reduce.union(*trace_reduce.clip(ops.starts, ops.ends,
                                                   t0, t1))
    return bs, be, np.concatenate([[0.0], np.cumsum(be - bs)])


def _busy_before(busy: tuple, t: np.ndarray) -> np.ndarray:
    """Busy nanoseconds of the chip before each time in ``t``."""
    bs, be, cum = busy
    if not len(bs):
        return np.zeros(len(t))
    k = np.searchsorted(bs, t, side="right")
    j = np.maximum(k - 1, 0)
    part = np.clip(t - bs[j], 0.0, be[j] - bs[j])
    return np.where(k > 0, cum[j] + part, 0.0)


def idle_inside(busy: tuple, starts, ends) -> float:
    """Idle seconds of a chip inside the union of the intervals."""
    s, e = trace_reduce.union(np.asarray(starts, np.float64),
                              np.asarray(ends, np.float64))
    if not len(s):
        return 0.0
    busy_ns = _busy_before(busy, e) - _busy_before(busy, s)
    return float(np.sum(e - s) - np.sum(busy_ns)) * 1e-9


def reduce(planes: dict, window_name: str = trace_reduce.WINDOW):
    """The spans of the program inside the window (module docstring), or
    None where the trace holds no window or no ``repro.solve``."""
    try:
        t0, t1 = trace_reduce.window(planes, window_name)
    except ValueError:
        return None
    spans = _host_spans(planes, t0, t1)
    solves = [(s, e) for n, s, e, _ in spans if n == SOLVE]
    if not solves:
        return None
    chips = {int(m.group(1)): lines for p, lines in planes.items()
             if (m := trace_reduce.DEVICE_PLANE.match(p))}
    busy = {k: _busy(lines, t0, t1) for k, lines in chips.items()}
    by_name: dict = {}
    for name, s, e, _ in spans:
        by_name.setdefault(name, []).append((s, e))
    table = {}
    for name, iv in sorted(by_name.items()):
        s, e = np.array(iv).T
        us, ue = trace_reduce.union(s, e)
        table[name] = {
            "count": len(iv), "host_s": float(np.sum(e - s)) * 1e-9,
            "wall_s": float(np.sum(ue - us)) * 1e-9,
            "idle_s": {k: idle_inside(b, s, e) for k, b in busy.items()}}
    stages = sorted({n for n, _, _, parent in spans if parent == SOLVE})
    by_stage = {}
    for k, b in busy.items():
        rows = {n: table[n]["idle_s"][k] for n in stages}
        child = [iv for n in stages for iv in by_name[n]]
        in_children = (idle_inside(b, *np.array(child).T) if child
                       else 0.0)
        in_solve = table[SOLVE]["idle_s"][k]
        rows[OUTSIDE_CHILDREN] = in_solve - in_children
        window_idle = idle_inside(b, [t0], [t1])
        rows[OUTSIDE_SOLVE] = window_idle - in_solve
        by_stage[k] = {"rows": rows, "idle_s": window_idle}
    return {"solves": len(solves), "spans": table, "by_stage": by_stage}


def table(reduced) -> str:
    """The idle by span as text: each span name with its count, host
    and wall seconds and idle seconds per chip, then the rows that sum
    to each chip's idle in the window."""
    if reduced is None:
        return "idle by span: the trace holds no repro.solve span"
    chips = sorted(reduced["by_stage"])
    head = "".join(f"  idle chip {k} s" for k in chips)
    out = [f"idle by span ({reduced['solves']} solves):",
           f"{'span':40s} {'count':>6s} {'host s':>12s} {'wall s':>12s}"
           f"{head}"]
    for name, row in reduced["spans"].items():
        idle = "".join(f"{row['idle_s'][k]:16.6f}" for k in chips)
        out.append(f"{name:40s} {row['count']:6d} {row['host_s']:12.6f} "
                   f"{row['wall_s']:12.6f}{idle}")
    out.append("idle by stage, summing to each chip's idle:")
    by_stage = [reduced["by_stage"][k] for k in chips]
    names = list(by_stage[0]["rows"]) if chips else []
    rows = [(n, [b["rows"][n] for b in by_stage]) for n in names]
    rows.append(("(sum)", [sum(b["rows"].values()) for b in by_stage]))
    rows.append(("(the chip's idle in the window)",
                 [b["idle_s"] for b in by_stage]))
    for name, values in rows:
        cells = "".join(f"{v:16.6f}" for v in values)
        out.append(f"{name:40s} {'':6s} {'':12s} {'':12s}{cells}")
    return "\n".join(out)
