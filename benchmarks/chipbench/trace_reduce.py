"""From the profiler's trace to device busy time, module and collective
time, and the idle gaps with what the host was doing in them.

A trace is held as ``{plane name: {line name: Line}}``, times in
nanoseconds on the profiler's one clock; ``Line.of`` takes any events
with ``name``, ``start_ns`` and ``duration_ns``, so a test can build a
trace by hand. ``load`` reads the ``.xplane.pb`` that ``jax.profiler``
writes. On a TPU each chip is a plane ``/device:TPU:<k>`` whose
``XLA Ops`` line holds one event per operation run (an op that holds a
loop or a branch spans the ops it runs, so ops nest) and whose
``XLA Modules`` line holds one event per compiled program run; the host
threads are lines of ``/host:*`` planes (Python frames on ``python*``).

Everything is measured inside the window: the span of the host event
the benchmark names (``WINDOW``), which it opens around its timed loop.
A trace of a whole window holds millions of op events, so each line is
kept as arrays, with every distinct name once.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import NamedTuple

import numpy as np

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
#: a device trace that stops this share of the window before its end
#: has lost events: the profiler drops whole buffers once its trace
#: nears 2 GB
LOST_TAIL = 0.05
#: how many of the longest idle gaps are attributed to a host activity
GAPS_LABELLED = 512
TOP = 10


class Line(NamedTuple):
    names: list          # distinct event names
    ids: np.ndarray      # each event's index into names
    starts: np.ndarray   # ns
    ends: np.ndarray     # ns

    @classmethod
    def of(cls, events) -> "Line":
        index: dict = {}
        ids, starts, durs = [], [], []
        for e in events:
            name = e.name
            i = index.get(name)
            if i is None:
                i = index[name] = len(index)
            ids.append(i)
            starts.append(e.start_ns)
            durs.append(e.duration_ns)
        s = np.asarray(starts, np.float64)
        return cls(list(index), np.asarray(ids, np.int64), s,
                   s + np.asarray(durs, np.float64))

    def __len__(self) -> int:
        return len(self.ids)


def find_xplane(log_dir) -> Path:
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path) -> dict:
    """The trace at ``path`` as ``{plane: {line: Line}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    return {plane.name: {line.name: Line.of(line.events)
                         for line in plane.lines}
            for plane in data.planes}


def summary(planes: dict) -> dict:
    """Event counts per plane and line: what a trace holds, at a glance."""
    return {p: {ln: len(ev) for ln, ev in lines.items() if len(ev)}
            for p, lines in planes.items() if any(map(len, lines.values()))}


def union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint, sorted (starts, ends) covering the same time."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    seg_end = np.append(reach[idx[1:] - 1], reach[-1])
    return s[idx], seg_end


def clip(starts, ends, t0: float, t1: float):
    s, e = np.maximum(starts, t0), np.minimum(ends, t1)
    keep = e > s
    return s[keep], e[keep]


def window(planes: dict, name: str = WINDOW):
    """(t0, t1) of the host event ``name``."""
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for line in lines.values():
            if name in line.names:
                k = int(np.flatnonzero(line.ids == line.names.index(name))[0])
                return float(line.starts[k]), float(line.ends[k])
    raise ValueError(f"the trace holds no host event {name!r}")


def module_name(name: str) -> str:
    """A program's name without the run id the trace appends."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """An op's HLO name from the instruction text the trace holds
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _leaves(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Which events hold no other event of their line."""
    order = np.lexsort((starts - ends, starts))    # by start, outer first
    leaf = np.ones(len(starts), bool)
    s, e = starts[order], ends[order]
    leaf[order[:-1]] = s[1:] >= e[:-1]
    return leaf


def _sum_by(keys: list, ids: np.ndarray, seconds: np.ndarray) -> dict:
    totals = np.bincount(ids, weights=seconds, minlength=len(keys))
    out: dict = {}
    for k, v in zip(keys, totals):
        if v > 0:
            out[k] = out.get(k, 0.0) + float(v)
    return out


def _device(lines: dict, t0: float, t1: float) -> dict:
    empty = Line([], np.zeros(0, np.int64), np.zeros(0), np.zeros(0))
    ops = lines.get(OPS_LINE, empty)
    mods = lines.get(MODULES_LINE, empty)
    window_s = (t1 - t0) * 1e-9
    busy_s, busy_e = union(*clip(ops.starts, ops.ends, t0, t1))
    m_sec = (np.minimum(mods.ends, t1) - np.maximum(mods.starts, t0)) * 1e-9
    m_names = [module_name(n) for n in mods.names]
    modules = _sum_by(m_names, mods.ids, np.maximum(m_sec, 0))
    # each op's program: the module run that holds its start
    order = np.argsort(mods.starts, kind="stable")
    m_s, m_e = mods.starts[order], mods.ends[order]
    h = np.searchsorted(m_s, ops.starts, side="right") - 1
    held = (h >= 0) & (ops.starts < m_e[np.maximum(h, 0)])
    prog = np.where(held, mods.ids[order][np.maximum(h, 0)], -1)
    o_sec = np.maximum(
        (np.minimum(ops.ends, t1) - np.maximum(ops.starts, t0)) * 1e-9, 0)
    # the longest ops by (program, op name), leaves only: a loop's op
    # spans the ops it runs
    leaf = _leaves(ops.starts, ops.ends) & (o_sec > 0)
    width = len(ops.names) + 1
    pairs, pair_ids = np.unique((prog[leaf] + 1) * width + ops.ids[leaf],
                                return_inverse=True)
    by_op = _sum_by(
        [f"{m_names[p - 1] if p else '?'}/{op_name(ops.names[o])}"
         for p, o in zip(pairs // width, pairs % width)],
        pair_ids.reshape(-1), o_sec[leaf])
    coll_name = np.array([bool(COLLECTIVE.search(op_name(n)))
                          for n in ops.names] or [False])
    coll = coll_name[ops.ids] if len(ops) else np.zeros(0, bool)
    col_s, col_e = union(*clip(ops.starts[coll], ops.ends[coll], t0, t1))
    busy = float(np.sum(busy_e - busy_s)) * 1e-9
    return {"busy_s": busy, "idle_share": 1.0 - busy / window_s,
            "collective_s": float(np.sum(col_e - col_s)) * 1e-9,
            "modules": modules, "ops": by_op, "_busy": (busy_s, busy_e),
            "_last": float(ops.ends.max()) if len(ops) else t0}


def _host(planes: dict, exclude: str) -> tuple:
    names, starts, ends = [], [], []
    for p, lines in planes.items():
        if not p.startswith("/host:"):
            continue
        for line in lines.values():
            keep = line.ends > line.starts
            names.extend(line.names[i] for i in line.ids[keep])
            starts.append(line.starts[keep])
            ends.append(line.ends[keep])
    s = np.concatenate(starts) if starts else np.zeros(0)
    e = np.concatenate(ends) if ends else np.zeros(0)
    keep = np.array([n != exclude for n in names], bool)
    return [n for n, k in zip(names, keep) if k], s[keep], e[keep]


def label_gap(names, hs, he, a: float, b: float) -> str:
    """What the host was doing in the gap [a, b): the shortest host event
    that spans at least half of it, else the one that spans most."""
    over = np.minimum(he, b) - np.maximum(hs, a)
    live = over > 0
    if not live.any():
        return "(no host event)"
    half = live & (over >= 0.5 * (b - a))
    if half.any():
        idx = np.flatnonzero(half)
        return names[idx[np.argmin((he - hs)[idx])]]
    return names[int(np.argmax(np.where(live, over, -1)))]


def _top(d: dict) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(planes: dict, window_name: str = WINDOW) -> dict:
    """Busy, idle, module and collective time per device inside the
    window, the device operations that took most time, and the longest
    idle gaps by what the host was doing (the device with the largest
    idle share). Raises when the trace holds no device."""
    t0, t1 = window(planes, window_name)
    devices = {}
    for pname, lines in planes.items():
        m = DEVICE_PLANE.match(pname)
        if m:
            devices[int(m.group(1))] = _device(lines, t0, t1)
    if not devices:
        raise ValueError(f"the trace holds no device plane: "
                         f"{sorted(planes)}")
    for k, d in devices.items():
        if t1 - d["_last"] > max(LOST_TAIL * (t1 - t0), 1e9):
            raise ValueError(
                f"device {k}'s trace ends {(t1 - d['_last']) * 1e-9} s "
                f"before the window does: the profiler dropped events (it "
                f"keeps its trace under 2 GB, ~6 million op events of a "
                f"v5e); trace a shorter window")
    ops: dict = {}
    for d in devices.values():
        for name, sec in d["ops"].items():
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
    idlest = max(devices, key=lambda k: devices[k]["idle_share"])
    bs, be = devices[idlest]["_busy"]
    gap_s = np.concatenate([[t0], be])
    gap_e = np.concatenate([bs, [t1]])
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    longest = np.argsort(gap_s - gap_e, kind="stable")[:GAPS_LABELLED]
    names, hs, he = _host(planes, window_name)
    gaps: dict = {}
    for i in longest:
        label = label_gap(names, hs, he, gap_s[i], gap_e[i])
        gaps[label] = gaps.get(label, 0.0) + (gap_e[i] - gap_s[i]) * 1e-9
    rest = np.ones(len(gap_s), bool)
    rest[longest] = False
    if rest.any():
        gaps["(shorter gaps)"] = float(
            np.sum(gap_e[rest] - gap_s[rest])) * 1e-9
    for d in devices.values():
        del d["_busy"], d["_last"]
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": float(np.mean([d["busy_s"] for d in devices.values()])),
            "devices": devices, "device_ops": _top(ops),
            "idle_gaps": _top(gaps),
            "gaps": {"count": int(len(gap_s)),
                     "longest_s": float(np.max(gap_e - gap_s)) * 1e-9
                     if len(gap_s) else 0.0}}
