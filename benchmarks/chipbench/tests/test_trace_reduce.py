"""The trace reduction on a small synthetic trace whose answers are
known, and the roofline's byte count."""
import pytest

from collections import namedtuple

from chipbench import run, trace_reduce

Event = namedtuple("Event", "name start_ns duration_ns")

MS = 1e6  # ns


def _trace():
    """A 100 ms window; device 0 busy 10-30 and 25-50 (overlapping ops)
    and 70-80 ms, device 1 busy 0-90 ms with a 20 ms all-gather; the
    host is in ``host_eigh`` from 50 to 70 ms and in ``solve`` all
    window long."""
    host = {"python": [
        Event("chipbench.window", 0, 100 * MS),
        Event("$gsyeig.py solve", 0, 100 * MS),
        Event("$looped.py host_eigh", 50 * MS, 20 * MS)]}
    dev0 = {
        "XLA Modules": [Event("jit__lanczos_segment(3)", 10 * MS, 40 * MS),
                        Event("jit_matmul", 70 * MS, 10 * MS)],
        "XLA Ops": [Event("%fusion.1 = f64[8]{0} fusion(...)", 10 * MS,
                          20 * MS),
                    Event("%fusion.2 = f64[8]{0} fusion(...)", 25 * MS,
                          25 * MS),
                    Event("%while.4 = (s32[]) while(...)", 70 * MS, 10 * MS),
                    Event("%dot.3 = f64[8]{0} dot(...)", 70 * MS, 6 * MS),
                    Event("%dot.3 = f64[8]{0} dot(...)", 77 * MS, 3 * MS)]}
    dev1 = {
        "XLA Modules": [Event("jit_prog", -10 * MS, 100 * MS)],
        "XLA Ops": [Event("fusion.1", -10 * MS, 70 * MS),
                    Event("all-gather.7", 60 * MS, 20 * MS),
                    Event("fusion.9", 80 * MS, 10 * MS)]}
    planes = {"/host:CPU": host, "/device:TPU:0": dev0, "/device:TPU:1": dev1,
              "/device:TPU_NON_CORE:0": {"XLA Ops": [Event("x", 0, 1e9)]}}
    return {p: {ln: trace_reduce.Line.of(ev) for ln, ev in lines.items()}
            for p, lines in planes.items()}


def test_busy_union_and_idle_share():
    red = trace_reduce.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.1)
    d0, d1 = red["devices"][0], red["devices"][1]
    assert sorted(red["devices"]) == [0, 1]
    assert d0["busy_s"] == pytest.approx(0.05)      # 10-50 and 70-80 ms
    assert d0["idle_share"] == pytest.approx(0.5)
    assert d1["busy_s"] == pytest.approx(0.09)      # clipped at 0
    assert red["busy_s"] == pytest.approx(0.07)     # mean over chips


def test_module_time_by_name():
    d0 = trace_reduce.reduce(_trace())["devices"][0]
    assert d0["modules"] == pytest.approx(
        {"jit__lanczos_segment": 0.04, "jit_matmul": 0.01})


def test_collective_share():
    red = trace_reduce.reduce(_trace())
    assert red["devices"][0]["collective_s"] == 0
    assert red["devices"][1]["collective_s"] == pytest.approx(0.02)
    mod = run.load_module("metrics", "collective_share")
    assert mod.read({}, red) == pytest.approx(20.0)
    idle = run.load_module("metrics", "device_idle")
    assert idle.read({}, red) == pytest.approx(50.0)


def test_idle_gaps_by_host_activity():
    red = trace_reduce.reduce(_trace())
    gaps = dict(red["idle_gaps"])
    # device 0 idles 0-10 (solve), 50-70 (host_eigh) and 80-100 (solve)
    assert gaps == pytest.approx({"$looped.py host_eigh": 0.02,
                                  "$gsyeig.py solve": 0.03})
    ops = dict(red["device_ops"])
    assert ops["jit__lanczos_segment/fusion.2"] == pytest.approx(0.025 / 2)
    assert ops["jit_prog/all-gather.7"] == pytest.approx(0.02 / 2)
    # the loop is not a leaf; the ops it ran are, summed by name
    assert ops["jit_matmul/dot.3"] == pytest.approx(0.009 / 2)
    assert not any("while" in k for k in ops)


def test_union_of_nested_and_disjoint_intervals():
    import numpy as np
    s, e = trace_reduce.union(np.array([5., 0., 1., 20.]),
                              np.array([6., 10., 2., 30.]))
    assert list(s) == [0., 20.] and list(e) == [10., 30.]


def test_no_device_or_window_is_an_error():
    tr = _trace()
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce({"/host:CPU": tr["/host:CPU"]})
    assert trace_reduce.summary(tr)["/device:TPU:0"] == {"XLA Modules": 2,
                                                        "XLA Ops": 5}
    with pytest.raises(ValueError, match="no host event"):
        trace_reduce.reduce(tr, window_name="elsewhere")


def test_a_trace_that_lost_its_tail_is_refused():
    """Past ~6 million op events the profiler drops whole buffers, and
    the device seems idle to the window's end: no number from it."""
    tr = _trace()
    host = {"python": [Event("chipbench.window", 0, 10_000 * MS)]}
    tr["/host:CPU"] = {ln: trace_reduce.Line.of(ev)
                       for ln, ev in host.items()}
    with pytest.raises(ValueError, match="dropped events"):
        trace_reduce.reduce(tr)


def test_roofline_byte_count():
    """steps x n^2 x 8 bytes over 819 GB/s, against the segment's time."""
    mod = run.load_module("metrics", "ke_segment.hbm_roofline")
    n, steps, seconds = 9997, 233, 14.0
    record = {"config": {"n": n, "dtype": "float64"},
              "peaks": {"hbm_bytes_per_s": 819e9},
              "solves": [{"n_matvec": 4 * steps, "p": 4}]}
    trace = {"devices": {0: {"modules": {"jit__lanczos_segment": seconds,
                                         "jit_other": 3.0}}}}
    least = steps * n * n * 8 / 819e9
    assert mod.read(record, trace) == pytest.approx(100 * least / seconds)
    assert 1.0 < mod.read(record, trace) < 2.0
    # a trace without the segment has nothing to read: no number, not 0
    assert mod.read(record, {"devices": {0: {"modules": {}}}}) is None
    assert mod.read(record, None) is None
