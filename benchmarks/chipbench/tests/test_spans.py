"""The program's spans in a trace (``spans.py``) on a small synthetic
trace whose answers are known, the span metrics' readers, and
``span_report.py`` on the host's CPU."""
from collections import namedtuple

import pytest

from chipbench import run, spans, trace_reduce
from chipbench.tests.conftest import tiny_cell

Event = namedtuple("Event", "name start_ns duration_ns")

MS = 1e6  # ns


def _ev(name, a, b):
    return Event(name, a * MS, (b - a) * MS)


def _trace():
    """A 100 ms window holding one solve (5-95 ms): GS1 10-20, GS2 20-30,
    KE_iter 30-80 (two restarts, each with its sync), BT1 85-90; three
    host eigh calls on two callback threads. Chip 0 is busy 0-12, 15-25
    (two overlapping ops), 32-50, 56-76 and 86-90; chip 1 is busy but
    for 20-30."""
    main = [_ev("chipbench.window", 0, 100), _ev("repro.solve", 5, 95),
            _ev("$gsyeig.py solve", 5, 95),
            _ev("repro.GS1", 10, 20), _ev("repro.GS2", 20, 30),
            _ev("repro.KE_iter", 30, 80),
            _ev("repro.ke.restart", 30, 50), _ev("repro.ke.sync", 50, 55),
            _ev("repro.ke.restart", 55, 75), _ev("repro.ke.sync", 75, 80),
            _ev("repro.BT1", 85, 90)]
    host = {"python": main,
            "callback-1": [_ev("repro.ke.host_eigh", 40, 45),
                           _ev("repro.ke.host_eigh", 60, 63)],
            "callback-2": [_ev("repro.ke.host_eigh", 41, 44)]}
    fusion = "%fusion.1 = f64[8]{0} fusion(...)"
    dev0 = {"XLA Modules": [_ev("jit_ke_restart(1)", 0, 100)],
            "XLA Ops": [_ev(fusion, 0, 12), _ev(fusion, 15, 22),
                        _ev(fusion, 18, 25), _ev(fusion, 32, 50),
                        _ev(fusion, 56, 76), _ev(fusion, 86, 90)]}
    dev1 = {"XLA Modules": [_ev("jit_ke_restart(1)", 0, 100)],
            "XLA Ops": [_ev(fusion, 0, 20), _ev(fusion, 30, 100)]}
    planes = {"/host:CPU": host, "/device:TPU:0": dev0,
              "/device:TPU:1": dev1}
    return {p: {ln: trace_reduce.Line.of(ev) for ln, ev in lines.items()}
            for p, lines in planes.items()}


@pytest.fixture(scope="module")
def reduced():
    planes = _trace()
    red = trace_reduce.reduce(planes)
    red["spans"] = spans.reduce(planes)
    return red


def test_idle_inside_each_span_per_chip(reduced):
    table = reduced["spans"]["spans"]
    idle = {n: row["idle_s"] for n, row in table.items()}
    assert idle["repro.GS1"] == pytest.approx({0: 0.003, 1: 0.0})
    assert idle["repro.GS2"] == pytest.approx({0: 0.005, 1: 0.010})
    assert idle["repro.KE_iter"] == pytest.approx({0: 0.012, 1: 0.0})
    assert idle["repro.BT1"] == pytest.approx({0: 0.001, 1: 0.0})
    # nested spans: the two syncs inside KE_iter, 5 + 4 ms idle on chip 0
    assert idle["repro.ke.sync"] == pytest.approx({0: 0.009, 1: 0.0})
    # overlapping spans on two threads count their union once
    assert idle["repro.ke.host_eigh"] == pytest.approx({0: 0.0, 1: 0.0})
    assert table["repro.ke.sync"]["count"] == 2
    assert table["repro.solve"]["host_s"] == pytest.approx(0.09)
    # 5 + 3 + 3 ms of host eigh calls, of which 8 ms of wall time
    assert table["repro.ke.host_eigh"]["host_s"] == pytest.approx(0.011)
    assert table["repro.ke.host_eigh"]["wall_s"] == pytest.approx(0.008)
    assert reduced["spans"]["solves"] == 1


def test_idle_by_stage_sums_to_the_chips_idle(reduced):
    for k, share in ((0, 0.36), (1, 0.10)):
        by = reduced["spans"]["by_stage"][k]
        assert set(by["rows"]) == {
            "repro.GS1", "repro.GS2", "repro.KE_iter", "repro.BT1",
            spans.OUTSIDE_CHILDREN, spans.OUTSIDE_SOLVE}
        assert sum(by["rows"].values()) == pytest.approx(by["idle_s"])
        # the same idle as the trace's own reduction
        assert by["idle_s"] == pytest.approx(
            reduced["devices"][k]["idle_share"] * reduced["window_s"])
        assert by["idle_s"] == pytest.approx(share * 0.1)
    rows = reduced["spans"]["by_stage"][0]["rows"]
    assert rows[spans.OUTSIDE_CHILDREN] == pytest.approx(0.010)
    assert rows[spans.OUTSIDE_SOLVE] == pytest.approx(0.005)
    text = spans.table(reduced["spans"])
    assert "repro.ke.sync" in text and spans.OUTSIDE_SOLVE in text


def test_span_metrics(reduced):
    read = {n: run.load_module("metrics", n).read
            for n in ("stage_idle_s.gs", "stage_idle_s.ke_iter",
                      "ke_host_eigh_s")}
    record = {"solves": [{"n_matvec": 40, "p": 4}]}
    # the largest over chips of GS1 + GS2 idle: chip 1's 10 ms
    assert read["stage_idle_s.gs"](record, reduced) == pytest.approx(0.010)
    assert read["stage_idle_s.ke_iter"](record, reduced) == pytest.approx(
        0.012)
    # the union of the calls: the one inside another counts once
    assert read["ke_host_eigh_s"](record, reduced) == pytest.approx(0.008)


def test_counter_metrics():
    solves = [{"counters": {"host_syncs": 23, "dispatches": 30,
                            "compiles": 2}},
              {"counters": {"host_syncs": 25, "dispatches": 30,
                            "compiles": 0}}]
    syncs = run.load_module("metrics", "host_syncs").read
    compiles = run.load_module("metrics", "solve_compiles").read
    assert syncs({"solves": solves}, None) == 24
    assert compiles({"solves": solves}, None) == 1
    # a program that counts nothing has nothing to read
    for read in (syncs, compiles):
        assert read({"solves": [{"counters": None}, {}]}, None) is None


@pytest.mark.parametrize("name", ["stage_idle_s.gs", "stage_idle_s.ke_iter",
                                  "ke_host_eigh_s"])
def test_span_readers_without_spans_read_nothing(name):
    read = run.load_module("metrics", name).read
    record = {"solves": [{"n_matvec": 4, "p": 4}]}
    assert read(record, None) is None
    assert read(record, {"devices": {}}) is None
    assert read(record, {"spans": None}) is None
    empty = {"solves": 1, "spans": {}, "by_stage": {}}
    assert read(record, {"spans": empty}) is None


def test_a_program_without_spans_reduces_to_none():
    """The parent of this change opens no ``repro.*`` span: nothing to
    read, and no error."""
    planes = _trace()
    host = {"python": [_ev("chipbench.window", 0, 100),
                       _ev("$gsyeig.py solve", 5, 95)]}
    planes["/host:CPU"] = {ln: trace_reduce.Line.of(ev)
                           for ln, ev in host.items()}
    assert spans.reduce(planes) is None
    assert spans.reduce({}) is None
    assert "no repro.solve" in spans.table(None)


def test_span_report_on_the_host(tmp_path, monkeypatch, capsys):
    """One traced solve of a tiny cell on the host's CPU: the host spans
    of the real trace reduce (no device plane here, so the trace's own
    reduction is stood in), and the counters reach their readers."""
    import jax

    from chipbench import span_report
    bench = tiny_cell(tmp_path, "tiny-ke", "md-ke")
    monkeypatch.setattr(trace_reduce, "reduce", lambda planes: {
        "window_s": 1.0, "busy_s": 0.5, "device_ops": [], "idle_gaps": [],
        "devices": {0: {"busy_s": 0.5, "idle_share": 0.5,
                        "collective_s": 0.0, "modules": {}, "ops": {}}}})
    cell = run.load_cell("tiny-ke", tmp_path)
    out = span_report.report("tiny-ke", 5, cell, bench, jax.devices()[:1],
                             root=tmp_path)
    n_restart = out["solve"]["n_restart"]
    assert out["correct"], out["checks"]
    assert out["metrics"]["host_syncs"] == n_restart + 8
    assert out["metrics"]["solve_compiles"] >= 0
    assert out["metrics"]["stage_s.ke_iter"] > 0
    assert out["events"]
    text = capsys.readouterr().out
    assert "repro.ke.sync" in text and "repro.KE_iter" in text
