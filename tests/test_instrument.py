"""The solver's spans and counters (``core/instrument.py``): the profiler's
trace of a KE solve holds ``repro.solve`` with the stage spans nested in
it and one ``repro.ke.sync`` per restart; ``info["counters"]`` counts
every host wait, dispatch and lowered program of the solve."""
import glob
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import instrument, solve
from repro.data.problems import md_like

N, S = 300, 10
KE = dict(variant="KE", invert=True, tol=1e-9, krylov_block=4)
STAGES = ("GS1", "GS2", "KE_iter", "BT1", "finalize")


def _spans(log_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``repro.*`` host event."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(instrument.PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda sp: sp[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def pencil():
    return md_like(N)


@pytest.fixture(scope="module")
def traced(pencil, tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    solve(pencil.A, pencil.B, S, **KE)          # programs compiled
    with jax.profiler.trace(str(log_dir)):
        res = solve(pencil.A, pencil.B, S, **KE)
    return res, _spans(log_dir)


def test_stage_spans_nest_inside_the_solve_span(traced):
    res, spans = traced
    top = [sp for sp in spans if sp[0] == "repro.solve"]
    assert len(top) == 1
    assert top[0][3] == {"n": N, "s": S, "variant": "KE"}
    names = [sp[0] for sp in spans]
    for stage in STAGES:
        assert f"repro.{stage}" in names, names
    assert all(_inside(sp, top[0]) for sp in spans)
    ke = next(sp for sp in spans if sp[0] == "repro.KE_iter")
    inner = [sp for sp in spans if sp[0].startswith("repro.ke.")]
    assert {sp[0] for sp in inner} == {
        "repro.ke.prep", "repro.ke.segment", "repro.ke.restart",
        "repro.ke.sync", "repro.ke.ritz"}
    assert all(_inside(sp, ke) for sp in inner)
    # the stages follow one another on the calling thread
    order = [next(sp for sp in spans if sp[0] == f"repro.{st}")
             for st in STAGES]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


def test_one_sync_span_per_restart(traced):
    res, spans = traced
    syncs = [sp[3]["restart"] for sp in spans if sp[0] == "repro.ke.sync"]
    n_restart = res.info["n_restart"]
    assert syncs == list(range(n_restart))
    for kind in ("segment", "restart"):
        assert sum(sp[0] == f"repro.ke.{kind}" for sp in spans) == n_restart


def test_host_syncs_follow_the_restarts(pencil):
    """Local KE (no filter, one attempt): GS1 wait and verdict, GS2 wait
    and verdict, one verdict fetch a restart, the KE_iter wait and its
    residual bounds, the BT1 wait and the output sentinel's fetch:
    ``n_restart + 8`` host syncs; two dispatches a restart."""
    res = solve(pencil.A, pencil.B, S, **KE)
    c = res.info["counters"]
    n_restart = res.info["n_restart"]
    assert n_restart >= 2
    assert c["host_syncs"] == n_restart + 8, c
    assert c["dispatches"] == 2 * n_restart, c


def test_a_second_identical_solve_compiles_nothing(pencil):
    first = solve(pencil.A, pencil.B, S, **KE).info["counters"]
    second = solve(pencil.A, pencil.B, S, **KE).info["counters"]
    assert second["compiles"] == 0, second
    assert first["compiles"] >= second["compiles"]
    assert second["host_syncs"] == first["host_syncs"]


def test_info_with_counters_survives_json(traced):
    res, _ = traced
    info = json.loads(json.dumps(res.info))
    assert set(info["counters"]) == set(instrument.COUNTERS)
    assert all(type(v) is int for v in info["counters"].values())


def test_counters_nest_and_stay_on_their_thread():
    x = jnp.arange(4.0)
    counter = instrument.DispatchCounter()
    seen = {}

    def elsewhere():
        instrument.fetch(x)
        with instrument.counting() as c:
            instrument.wait(x)
        seen.update(c)

    with instrument.counting() as outer:
        instrument.wait(x)
        with instrument.counting() as inner:
            np.testing.assert_array_equal(instrument.fetch(x), np.arange(4.0))
            counter(lambda a: a, x)
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert inner == {"host_syncs": 1, "dispatches": 1, "compiles": 0,
                     "split_operator": 0}
    assert outer["host_syncs"] == 2 and outer["dispatches"] == 1
    assert seen["host_syncs"] == 1
    assert counter.count() == 1
    counter.reset()
    assert counter.count() == 0


def test_compiles_count_lowered_programs():
    x = jnp.arange(3.0)

    def fresh(v):
        return v * 3.0 + 1.0

    f = jax.jit(fresh)
    with instrument.counting() as c:
        f(x).block_until_ready()
        f(x).block_until_ready()
    assert c["compiles"] == 1, c


def test_the_stage_timer_adds_host_seconds_under_its_span(tmp_path):
    times = {"GS1": 1.0}
    with jax.profiler.trace(str(tmp_path)):
        with instrument.counting() as c:
            out = instrument.timed(times, "GS1", jnp.add, jnp.ones(2), 1.0)
    np.testing.assert_array_equal(out, [2.0, 2.0])
    assert times["GS1"] > 1.0
    assert c["host_syncs"] == 1
    assert [sp[0] for sp in _spans(tmp_path)] == ["repro.GS1"]


def test_host_eigh_runs_in_its_span(tmp_path, monkeypatch):
    """On emulated f64 the restart's eigh runs on the host, in the span
    ``repro.ke.host_eigh`` (here the chip's path is taken on the CPU)."""
    from repro.core import looped
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    M = jax.random.normal(jax.random.PRNGKey(0), (12, 12), jnp.float64)
    T = M + M.T
    with jax.profiler.trace(str(tmp_path)):
        w, V = looped.eigh_small(T)
        jax.block_until_ready((w, V))
    np.testing.assert_allclose(np.asarray(w), np.linalg.eigvalsh(T),
                               rtol=1e-12, atol=1e-12)
    assert [sp[0] for sp in _spans(tmp_path)] == ["repro.ke.host_eigh"]
