"""repro.obs.trace span records — ids, parents, call ids, start times and
the per-span compile counter — and the stage spans that ``ELSession``'s
compiled entry points open around their host work."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.el import ELSession, SweepSpec
from repro.el.cache import ProgramCache
from repro.launch.classic import classic_fixture
from repro.obs import trace as obs_trace

STAGES = ("session.prepare", "session.dispatch", "session.records",
          "session.evaluate", "session.report")


@pytest.fixture
def tracer():
    """A fresh process-wide tracer for the test; the previous one is put
    back afterwards."""
    tr = obs_trace.Tracer()
    prev = obs_trace.use_tracer(tr)
    yield tr
    obs_trace.use_tracer(prev)


def _by_name(tr):
    return {e["name"]: e for e in tr.events()}


def test_nested_spans_carry_id_parent_and_call():
    tr = obs_trace.Tracer()
    with tr.span("root"):
        with tr.span("a"):
            with tr.span("a.inner"):
                pass
        with tr.span("b"):
            pass
    with tr.span("second"):
        pass
    ev = _by_name(tr)
    ids = [e["id"] for e in tr.events()]
    assert len(set(ids)) == len(ids) == 5
    root = ev["root"]
    assert root["parent"] is None and root["call"] == root["id"]
    assert ev["a"]["parent"] == ev["b"]["parent"] == root["id"]
    assert ev["a.inner"]["parent"] == ev["a"]["id"]
    assert {ev[n]["call"] for n in ("a", "a.inner", "b")} == {root["id"]}
    # a later root starts a call of its own
    assert ev["second"]["parent"] is None
    assert ev["second"]["call"] == ev["second"]["id"] != root["id"]


def test_span_start_times_nest_inside_the_parent():
    tr = obs_trace.Tracer()
    with tr.span("outer"):
        with tr.span("first"):
            sum(range(1000))
        with tr.span("second"):
            sum(range(1000))
    ev = _by_name(tr)

    def end(e):
        return e["t0_us"] + e["dur_us"]

    outer = ev["outer"]
    for child in (ev["first"], ev["second"]):
        assert outer["t0_us"] <= child["t0_us"]
        assert end(child) <= end(outer)
    assert end(ev["first"]) <= ev["second"]["t0_us"]


def test_span_start_is_on_the_perf_counter_clock():
    import time
    tr = obs_trace.Tracer()
    before = time.perf_counter()
    with tr.span("timed"):
        pass
    after = time.perf_counter()
    rec, = tr.events()
    assert before * 1e6 <= rec["t0_us"] <= after * 1e6


def test_compiles_land_on_the_innermost_open_span(tracer):
    def fresh(x):                   # a new function: never compiled before
        return jnp.sin(x) * 3.0 + 1.0

    with obs_trace.span("outer"):
        with obs_trace.span("sibling.before"):
            pass
        with obs_trace.span("compiling"):
            jax.block_until_ready(jax.jit(fresh)(jnp.ones(7)))
        with obs_trace.span("sibling.after"):
            pass
    ev = _by_name(tracer)
    assert ev["compiling"]["compiles"] >= 1
    assert ev["compiling"]["compile_ms"] > 0
    for name in ("outer", "sibling.before", "sibling.after"):
        assert ev[name]["compiles"] == 0
        assert ev[name]["compile_ms"] == 0.0


def test_compiles_with_no_span_open_are_dropped(tracer):
    jax.block_until_ready(jax.jit(lambda x: jnp.cos(x) - 2.0)(jnp.ones(5)))
    with obs_trace.span("after"):
        pass
    assert _by_name(tracer)["after"]["compiles"] == 0


def test_program_cache_get_emits_no_event(tracer):
    cache = ProgramCache(max_entries=1)
    assert cache.get(("k",)) is None
    cache.put(("k",), "program")
    assert cache.get(("k",)) == "program"
    assert tracer.events() == []
    assert cache.stats() == {"entries": 1, "max_entries": 1, "hits": 1,
                             "misses": 1, "evictions": 0, "profiled": 0}
    cache.put(("j",), "other")               # an eviction still says so
    assert [e["name"] for e in tracer.events()] == ["cache.evict"]


# -- the session's stage spans ------------------------------------------------


@pytest.fixture(scope="module")
def svm():
    return classic_fixture("svm-wafer", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


@pytest.fixture(scope="module")
def kmeans():
    return classic_fixture("kmeans-traffic", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


def _session(fx, mode):
    cfg = dataclasses.replace(
        fx["exp"].ol4el, mode=mode, policy="ol4el", n_edges=4,
        utility=fx["utility"], budget=600.0, seed=0)
    return (ELSession(cfg, metric_name=fx["metric"])
            .with_executor(fx["executor"], init_params=fx["init_params"],
                           n_samples=(fx["n_samples"] if mode == "sync"
                                      else None)))


def _calls(tr):
    """``[(root, [children in start order]), ...]`` per ``session.call``."""
    evs = tr.events()
    out = []
    for root in (e for e in evs if e["name"] == "session.call"):
        kids = sorted((e for e in evs if e.get("parent") == root["id"]),
                      key=lambda e: e["t0_us"])
        out.append((root, kids))
    return out


ENTRY_POINTS = {
    "sync": ("svm", lambda s: s.run_sync_ingraph(max_rounds=32),
             STAGES),
    "async": ("svm", lambda s: s.run_async_ingraph(max_events=32),
              STAGES),
    # the sweep scores its cells on the report it has built
    "sweep": ("kmeans", lambda s: s.sweep(SweepSpec(
        heterogeneity=(1.0, 9.0), seeds=(0,), max_rounds=16)),
        ("session.prepare", "session.dispatch", "session.report",
         "session.evaluate")),
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_session_call_holds_its_stage_spans(entry, tracer, request):
    fixture, call, stages = ENTRY_POINTS[entry]
    session = _session(request.getfixturevalue(fixture),
                       "async" if entry == "async" else "sync")
    call(session)
    call(session)
    calls = _calls(tracer)
    assert len(calls) == 2
    for root, kids in calls:
        assert root["mode"] == entry and root["parent"] is None
        assert tuple(k["name"] for k in kids) == stages
        assert {k["call"] for k in kids} == {root["id"]}
        assert sum(k["dur_us"] for k in kids) <= root["dur_us"]
        for k in kids:
            assert root["t0_us"] <= k["t0_us"]
            assert (k["t0_us"] + k["dur_us"]
                    <= root["t0_us"] + root["dur_us"])
    (first, kids1), (_, kids2) = calls
    prep1, prep2 = kids1[0], kids2[0]
    assert (prep1["cache"], prep2["cache"]) == ("miss", "hit")
    # the first call compiles its program inside its prepare stage
    compile_span, = [e for e in tracer.events()
                     if e["name"] == "session.compile"]
    assert compile_span["parent"] == prep1["id"]
    assert compile_span["call"] == first["id"]
    n = {k["name"]: k.get("n") for k in kids2}
    if entry == "sweep":
        assert n["session.evaluate"] == 2          # one F1 per sweep cell
    else:
        assert n["session.evaluate"] == 1
        assert n["session.records"] == next(
            e for e in tracer.events() if e["name"] == "session.dispatch"
            and e["call"] == calls[1][0]["id"]).get(
                "n_rounds" if entry == "sync" else "n_events")


def _sweep_twice(session, tracer):
    """Two sweeps of one grid shape (other seeds the second time); the
    report of each and its ``session.evaluate`` span."""
    reports = [session.sweep(SweepSpec(heterogeneity=(1.0, 9.0),
                                       seeds=(seed,), max_rounds=16))
               for seed in (0, 1)]
    return reports, tracer.events("session.evaluate")[-2:]


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_sweep_scores_every_cell_in_one_compiled_call(impl, tracer):
    """The sweep's cells are scored by the executor's batched form: a
    second sweep of the same grid shape compiles nothing there."""
    fx = classic_fixture("kmeans-traffic", samples=128, n_edges=4,
                         alpha=100.0, data_seed=0, kmeans_impl=impl)
    reports, (first, second) = _sweep_twice(_session(fx, "sync"), tracer)
    for rep, span in zip(reports, (first, second)):
        assert span["n"] == span["batched"] == rep.n_cells == 2
    assert first["compiles"] >= 1
    assert second["compiles"] == 0


def test_sweep_without_a_batched_form_scores_cell_by_cell(kmeans, tracer):
    """An executor that offers no batched form still scores every cell,
    one ``evaluate`` each, to the same numbers."""
    ex = kmeans["executor"]

    class CellByCell(type(ex)):
        evaluate_many = None

    per_cell = dict(kmeans, executor=CellByCell(
        ex.model, ex.edge_data, ex.eval_set, batch=ex.batch, lr=ex.lr))
    reports, spans = _sweep_twice(_session(per_cell, "sync"), tracer)
    assert [(s["n"], s["batched"]) for s in spans] == [(2, 0), (2, 0)]
    batched, _ = _sweep_twice(_session(kmeans, "sync"), tracer)
    for rep, ref in zip(reports, batched):
        np.testing.assert_array_equal(rep.out["final_metric_host"],
                                      ref.out["final_metric_host"])


@pytest.mark.parametrize("mode", ("sync", "async"))
def test_records_are_built_from_one_host_copy(mode, svm, tracer,
                                              monkeypatch):
    """A compiled run's records and report come from one host copy of
    its ``out``: the builders see numpy leaves, one ``device_get`` a
    call, and the result equals, bit for bit, what the builders make
    from the program's device arrays."""
    from repro.el import session as el_session
    seen, copies = [], []
    build, device_get = el_session.records_from_out, jax.device_get

    def spy_records(out, lo, hi):
        seen.append(dict(out))
        return build(out, lo, hi)

    def spy_get(tree):
        copies.append(tree)
        return device_get(tree)

    monkeypatch.setattr(el_session, "records_from_out", spy_records)
    monkeypatch.setattr(jax, "device_get", spy_get)
    report = ENTRY_POINTS[mode][1](_session(svm, mode))
    (host,), (dev,) = seen, copies
    assert set(host) >= {"wall", "consumed", "metric", "utility",
                         "interval", "n_rounds", "arm_pulls"}
    assert ("edge" in host) == (mode == "async")
    for leaf in jax.tree.leaves(host):
        assert isinstance(leaf, np.ndarray)
    assert any(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(dev))

    def bits(recs):
        return np.array([dataclasses.astuple(r) for r in recs],
                        np.float64).view(np.uint64)

    ref = build(dev, 0, int(dev["n_rounds"]))
    assert len(ref) == report.n_aggregations > 0
    np.testing.assert_array_equal(bits(report.records), bits(ref))
    ref_report = el_session.report_from_out(   # horizon: ENTRY_POINTS' 32
        dev, mode=mode, policy=report.policy, horizon=32,
        final_metric=report.final_metric, final_params=None, elapsed_s=0.0)
    for k in ("n_aggregations", "total_consumed", "wall_time",
              "terminated_reason", "arm_pulls"):
        assert getattr(report, k) == getattr(ref_report, k), k
    span, = tracer.events("session.records")
    assert span["host_bytes"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(dev)) > 0
