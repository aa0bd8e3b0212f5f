"""Structured tracing, EXPLAIN, and the determinism contract on traces (PR 9).

Four claims under test:

  * the tracer itself: span nesting reconstructs under an 8-thread hammer,
    and the disabled fast path allocates nothing (shared null-span
    singleton, ``live() is None``, zero events);
  * the counter view is bit-identical serial vs ``n_workers=4`` — clean
    runs AND runs under fault injection (the schedule decides who executes
    a split, never what the trace's deterministic events say);
  * ``explain`` predicts the exact prune counters a real scan then reports,
    while decoding zero bytes itself;
  * Chrome export reconciles: the sum of ``split.stats`` counter events
    equals the job's final ``ScanStats``, field for field.
"""
import dataclasses
import json
import threading

import numpy as np
import pytest

from repro.core import (
    CIFReader, COFWriter, ColumnFormat, FailurePolicy, FaultPlan, Histogram,
    Placement, ScanStats, col, explain, fig1_map_batch, fig1_reduce,
    fig1_where, format_job_report, run_job, urlinfo_schema,
)
from repro.core import trace

from conftest import make_crawl_records, profiled_host_events

T0 = 1300000000
POLICY = FailurePolicy(max_attempts=4, max_reexecutions=2, seed=0)


@pytest.fixture(scope="module")
def crawl(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("crawl-trace") / "d")
    w = COFWriter(root, urlinfo_schema(),
                  formats={"metadata": ColumnFormat("dcsl"),
                           "url": ColumnFormat("skiplist"),
                           "content": ColumnFormat("cblock", codec="zlib")},
                  split_records=256)
    w.append_all(make_crawl_records(2000))
    w.close()
    return root


# -- the tracer itself ---------------------------------------------------------


def test_span_nesting_under_thread_hammer():
    tr = trace.Tracer()
    DEPTH, REPS, THREADS = 5, 40, 8

    def nest(d):
        if d < DEPTH:
            with tr.span(f"lvl{d}"):
                nest(d + 1)

    def hammer(tid):
        for r in range(REPS):
            with tr.span("outer", {"tid": tid, "r": r}):
                nest(0)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    depths = tr.span_depths()
    assert len(depths) == THREADS * REPS * (DEPTH + 1)
    # spans close inner-first, so lvl_d must sit at depth d+1 on ITS thread
    # regardless of interleaving with the other 7 threads
    for _tid, name, depth in depths:
        if name == "outer":
            assert depth == 0
        else:
            assert depth == int(name[3:]) + 1
    # every hammer iteration completed one full outer+nested stack (thread
    # idents may be reused as threads retire, so count stacks, not tids)
    assert sum(1 for _t, n, _d in depths if n == "outer") == THREADS * REPS


def test_disabled_tracer_is_free():
    assert trace.live() is None  # default: disabled singleton installed
    tr = trace.active()
    assert not tr.enabled
    # span() hands back ONE shared object — no allocation per call
    s1, s2 = tr.span("a"), tr.span("b", {"x": 1})
    assert s1 is s2
    with s1:
        pass
    tr.instant("i", {"x": 1})
    tr.counter("c", {"n": 2})
    tr.complete("x", 0, 10)
    assert tr.events() == []


def test_tracing_scope_installs_and_restores():
    assert trace.live() is None
    with trace.tracing() as tr:
        assert trace.live() is tr and tr.enabled
        tr.instant("hello", {"k": "v"})
    assert trace.live() is None
    assert [e[1] for e in tr.events()] == ["hello"]


def test_counter_view_drops_timing_and_sched():
    with trace.tracing() as tr:
        tr.instant("det.ev", {"split": 1})
        tr.instant("det.ev", {"split": 1})
        tr.instant("who.claimed", {"host": 2}, cat="sched")
        tr.counter("stats", {"n": 3})
    view = json.loads(tr.counter_view())
    assert {(r["name"], r["count"]) for r in view} == {
        ("det.ev", 2), ("stats", 1)
    }
    assert all("ts" not in r and "tid" not in r for r in view)


def test_histogram_matches_numpy_percentiles(rnd):
    xs = [rnd.random() * 10 for _ in range(257)]
    h = Histogram()
    for x in xs[:100]:
        h.record(x)
    h.merge(Histogram(xs[100:]))
    assert h.count == len(xs)
    assert h.p50 == float(np.percentile(xs, 50))
    assert h.p99 == float(np.percentile(xs, 99))
    assert h.mean() == pytest.approx(float(np.mean(xs)))
    assert Histogram().p99 == 0.0 and Histogram().mean() == 0.0
    assert "p99" in h.summary(scale=1e3, unit="ms")


# -- traced jobs: determinism + reconciliation --------------------------------


def _job(root, n_workers, plan=None, policy=None):
    """The fig1 where-job; returns (result, the reader's ScanStats)."""
    p = Placement(8, 4)
    r = CIFReader(root, columns=["url", "metadata"],
                  fault_plan=plan, failure_policy=policy)
    ids, ob = r.job_inputs(batch_size=512, where=fig1_where(), placement=p)
    res = run_job(ids, reduce_fn=fig1_reduce, n_hosts=4, placement=p,
                  open_split_batches=ob, map_batch_fn=fig1_map_batch(),
                  n_workers=n_workers, fault_plan=plan,
                  failure_policy=policy, scan_stats=r.stats)
    return res, r.stats


def _traced_job(root, n_workers, plan=None, policy=None):
    """Run the fig1 where-job under a fresh tracer; readers MUST be
    constructed inside the tracing scope (they capture the tracer)."""
    with trace.tracing() as tr:
        res, stats = _job(root, n_workers, plan, policy)
    return tr, res, stats


def test_counter_view_bit_identical_serial_vs_concurrent(crawl):
    tr1, res1, st1 = _traced_job(crawl, 1)
    tr4, res4, st4 = _traced_job(crawl, 4)
    assert res1.output == res4.output
    assert tr1.counter_view() == tr4.counter_view()
    # and the sched-excluded events really were present (claims happened)
    assert any(e[6] == "sched" for e in tr4.events())


JOB_SPANS = {"job.plan", "job.map", "job.shuffle", "job.reduce", "split",
             "storage.read", "colfile.verify"}


def test_counter_view_covers_the_storage_spans(crawl):
    """The serial-vs-concurrent identity holds with the file reads and CRC
    verifies in the view, each with its column or byte count."""
    tr1, _res1, _ = _traced_job(crawl, 1)
    tr4, _res4, _ = _traced_job(crawl, 4)
    assert JOB_SPANS <= {e[1] for e in tr1.events() if e[0] == "X"}
    assert tr1.counter_view() == tr4.counter_view()
    view = json.loads(tr1.counter_view())
    reads = [json.loads(r["args"]) for r in view if r["name"] == "storage.read"]
    assert {a["column"] for a in reads} == {"url", "metadata"}
    assert all(a["bytes"] > 0 for a in reads)


def test_job_spans_reach_the_profile_without_a_tracer(crawl, tmp_path):
    assert trace.live() is None
    (res, _), events = profiled_host_events(tmp_path, lambda: _job(crawl, 2))
    assert JOB_SPANS <= {e["name"] for e in events}
    assert res.output == _traced_job(crawl, 1)[1].output


def test_counter_view_bit_identical_under_faults(crawl):
    p = Placement(8, 4)
    plan = FaultPlan(
        corrupt_blocks=frozenset({(p.primary(1), 1, "url", 0)}),
        io_errors=frozenset({(p.primary(2), 2, "url")}),
    )
    tr1, res1, st1 = _traced_job(crawl, 1, plan, POLICY)
    tr4, res4, st4 = _traced_job(crawl, 4, plan, POLICY)
    clean_tr, clean_res, _ = _traced_job(crawl, 1)
    assert res1.output == res4.output == clean_res.output
    assert tr1.counter_view() == tr4.counter_view()
    # the failure ladder showed up in the deterministic view: fetch
    # attempts beyond the first, and the repair enqueue for the bad copy
    names1 = {e[1] for e in tr1.events()}
    assert "repair.enqueue" in names1
    assert tr1.counter_view() != clean_tr.counter_view()


def _sum_counter_events(tr):
    tot = {}
    for ph, _name, _ts, _dur, _tid, args, _cat, _depth in tr.events():
        if ph != "C":
            continue
        for k, v in args.items():
            if k != "split" and isinstance(v, int):
                tot[k] = tot.get(k, 0) + v
    return tot


def test_counter_events_reconcile_with_scan_stats(crawl):
    for n_workers in (1, 4):
        tr, _res, stats = _traced_job(crawl, n_workers)
        tot = _sum_counter_events(tr)
        for f in dataclasses.fields(ScanStats):
            v = getattr(stats, f.name)
            if isinstance(v, int):
                assert tot.get(f.name, 0) == v, f.name


def test_chrome_export_is_loadable(crawl, tmp_path):
    tr, _res, _stats = _traced_job(crawl, 2)
    path = str(tmp_path / "trace.json")
    tr.export_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert evs and doc["displayTimeUnit"] == "ms"
    phases = {"X", "i", "C"}
    for e in evs:
        assert e["ph"] in phases
        assert isinstance(e["ts"], int) and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # the phase spans all made it out
    names = {e["name"] for e in evs}
    assert {"job.plan", "job.map", "job.shuffle", "job.reduce",
            "split", "split.stats"} <= names


def test_phase_times_and_job_report(crawl):
    _tr, res, stats = _traced_job(crawl, 2)
    pt = res.phase_times
    assert pt is not None and pt.total > 0
    assert pt.plan >= 0 and pt.map_wall > 0
    assert pt.plan + pt.map_wall + pt.shuffle + pt.reduce <= pt.total * 1.01
    rep = format_job_report(res, stats)
    assert "plan" in rep and "reduce" in rep and "bytes_decoded" in rep


# -- explain vs the real scan's counters --------------------------------------

EXPLAIN_CASES = [
    f"fetchTime < {T0 + 120}",           # sorted ints: zone-map prunes
    "url contains ibm.com/jp",           # dict strings: value-set prunes
    f"fetchTime < {T0}",                 # matches nothing: all pruned
    f"fetchTime >= {T0}",                # matches everything: none pruned
]


@pytest.mark.parametrize("text", EXPLAIN_CASES)
def test_explain_matches_scan_counters(crawl, text):
    rep = explain(crawl, text, columns=["url", "fetchTime"])
    r = CIFReader(crawl, columns=["url", "fetchTime"])
    rows = 0
    from repro.core import parse_predicate
    for b in r.scan_batches(batch_size=512, where=parse_predicate(text)):
        rows += len(next(iter(b.values())))
    assert rep.blocks_pruned == r.stats.blocks_pruned_stats
    assert rep.candidate_rows >= rows  # candidates only ever over-approximate
    assert rep.splits_total == len(rep.splits)
    # attribution totals account for exactly the pruned blocks
    assert sum(rep.source_totals().values()) == rep.blocks_pruned
    # and the report renders + names the zero-decode invariant
    txt = rep.format()
    assert "bytes_decoded=0" in txt and "EXPLAIN" in txt


def test_explain_decodes_nothing(crawl):
    before = ScanStats()
    rep = explain(crawl, f"fetchTime < {T0 + 120}", columns=["url"])
    assert rep.stats.bytes_decoded == 0 and rep.stats.cells_decoded == 0
    # a second explain is idempotent — prune attribution moves no counters
    rep2 = explain(crawl, f"fetchTime < {T0 + 120}", columns=["url"])
    assert rep2.blocks_pruned == rep.blocks_pruned
    assert rep2.source_totals() == rep.source_totals()
    assert before == ScanStats()  # sanity: nothing global mutated
