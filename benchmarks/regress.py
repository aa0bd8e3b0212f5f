"""regress — ScanStats record/replay perf regression gate (PR 9).

Fixed-size deterministic workloads replay against counter baselines
committed in ``BENCH_baseline.json``.  Because every ``ScanStats`` integer
counter is bit-identical across reruns and schedules (the PR 2/6/8
determinism contract), the gate compares EXACTLY — no noise margins — and
fails only on drift in the bad direction:

  * work counters may not RISE  (bytes_decoded, bytes_io, cache_misses, ...)
  * savings counters may not FALL (cache_hits, blocks_pruned_stats,
    cells_skipped, rows_short_circuited, bytes_served_from_cache)
  * workload invariants (records_scanned, ...) must match exactly — a
    changed workload makes the comparison meaningless, so it re-records.

Drift in the GOOD direction (an optimization landed) also fails, with a
message telling you to re-record — baselines are ratcheted deliberately,
never silently.

    PYTHONPATH=src python -m benchmarks.regress            # check
    PYTHONPATH=src python -m benchmarks.regress --record   # write baseline

The module also carries the two PR-9 tracing acceptance checks, cheap
enough to run on every gate pass:

  * disabled-tracer overhead: the instrumented code paths pay one ``if tr
    is not None`` per would-be event; we count a traced run's events E and
    directly measure E no-op guard checks, asserting the total under 2% of
    the disabled-run wall time (the PR-7 "directly measured" style — the
    pre-PR binary is not available at runtime to diff against);
  * a traced smoke job exports Chrome trace-event JSON that is loadable
    (well-formed ``traceEvents``, valid phases) and whose ``split.stats``
    counter events sum EXACTLY to the job's final ``ScanStats``.

Scenarios use FIXED sizes (no --full/--smoke scaling): record/replay only
means anything when the recorded and checked workloads are identical.
Smoke runs never write the baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time
from dataclasses import fields as dataclass_fields
from typing import Dict, Optional, Tuple

from repro.core import (
    CIFReader, COFWriter, ColumnFormat, FailurePolicy, FaultPlan, Placement,
    ScanStats, col, explain, fig1_map_batch, fig1_reduce, fig1_where,
    run_job, urlinfo_schema,
)
from repro.core import trace
from repro.core.blockcache import BlockCache
from repro.launch.load_data import synth_crawl_records

from .common import Csv, timeit

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO_ROOT, "BENCH_baseline.json")

N = 3000                 # records — fixed; never scaled by --full/--smoke
SPLIT_RECORDS = 512      # -> 6 splits
N_HOSTS = 4
CUTOFF = 1300000000 + 300  # fetchTime < CUTOFF selects the first 300 rows
POLICY = FailurePolicy(max_attempts=4, max_reexecutions=2, seed=0)
LAYOUT_N = 2048             # the PR-10 layout corpus
LAYOUT_SPLIT_RECORDS = 512  # -> 4 splits
LAYOUT_CUT = 80             # k < LAYOUT_CUT: ~4% — clustered on the sorted copy


def _layout_placement() -> Placement:
    return Placement(LAYOUT_N // LAYOUT_SPLIT_RECORDS, N_HOSTS, 2)

# drift directions: a work counter RISING or a savings counter FALLING is
# a regression; anything else that moves means the workload changed or an
# optimization landed — either way, re-record deliberately.
BAD_UP = frozenset({
    "bytes_io", "bytes_touched", "bytes_decoded", "cells_decoded",
    "blocks_decompressed", "files_opened", "cache_misses",
    "cache_evictions", "checksum_failures", "read_retries",
    "replica_failovers", "splits_reexecuted", "repairs_enqueued",
    # a clean scheduled run serving MORE splits from the insertion-order
    # fallback means the layout cost step stopped winning (PR 10)
    "layout_fallbacks",
})
BAD_DOWN = frozenset({
    "cache_hits", "bytes_served_from_cache", "blocks_pruned_stats",
    "cells_skipped", "rows_short_circuited", "layout_best_choices",
})


def _counters(stats: ScanStats) -> Dict[str, int]:
    """Every integer ScanStats field — the deterministic subset (floats
    and the repair set are schedule- or summation-order-sensitive)."""
    return {
        f.name: v for f in dataclass_fields(ScanStats)
        if isinstance(v := getattr(stats, f.name), int)
    }


def _build_corpora(base: str) -> None:
    """One crawl corpus (scan/job/fault scenarios) + one token corpus
    (the PR-8 serving cache scenario) — both seeded, both fixed-size."""
    from repro.data.tokens import TokenCorpusWriter
    from repro.launch.load_data import synth_token_docs

    w = COFWriter(os.path.join(base, "crawl"), urlinfo_schema(),
                  formats={"url": ColumnFormat("skiplist"),
                           "metadata": ColumnFormat("dcsl"),
                           "content": ColumnFormat("cblock", codec="lzo")},
                  split_records=SPLIT_RECORDS)
    w.append_all(synth_crawl_records(N, content_bytes=256))
    w.close()
    tw = TokenCorpusWriter(os.path.join(base, "tokens"), seq_len=48,
                           split_records=96)
    for toks, meta in synth_token_docs(100, vocab=120, seed=17):
        tw.add_document(toks % 50 + 1, meta)
    tw.close()
    # the PR-10 layout corpus: a shuffled int key (every key range is
    # scattered across insertion-order blocks) + a k-sorted replica copy
    import random

    from repro.core import Schema, materialize_layouts
    from repro.core.schema import INT64, STRING

    keys = list(range(LAYOUT_N))
    random.Random(42).shuffle(keys)
    lw = COFWriter(os.path.join(base, "layouts"),
                   Schema([("k", INT64()), ("payload", STRING())]),
                   formats={"k": ColumnFormat(enc_block=64),
                            "payload": ColumnFormat(enc_block=64)},
                   split_records=LAYOUT_SPLIT_RECORDS)
    for k in keys:
        lw.append({"k": k, "payload": f"p{k:06d}-" + "x" * (10 + k % 20)})
    lw.close()
    materialize_layouts(os.path.join(base, "layouts"), _layout_placement(),
                        ["k"])


# -- scenarios: each returns (counters, extra) -------------------------------

def _scn_fig1_where_job(base: str, n_workers: int = 4):
    """The paper's Fig. 1 job on the where= batch path — the end-to-end
    counter profile of the whole scan engine."""
    r = CIFReader(os.path.join(base, "crawl"), columns=["url", "metadata"])
    ids, ob = r.job_inputs(batch_size=1024, where=fig1_where())
    res = run_job(ids, reduce_fn=fig1_reduce, n_hosts=N_HOSTS,
                  n_workers=n_workers, open_split_batches=ob,
                  map_batch_fn=fig1_map_batch(), scan_stats=r.stats)
    return _counters(r.stats), {"output_rows": len(res.output)}, r.stats


def _scn_sorted_prune(base: str):
    """Zone-map pruning on the sorted fetchTime column, cross-checked
    against ``cif.explain`` — the planner's prediction IS the accounting."""
    root = os.path.join(base, "crawl")
    text = f"fetchTime < {CUTOFF}"
    rep = explain(root, text, columns=["url", "fetchTime"])
    r = CIFReader(root, columns=["url", "fetchTime"])
    rows = 0
    for b in r.scan_batches(batch_size=1024, where=col("fetchTime") < CUTOFF):
        rows += len(next(iter(b.values())))
    assert rep.blocks_pruned == r.stats.blocks_pruned_stats, (
        f"explain predicted {rep.blocks_pruned} pruned blocks, the scan "
        f"pruned {r.stats.blocks_pruned_stats}"
    )
    srcs = {k: int(v) for k, v in sorted(rep.source_totals().items())}
    return _counters(r.stats), {"rows": rows, "prune_sources": srcs}, r.stats


def _scn_cached_refetch(base: str):
    """The PR-8 serving cache path: the same prompt refs fetched twice
    through one shared BlockCache — the second pass's dict pages and mask
    blocks must be cache hits, gated on exact bytes."""
    from repro.data.tokens import TokenCorpus
    from repro.serving.engine import PromptStore

    corpus = TokenCorpus(os.path.join(base, "tokens"))
    store = PromptStore(corpus, max_prompt=6, cache=BlockCache(8 << 20))
    refs = [(sid, rid) for sid in corpus.split_ids() for rid in (0, 1, 2)]
    for _ in range(2):
        store.fetch(refs)
    stats = store.close()
    return _counters(stats), {}, stats


def _scn_faults(base: str):
    """The PR-6/7 failure ladder under a fixed fault plan: failover,
    retry, and repair-queue counters are part of the perf contract too —
    a regression that re-reads more than it must shows up here."""
    root = os.path.join(base, "crawl")
    n_splits = len(CIFReader(root).splits())
    p = Placement(n_splits, N_HOSTS)
    plan = FaultPlan(
        corrupt_blocks=frozenset({(p.primary(1), 1, "url", 0)}),
        io_errors=frozenset({(p.primary(2), 2, "url")}),
    )
    r = CIFReader(root, columns=["url", "metadata"],
                  fault_plan=plan, failure_policy=POLICY)
    ids, ob = r.job_inputs(batch_size=1024, where=fig1_where(), placement=p)
    run_job(ids, reduce_fn=fig1_reduce, n_hosts=N_HOSTS, placement=p,
            open_split_batches=ob, map_batch_fn=fig1_map_batch(),
            n_workers=1, fault_plan=plan, failure_policy=POLICY,
            scan_stats=r.stats)
    return _counters(r.stats), {}, r.stats


def _scn_layout_sched(base: str):
    """The PR-10 layout-aware scheduler on the shuffled-key corpus: every
    split must route to its k-sorted replica copy (``layout_best_choices``
    == n_splits, ``layout_fallbacks`` == 0 — both baselined), and the
    explain report's prune count must equal the scan's counter."""
    root = os.path.join(base, "layouts")
    p = _layout_placement()
    pred = col("k") < LAYOUT_CUT
    r = CIFReader(root, columns=["payload"])
    sched = r.schedule_layouts(pred, p)
    ids, ob = r.job_inputs(schedule=sched)

    def map_batch(split_id, cols, emit):
        emit(None, (cols.n_rows, sum(len(v) for v in cols["payload"])))

    res = run_job(ids, n_hosts=p.n_hosts, placement=sched.placement,
                  open_split_batches=ob, map_batch_fn=map_batch,
                  scan_stats=r.stats)
    rows = sum(v[0] for _, vs in res.output for v in vs)
    assert rows == LAYOUT_CUT, f"selected {rows} rows, wanted {LAYOUT_CUT}"
    rep = explain(root, pred, columns=["payload"], placement=p)
    assert rep.blocks_pruned == r.stats.blocks_pruned_stats, (
        f"layout-aware explain predicted {rep.blocks_pruned} pruned "
        f"blocks, the scheduled scan pruned {r.stats.blocks_pruned_stats}"
    )
    return _counters(r.stats), {"rows": rows}, r.stats


SCENARIOS = [
    ("fig1_where_job", _scn_fig1_where_job),
    ("sorted_prune", _scn_sorted_prune),
    ("cached_refetch", _scn_cached_refetch),
    ("faults", _scn_faults),
    ("layout_sched", _scn_layout_sched),
]


# -- tracing acceptance checks ----------------------------------------------

def _check_overhead(csv: Csv, root: str) -> None:
    """Disabled-tracer overhead < 2%: E events' worth of no-op ``if tr is
    not None`` guards, plus the job's spans' worth of ``trace.span`` (each
    still enters its profiler annotation), measured directly, vs the
    disabled-run wall time."""
    t_dis, _ = timeit(lambda: _scn_fig1_where_job(root), repeat=2)
    with trace.tracing() as tr:
        _scn_fig1_where_job(root)
    n_events = len(tr.events())
    n_spans = sum(1 for e in tr.events() if e[0] == "X")
    live = trace.live()  # tracing() exited -> None again
    assert live is None
    t0 = time.perf_counter()
    hits = 0
    for _ in range(n_events):
        if live is not None:  # the exact guard the hot paths pay
            hits += 1
    for _ in range(n_spans):
        with trace.span("regress.span"):
            pass
    t_guards = time.perf_counter() - t0
    assert hits == 0
    frac = t_guards / t_dis
    assert frac < 0.02, (
        f"{n_events} disabled-tracer guards cost {t_guards*1e6:.1f}us = "
        f"{frac*100:.2f}% of the {t_dis*1e3:.1f}ms job (>= 2%)"
    )
    csv.add("regress/tracer_disabled_overhead", t_guards,
            f"events={n_events} frac={frac*100:.4f}% of {t_dis*1e3:.1f}ms")


def _check_traced_smoke(csv: Csv, root: str) -> None:
    """A traced job must export loadable Chrome trace JSON whose counter
    events reconcile EXACTLY with the final ScanStats."""
    t0 = time.perf_counter()
    with trace.tracing() as tr:
        _counters_run, _extra, stats = _scn_fig1_where_job(root)
    out = os.path.join(tempfile.gettempdir(), "regress-trace.json")
    tr.export_chrome(out)
    try:
        with open(out) as f:
            doc = json.load(f)  # must be well-formed JSON
    finally:
        os.unlink(out)
    evs = doc["traceEvents"]
    assert evs and doc.get("displayTimeUnit") == "ms"
    for e in evs:  # Perfetto-required shape
        assert e["ph"] in ("X", "i", "C") and isinstance(e["ts"], int)
        assert "name" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert isinstance(e["dur"], int)

    # reconciliation: sum of per-split counter deltas == final ScanStats
    tot: Dict[str, int] = {}
    for ph, name, _ts, _dur, _tid, args, _cat, _depth in tr.events():
        if ph != "C":
            continue
        for k, v in args.items():
            if k != "split" and isinstance(v, int):
                tot[k] = tot.get(k, 0) + v
    want = _counters(stats)
    mismatched = {k: (tot.get(k, 0), v) for k, v in want.items()
                  if tot.get(k, 0) != v}
    assert not mismatched, (
        f"trace counter events do not reconcile with ScanStats: "
        f"{mismatched} (trace_sum, scan_stats)"
    )
    csv.add("regress/traced_smoke", time.perf_counter() - t0,
            f"chrome_events={len(evs)} counters_reconciled={len(want)}")


# -- the gate ----------------------------------------------------------------

def _diff(name: str, base: Dict[str, int], now: Dict[str, int]):
    """Classify drift: (regressions, ratchets) — ratchets are changes that
    demand a deliberate re-record rather than signalling breakage."""
    regressions, ratchets = [], []
    for k in sorted(set(base) | set(now)):
        b, n = base.get(k, 0), now.get(k, 0)
        if n == b:
            continue
        row = f"{name}.{k}: {b} -> {n}"
        if (k in BAD_UP and n > b) or (k in BAD_DOWN and n < b):
            regressions.append(row)
        else:
            ratchets.append(row)
    return regressions, ratchets


def regress(csv: Csv, record: bool = False, root: Optional[str] = None) -> None:
    tmp = None
    if root is None:
        tmp = tempfile.mkdtemp(prefix="bench-regress-")
        root = tmp
    try:
        if not os.path.isdir(os.path.join(root, "crawl")):
            _build_corpora(root)
        current: Dict[str, Dict] = {}
        for name, fn in SCENARIOS:
            dt, (counters, extra, _stats) = timeit(lambda fn=fn: fn(root))
            current[name] = {"counters": counters, **extra}
            csv.add(f"regress/{name}", dt,
                    f"bytes_decoded={counters['bytes_decoded']} "
                    f"pruned={counters['blocks_pruned_stats']} "
                    f"cache_hits={counters['cache_hits']}")

        _check_overhead(csv, root)
        _check_traced_smoke(csv, root)

        if record:
            with open(BASELINE_PATH, "w") as f:
                json.dump({"workload": {"n": N, "split_records": SPLIT_RECORDS,
                                        "n_hosts": N_HOSTS, "cutoff": CUTOFF},
                           "scenarios": current}, f, indent=2, sort_keys=True)
            print(f"recorded baseline -> {BASELINE_PATH}")
            return

        assert os.path.exists(BASELINE_PATH), (
            f"{BASELINE_PATH} missing — record it once with "
            "`PYTHONPATH=src python -m benchmarks.regress --record` and "
            "commit it"
        )
        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        regressions, ratchets = [], []
        for name, entry in current.items():
            base = baseline["scenarios"].get(name)
            assert base is not None, (
                f"scenario {name!r} not in baseline — re-record"
            )
            r, t = _diff(name, base["counters"], entry["counters"])
            regressions += r
            ratchets += t
            for k in ("prune_sources", "output_rows", "rows"):
                if base.get(k) != entry.get(k):
                    ratchets.append(f"{name}.{k}: {base.get(k)} -> {entry.get(k)}")
        assert not regressions, (
            "ScanStats regression vs BENCH_baseline.json:\n  "
            + "\n  ".join(regressions)
        )
        assert not ratchets, (
            "counters drifted in a non-regression direction (an optimization "
            "landed, or the workload changed) — re-record the baseline "
            "deliberately with --record and commit it:\n  "
            + "\n  ".join(ratchets)
        )
        print(f"# regress gate: {len(current)} scenarios match baseline")
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help="write BENCH_baseline.json instead of checking")
    args = ap.parse_args()
    regress(Csv(), record=args.record)


if __name__ == "__main__":
    main()
