"""Mini MapReduce executor over columnar splits (paper Fig. 1 semantics).

Runs hand-coded map/reduce functions (no declarative layer — §3.4) with
phase-level timing so benchmarks can report the paper's "map time" vs "total
time" split (Table 1).  Hosts process splits per the ColumnPlacementPolicy
analog; a WorkQueue provides speculative re-execution of dead hosts' splits.

Two map-side execution modes share one scheduler:

  * record mode (compatibility) — ``open_split(split_id)`` yields
    ``(key, value)`` pairs and ``map_fn`` runs once per record (the paper's
    RecordReader world, incl. lazy records).
  * batch mode (the fast path) — ``open_split_batches(split_id)`` yields
    columnar ``BatchColumns`` spans straight off ``SplitReader.read_range``
    and ``map_batch_fn(split_id, columns, emit)`` runs once per span, so
    map functions consume whole NumPy arrays / ``RaggedColumn`` views with
    no per-record ``Record`` objects at all.

Predicate pushdown: ``run_job(..., where=pred)`` filters the map inputs
with a typed predicate tree (``core.predicate.col``).  In batch mode every
span is routed through ``BatchColumns.filter`` — zone-map/dict-page/
stats-tag block pruning, vectorized evaluation of only the predicate
columns, and late materialization of everything else for just the matching
rows — so map functions receive pre-filtered ``FilteredBatchColumns``.
In record mode the predicate evaluates per record on lazy records (only
the referenced columns decode; a map-key leaf such as
``col("metadata")["content-type"] == v`` rides ``Record.get_map_value``,
i.e. the DCSL single-key fast path, so even record-mode filtering never
builds a full map cell).  Either way the surviving row set is
bit-identical to running unfiltered and discarding non-matches.

Concurrency: ``n_workers > 1`` drives the WorkQueue from a
``ThreadPoolExecutor`` with one worker per live host, so work stealing,
dead-host takeover, and straggler mitigation actually overlap and
``JobResult.total_time`` reflects wall-clock concurrency (``map_time``
stays the aggregate per-slot time, like the paper divides total map-task
time by slots).  Map outputs are folded into the shuffle in split order
AFTER the barrier, so job output is bit-identical to a serial run no matter
how the claim/completion race resolves.  Reducer partitioning routes
through ``placement.stable_partition`` (sha256), not the builtin
PYTHONHASHSEED-salted ``hash``, so partition assignment is reproducible
across processes.
"""
from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from . import trace
from .errors import (
    DEFAULT_POLICY,
    CorruptFileError,
    CoverageError,
    FailurePolicy,
    SplitRetryExhausted,
    SplitUnserveableError,
)
from .faults import FaultPlan, execution_epoch
from .placement import Placement, WorkQueue, stable_partition

MapFn = Callable[[Any, Any, Callable[[Any, Any], None]], None]
MapBatchFn = Callable[[int, Any, Callable[[Any, Any], None]], None]
ReduceFn = Callable[[Any, List[Any], Callable[[Any, Any], None]], None]


@dataclass(frozen=True)
class PhaseTimes:
    """Typed per-phase wall-clock breakdown of one job (PR 9).

    ``map`` is the aggregate per-slot map seconds (the paper divides total
    map-task time by slots — same number as ``JobResult.map_time``);
    ``map_wall`` is the barrier-to-barrier wall clock the map phase
    actually took, which is what shrinks with ``n_workers``.
    """

    plan: float
    map: float
    map_wall: float
    shuffle: float
    reduce: float
    total: float


@dataclass
class JobResult:
    output: List[Tuple[Any, Any]]
    map_time: float
    shuffle_time: float
    reduce_time: float
    total_time: float
    splits_processed: int
    map_output_records: int
    host_of_split: Dict[int, int] = field(default_factory=dict)
    remote_reads: int = 0
    mode: str = "records"  # "records" | "batches"
    n_workers: int = 1
    # fault tolerance (PR 6): splits whose work ran more than once — dead-
    # owner steals plus retry-exhaustion requeues — and hosts that died
    # MID-job (start-time dead_hosts excluded).  Both deterministic for a
    # given FaultPlan, serial or concurrent.
    splits_reexecuted: int = 0
    hosts_failed: int = 0
    phase_times: Optional[PhaseTimes] = None


def format_job_report(res: JobResult, stats: Optional[Any] = None,
                      title: str = "job report") -> str:
    """Pretty-print a JobResult (and optionally its ScanStats highlights)."""
    pt = res.phase_times or PhaseTimes(
        plan=0.0, map=res.map_time, map_wall=res.map_time,
        shuffle=res.shuffle_time, reduce=res.reduce_time,
        total=res.total_time)
    lines = [
        f"{title} — {res.mode} mode, {res.n_workers} worker(s)",
        f"  {'phase':<10} {'seconds':>10}",
        f"  {'plan':<10} {pt.plan:>10.4f}",
        f"  {'map':<10} {pt.map:>10.4f}  (aggregate over slots; "
        f"wall {pt.map_wall:.4f})",
        f"  {'shuffle':<10} {pt.shuffle:>10.4f}",
        f"  {'reduce':<10} {pt.reduce:>10.4f}",
        f"  {'total':<10} {pt.total:>10.4f}",
        f"  splits={res.splits_processed} (reexecuted {res.splits_reexecuted})"
        f"  map-out={res.map_output_records}  remote-reads={res.remote_reads}"
        f"  hosts-failed={res.hosts_failed}",
    ]
    if stats is not None:
        lines.append(
            f"  scan: bytes_decoded={stats.bytes_decoded}"
            f" blocks_pruned={stats.blocks_pruned_stats}"
            f" rows_short_circuited={stats.rows_short_circuited}"
            f" cache_hits={stats.cache_hits}"
            f" repairs_enqueued={stats.repairs_enqueued}")
    return "\n".join(lines)


def run_job(
    split_ids: List[int],
    open_split: Optional[Callable[[int], Iterator[Tuple[Any, Any]]]] = None,
    map_fn: Optional[MapFn] = None,
    reduce_fn: Optional[ReduceFn] = None,
    n_reducers: int = 1,
    combiner: Optional[ReduceFn] = None,
    n_hosts: int = 1,
    dead_hosts: Optional[set] = None,
    placement: Optional[Placement] = None,
    *,
    open_split_batches: Optional[Callable[[int], Iterator[Any]]] = None,
    map_batch_fn: Optional[MapBatchFn] = None,
    n_workers: int = 1,
    where: Optional[Any] = None,
    fault_plan: Optional[FaultPlan] = None,
    failure_policy: Optional[FailurePolicy] = None,
    scan_stats: Optional[Any] = None,
) -> JobResult:
    """Execute a MapReduce job.

    Record mode: ``open_split(split_id)`` yields (key, value) pairs — the
    RecordReader — and ``map_fn(key, value, emit)`` runs per record.

    Batch mode: pass ``open_split_batches`` (yielding columnar batches,
    e.g. from ``CIFReader.job_inputs``) plus
    ``map_batch_fn(split_id, columns, emit)`` instead.

    ``n_workers > 1`` executes the simulated hosts concurrently (one worker
    thread per live host, capped at ``n_workers``); output is bit-identical
    to a serial run of the same mode.

    ``where=pred`` pushes a predicate into the map inputs: batch spans are
    pruned/filtered via ``BatchColumns.filter`` (map functions then see
    only matching rows, late-materialized), record-mode map functions run
    only on records the predicate matches.  NOTE: this function is
    schema-agnostic, so only the batch path (whose spans carry a schema)
    can validate predicate literals; a record-mode type-mismatched
    literal silently matches nothing.  When a schema is available,
    prefer ``CIFReader.job_records(where=)`` / ``job_inputs(where=)``,
    which validate up front.

    Fault tolerance (PR 6): ``fault_plan`` contributes start-time dead
    hosts (``fail_at`` <= 0) and kills hosts MID-job on their scheduled
    claim — the in-flight split is stolen by a replica holder and
    re-executed.  A split whose reads exhaust the ``failure_policy``
    (``SplitRetryExhausted``/``CorruptFileError``/``OSError`` from the
    split iterator, which the CIF layer raises when its own retry loop
    gives up) is re-enqueued with a bumped execution epoch, up to
    ``max_reexecutions`` times.  Output, ``remote_reads``, and the
    pre-existing ScanStats stay bit-identical to the no-fault serial run;
    ``scan_stats`` (if given) additionally absorbs ``splits_reexecuted``.
    Note the plan injects READ faults only through a reader wired with it
    (``CIFReader(fault_plan=..., failure_policy=...)``) — pass the same
    plan to both layers.
    """
    t0 = time.perf_counter()
    tr = trace.live()
    batch_mode = map_batch_fn is not None or open_split_batches is not None
    with trace.span("job.plan", splits=len(split_ids),
                    mode="batches" if batch_mode else "records",
                    where=where is not None):
        if batch_mode:
            assert map_batch_fn is not None and open_split_batches is not None, (
                "batch mode needs both open_split_batches and map_batch_fn"
            )
            assert map_fn is None and open_split is None, "pick ONE map-side mode"
            if where is not None:
                inner_open = open_split_batches

                def open_split_batches(split_id: int) -> Iterator[Any]:
                    for cb in inner_open(split_id):
                        fb = cb.filter(where)
                        if fb is not None and fb.n_rows:
                            yield fb
        else:
            assert map_fn is not None and open_split is not None, (
                "record mode needs both open_split and map_fn"
            )
            if where is not None:
                inner_map = map_fn

                def map_fn(key: Any, rec: Any, emit: Callable[[Any, Any], None]) -> None:
                    if where.matches_record(rec):
                        inner_map(key, rec, emit)
        placement = placement or Placement(n_splits=len(split_ids), n_hosts=n_hosts)
        start_dead = set(dead_hosts or ())
        if fault_plan is not None:
            start_dead |= fault_plan.start_dead()
        wq = WorkQueue(placement, dead_hosts=start_dead)
        if not wq.coverage_possible():
            raise CoverageError("a split lost all replicas — job cannot run")
        policy = failure_policy or (DEFAULT_POLICY if fault_plan is not None else None)

        live_hosts = [h for h in range(placement.n_hosts) if h not in start_dead]

    t_plan = time.perf_counter()

    def run_split(sidx: int) -> Tuple[List[Tuple[Any, Any]], float]:
        split_id = split_ids[sidx]
        local_out: List[Tuple[Any, Any]] = []
        emit = lambda k, v: local_out.append((k, v))
        t_map = time.perf_counter()
        if batch_mode:
            for columns in open_split_batches(split_id):
                map_batch_fn(split_id, columns, emit)
        else:
            for key, value in open_split(split_id):
                map_fn(key, value, emit)
        dt = time.perf_counter() - t_map
        if combiner is not None:
            grouped: Dict[Any, List[Any]] = defaultdict(list)
            for k, v in local_out:
                grouped[k].append(v)
            local_out = []
            emit_c = lambda k, v: local_out.append((k, v))
            for k, vs in grouped.items():
                combiner(k, vs, emit_c)
        return local_out, dt

    # mid-job host death: a host dies upon making its fail_at-th claim,
    # WHILE holding that split — the claim stays on the books so a replica
    # holder steals it through the dead-owner branch (a re-execution).
    # Claim counts are per host and schedule-independent for the primary
    # splits (each host drains its primaries in order before stealing).
    claim_counts: Dict[int, int] = defaultdict(int)
    claims_lock = threading.Lock()

    def claim(host: int) -> Optional[int]:
        if host in wq.dead:
            return None
        sidx = wq.next_split(host)
        if sidx is not None and tr is not None:
            # which worker claims a stolen split is a scheduler race —
            # excluded from the deterministic counter view
            tr.instant("job.claim",
                       {"host": host, "split": split_ids[sidx]}, cat="sched")
        if sidx is None or fault_plan is None:
            return sidx
        with claims_lock:
            claim_counts[host] += 1
            k = claim_counts[host]
        dies = fault_plan.dies_after_claims(host)
        if dies is not None and k >= dies:
            if tr is not None:
                tr.instant("host.death", {"host": host}, cat="sched")
            wq.mark_dead(host)  # raises CoverageError when coverage is lost
            return None
        return sidx

    def process(sidx: int) -> Optional[Tuple[List[Tuple[Any, Any]], float]]:
        """Run one split under its execution epoch; on read exhaustion
        re-enqueue it (None) so another worker — with fresh attempt numbers
        — retries.  Once the re-execution cap is hit no replica can serve
        the split: that is coverage lost in substance, so the terminal
        error is ``SplitUnserveableError`` (both a ``CoverageError`` and a
        ``SplitRetryExhausted``) and the remedy is ``cif.repair``."""
        epoch = wq.epoch(sidx)
        try:
            with execution_epoch(epoch):
                # (split, epoch) executions are deterministic — epochs
                # bump on deterministic requeues, never on the race of
                # which worker ran them
                with trace.span("split", split=split_ids[sidx], epoch=epoch):
                    return run_split(sidx)
        except (SplitRetryExhausted, CorruptFileError, OSError) as e:
            if policy is None or not wq.requeue(sidx, policy.max_reexecutions):
                raise SplitUnserveableError(
                    f"split {split_ids[sidx]}: no replica served a clean "
                    f"copy within {0 if policy is None else policy.max_reexecutions} "
                    f"re-execution(s); last error: {e}"
                ) from e
            if tr is not None:
                tr.instant("split.requeue",
                           {"split": split_ids[sidx], "epoch": epoch,
                            "error": type(e).__name__})
            return None

    # Task = (sidx, host, local_out, map_seconds).  Each split is claimed and
    # processed exactly once; the post-barrier fold below is ordered by sidx,
    # which is what makes serial and concurrent output identical.
    def host_loop(host: int) -> List[Tuple[int, int, List[Tuple[Any, Any]], float]]:
        done: List[Tuple[int, int, List[Tuple[Any, Any]], float]] = []
        while True:
            sidx = claim(host)
            if sidx is None:
                return done
            got = process(sidx)
            if got is None:
                # requeued: keep looping — this host holds a replica of the
                # split it just failed, so it can re-claim it even after
                # every other worker has exited
                continue
            local_out, dt = got
            wq.complete(sidx)
            done.append((sidx, host, local_out, dt))

    tasks: List[Tuple[int, int, List[Tuple[Any, Any]], float]] = []

    def drain(into: List[Tuple[int, int, List[Tuple[Any, Any]], float]]) -> None:
        # serial round-robin over the live hosts (the original simulated
        # cluster); also the post-pool sweep for splits orphaned by a host
        # that died after every other worker had already exited
        pending = True
        while pending:
            pending = False
            for h in live_hosts:
                if h in wq.dead:
                    continue
                sidx = claim(h)
                if sidx is None:
                    # the claim itself may have just killed this host,
                    # orphaning its split — run another pass to steal it
                    if h in wq.dead and not wq.all_done():
                        pending = True
                    continue
                pending = True
                got = process(sidx)
                if got is None:
                    continue
                local_out, dt = got
                wq.complete(sidx)
                into.append((sidx, h, local_out, dt))

    # pool size: one thread per live host, capped by the request and by the
    # hardware — more threads than cores only adds GIL/scheduler thrash in a
    # single-process simulated cluster.  Every live host's loop still runs.
    pool_size = min(n_workers, len(live_hosts), os.cpu_count() or n_workers)
    with trace.span("job.map", splits=len(split_ids)):
        if pool_size > 1:
            with ThreadPoolExecutor(max_workers=pool_size) as pool:
                for fut in [pool.submit(host_loop, h) for h in live_hosts]:
                    tasks.extend(fut.result())
            drain(tasks)  # no-op unless a late death orphaned an in-flight split
        else:
            drain(tasks)
    assert len(tasks) == len(split_ids), "scheduler lost or duplicated a split"
    t_map_end = time.perf_counter()

    # the fold between the map barrier and t_shuffle is shuffle work too
    with trace.span("job.shuffle", reducers=n_reducers):
        # deterministic fold: split order, stable partitioning
        shuffle: List[Dict[Any, List[Any]]] = [defaultdict(list) for _ in range(n_reducers)]
        map_time = 0.0
        n_map_out = 0
        host_of_split: Dict[int, int] = {}
        remote_reads = 0
        for sidx, h, local_out, dt in sorted(tasks, key=lambda t: t[0]):
            host_of_split[split_ids[sidx]] = h
            if not placement.is_local(sidx, h):
                remote_reads += 1  # CPP makes this impossible; counted to prove it
            map_time += dt
            n_map_out += len(local_out)
            if n_reducers == 1:
                part = shuffle[0]
                for k, v in local_out:
                    part[k].append(v)
            else:
                for k, v in local_out:
                    shuffle[stable_partition(k, n_reducers)][k].append(v)

        t_shuffle = time.perf_counter()
        # sort phase (keys sorted per reducer, as Hadoop does)
        sorted_parts = [sorted(part.items(), key=lambda kv: repr(kv[0])) for part in shuffle]
    t_reduce = time.perf_counter()

    output: List[Tuple[Any, Any]] = []
    emit_r = lambda k, v: output.append((k, v))
    with trace.span("job.reduce"):
        if reduce_fn is None:
            for part in sorted_parts:
                output.extend((k, vs) for k, vs in part)
        else:
            for part in sorted_parts:
                for k, vs in part:
                    reduce_fn(k, vs, emit_r)
    t_end = time.perf_counter()

    if tr is not None:
        tr.counter("job.stats", {"splits_reexecuted": wq.reexecutions})

    if scan_stats is not None:
        scan_stats.splits_reexecuted += wq.reexecutions

    phase_times = PhaseTimes(
        plan=t_plan - t0,
        map=map_time,
        map_wall=t_map_end - t_plan,
        shuffle=t_reduce - t_shuffle,
        reduce=t_end - t_reduce,
        total=t_end - t0,
    )
    return JobResult(
        output=output,
        map_time=map_time,
        shuffle_time=t_reduce - t_shuffle,
        reduce_time=t_end - t_reduce,
        total_time=t_end - t0,
        splits_processed=len(wq.done),
        map_output_records=n_map_out,
        host_of_split=host_of_split,
        remote_reads=remote_reads,
        mode="batches" if batch_mode else "records",
        n_workers=max(1, pool_size),
        splits_reexecuted=wq.reexecutions,
        hosts_failed=len(wq.dead) - len(start_dead),
        phase_times=phase_times,
    )


# ---------------------------------------------------------------------------
# The paper's example job (Fig. 1): distinct content-types for ibm.com/jp
# ---------------------------------------------------------------------------


def fig1_map(pattern: str = "ibm.com/jp") -> MapFn:
    def map_fn(key: Any, rec: Any, emit: Callable[[Any, Any], None]) -> None:
        url = rec.get("url")
        if pattern in url:
            ct = rec.get_map_value("metadata", "content-type")
            if ct is not None:
                emit(None, ct)

    return map_fn


def fig1_where(pattern: str = "ibm.com/jp"):
    """The Fig. 1 predicate as a pushdown expression — pair it with
    ``fig1_map_batch`` via ``run_job(..., where=fig1_where())`` (or
    ``job_inputs(where=...)``)."""
    from .predicate import col

    return col("url").contains(pattern)


def fig1_map_batch() -> MapBatchFn:
    """Batch-mode Fig. 1 on the blessed ``where=`` path: the engine has
    already evaluated the url predicate vectorized (pruning blocks via
    zone maps / dict pages where possible) and hands this function only
    the MATCHING rows, so all that is left is the sparse single-key DCSL
    fetch of content-type — late materialization without a line of
    hand-rolled mask/sparse plumbing.  (The pre-pushdown hand-rolled
    variant survives as the equivalence oracle in tests/test_pushdown.py.)
    """

    def map_batch(split_id: int, cols: Any, emit: Callable[[Any, Any], None]) -> None:
        assert getattr(cols, "prefiltered", False), (
            "fig1_map_batch expects predicate-filtered spans — run with "
            "run_job(..., where=fig1_where()) or job_inputs(where=...)"
        )
        for ct in cols.sparse("metadata", range(cols.n_rows), key="content-type"):
            if ct is not None:
                emit(None, ct)

    return map_batch


def fig1_reduce(key: Any, vals: List[Any], emit: Callable[[Any, Any], None]) -> None:
    distinct = set(vals)
    for v in sorted(distinct):
        emit(None, v)
