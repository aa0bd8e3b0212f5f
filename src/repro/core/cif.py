"""ColumnInputFormat (CIF, §4.2): projection pushdown + lazy records.

Mirrors the paper's API:

    CIF.set_columns(job, "url, metadata")         -> columns=[...]
    getSplits()                                   -> list_splits()/plan_splits()
    getRecordReader()                             -> CIFReader.scan()

The record objects produced are populated only with the projected columns;
the remaining column files are never opened (I/O elimination at column-file
granularity — CIF's headline win over SEQ/RCFile in Fig. 7).

Batch fast path: ``SplitReader.read_range``/``read_batch`` and
``CIFReader.scan_batches`` return *columnar* dicts of arrays (NumPy for
numeric/bool columns, zero-copy ``RaggedColumn`` views for string/bytes,
lists otherwise) decoded via the vectorized ``ColumnFileReader.read_range``
— no per-record Python object churn.  ``iter_eager`` is implemented on top
of it: records are materialized from column chunks, so eager scans decode
whole spans per column in one pass.

Sharded scans: ``scan``/``scan_batches`` accept ``host=``/``n_hosts=``
(or an explicit ``placement=``) and then visit only the splits that host
*primarily* owns under the ColumnPlacementPolicy analog — the union of all
hosts' shards covers every split exactly once, and every read is CPP-local.
``ScanStats`` updates are lock-protected so per-host shards may be scanned
from concurrent threads against one reader.

Predicate pushdown (``where=``): ``scan_batches(where=p)`` and
``job_inputs(where=p)`` plan each split against the v3/v3.1 zone maps /
dict pages / bloom filters / per-block stats-tags (``SplitReader.plan``),
decode ONLY the predicate columns of the surviving block ranges, evaluate
``p`` exactly and vectorized, and late-materialize the remaining projected
columns for just the matching rows (``read_many``/DCSL ``lookup_many``
under the hood) — the paper's lazy record construction, automatic.
Map-key predicates (``col("metadata")["content-type"] == v``) prune splits
and blocks on key PRESENCE alone and fetch only the referenced key of the
surviving rows through the DCSL single-key path, so a non-matching map
cell is never decoded.  Pruning is advisory and the exact evaluation is
final, so the emitted row set is bit-identical to an unpruned scan
filtered post hoc; ``blocks_pruned_stats`` and ``rows_short_circuited``
account the avoided work and are deterministic across serial, batch, and
concurrent runs.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field, fields as dataclass_fields
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import trace
from .colfile import ColumnFileReader, ReadCounters
from .cof import COMMIT_MARKER, QUARANTINE_MARKER, REPLICA_OVERLAY, is_split_dir
from .errors import (
    CorruptFileError,
    DeadlineExceeded,
    FailurePolicy,
    FailureStats,
    SplitRetryExhausted,
)
from .faults import FaultPlan, attempt_base, current_epoch
from .layout import (
    ROWIDS_COLUMN,
    LayoutDescriptor,
    PinnedPlacement,
    host_layout_dir,
    read_layouts,
)
from .lazy import EagerRecord, LazyRecord, Record
from .placement import Placement, ScheduledPlacement
from .predicate import ColumnInfo, Expr, TRI_NONE, parse_predicate, validate_predicate
from .schema import INT64, Schema
from .stats import PruneResult, clip_ranges, intersect_ranges, ranges_rows
from .varcodec import RaggedColumn

EAGER_CHUNK = 1024  # records decoded per column pass in iter_eager


def list_splits(
    root: str, *, include_quarantined: bool = False
) -> List[Tuple[int, str]]:
    """Committed, serveable splits of a dataset directory.

    Visibility rules (PR 7, docs/FORMAT.md "Commit protocol"):

      * A split under construction lives in a hidden ``.split-*.building``
        directory — the naming convention alone hides it, so a writer
        killed at ANY byte offset leaves the corpus readable at its prior
        committed state.
      * A committed split carries a ``_committed.json`` marker/manifest.
        Final-named directories WITHOUT one are grandfathered as legacy
        (pre-marker) splits — but only while the whole corpus is legacy:
        once any split carries a marker, markerless siblings are treated
        as uncommitted debris and skipped.  (New writers publish by
        directory rename, so they can never produce such a directory —
        this guards against manual tampering.)
      * Splits ``core.repair`` quarantined (zero clean replica copies
        left) are excluded unless ``include_quarantined`` — the
        ``CoverageError`` downgrade path: jobs planned off this listing
        complete on the surviving data instead of dying.
    """
    dirs = []
    any_marker = False
    for name in sorted(os.listdir(root)):
        if not is_split_dir(name):
            continue
        sdir = os.path.join(root, name)
        committed = os.path.exists(os.path.join(sdir, COMMIT_MARKER))
        any_marker = any_marker or committed
        dirs.append((int(name.split("-")[1]), sdir, committed))
    out = []
    for idx, sdir, committed in dirs:
        if any_marker and not committed:
            continue
        if not include_quarantined and os.path.exists(
            os.path.join(sdir, QUARANTINE_MARKER)
        ):
            continue
        out.append((idx, sdir))
    return out


def quarantined_splits(root: str) -> List[int]:
    """Split ids ``core.repair`` has quarantined (sorted)."""
    out = []
    for name in sorted(os.listdir(root)):
        if is_split_dir(name) and os.path.exists(
            os.path.join(root, name, QUARANTINE_MARKER)
        ):
            out.append(int(name.split("-")[1]))
    return out


def read_schema(root: str) -> Schema:
    path = os.path.join(root, "schema.json")
    with open(path) as f:
        text = f.read()
    try:
        return Schema.from_json(text)
    except json.JSONDecodeError as e:
        raise CorruptFileError(path, e.pos, f"unreadable schema ({e.msg})") from e
    except (KeyError, TypeError, AssertionError) as e:
        raise CorruptFileError(path, -1, f"malformed schema ({e})") from e


def storage_report(root: str) -> Dict[str, Dict[str, Any]]:
    """Aggregate each column's write-time encoding stats across all splits.

    Returns ``{column: {"kind", "blocks": {encoding: count}, "raw_bytes",
    "encoded_bytes", "file_bytes", "ratio"}}`` from the ``_meta.json``
    sidecars only (no column file is opened).  Splits written before the
    encoding layer carry no ``encodings`` entry and report what is known
    (file bytes, kind).
    """
    report: Dict[str, Dict[str, Any]] = {}
    for _, sdir in list_splits(root):
        with open(os.path.join(sdir, "_meta.json")) as f:
            meta = json.load(f)
        for name, fmt in meta.get("columns", {}).items():
            col = report.setdefault(name, {
                "kind": fmt.get("kind", "plain"), "blocks": {},
                "raw_bytes": 0, "encoded_bytes": 0, "file_bytes": 0,
                "zone": {"blocks": 0, "min": None, "max": None, "bloom": False},
            })
            col["file_bytes"] += meta.get("bytes", {}).get(name, 0)
            enc = meta.get("encodings", {}).get(name)
            if enc:
                for k, v in enc.get("blocks", {}).items():
                    col["blocks"][k] = col["blocks"].get(k, 0) + v
                col["raw_bytes"] += enc.get("raw_bytes", 0)
                col["encoded_bytes"] += enc.get("encoded_bytes", 0)
                z = enc.get("zone")
                if z:  # zone-map coverage: blocks with stats + min/max span
                    cz = col["zone"]
                    cz["blocks"] += z.get("blocks", 0)
                    cz["bloom"] = cz["bloom"] or bool(z.get("bloom"))
                    for key, pick in (("min", min), ("max", max)):
                        v = z.get(key)
                        if v is None:
                            continue
                        try:
                            cz[key] = v if cz[key] is None else pick(cz[key], v)
                        except TypeError:
                            pass  # mixed types across splits: keep the first
                    if "keys" in z:  # map columns: key-presence coverage
                        ks = z["keys"]
                        cur = cz.get("keys", set())
                        cz["keys"] = (
                            None if ks is None or cur is None
                            else cur | set(ks)
                        )
    for col in report.values():
        col["ratio"] = (
            round(col["encoded_bytes"] / col["raw_bytes"], 3)
            if col["raw_bytes"] else 1.0
        )
        ks = col["zone"].get("keys")
        if isinstance(ks, set):
            col["zone"]["keys"] = sorted(ks)
    return report


def format_storage_report(root: str) -> str:
    """Human-readable per-column storage report (load_data prints this):
    the encoding histogram plus each column's zone-map coverage — blocks
    with stats and the overall min/max span the planner can prune on."""
    lines = [f"{'column':<12} {'kind':<9} {'blocks':<28} "
             f"{'raw':>10} {'encoded':>10} {'ratio':>6}  zone-maps"]
    for name, col in storage_report(root).items():
        blocks = ",".join(f"{k}:{v}" for k, v in sorted(col["blocks"].items())) or "-"
        z = col["zone"]
        if z["blocks"]:
            if z.get("keys") is not None:  # map column: key presence
                span = f" keys={len(z['keys'])}"
            elif z["min"] is not None:
                span = f" [{z['min']!r}..{z['max']!r}]"
            else:
                span = " [no bounds]"
            zone = f"{z['blocks']}blk{span}" + ("+bloom" if z["bloom"] else "")
        else:
            zone = "-"
        lines.append(
            f"{name:<12} {col['kind']:<9} {blocks:<28} "
            f"{col['raw_bytes']:>10} {col['encoded_bytes']:>10} {col['ratio']:>6}  {zone}"
        )
    quarantined = quarantined_splits(root)
    if quarantined:
        lines.append(
            f"QUARANTINED splits (zero clean replica copies — excluded from "
            f"scans until repaired): {quarantined}"
        )
    return "\n".join(lines)


# the ReadCounters that ScanStats sums over every reader a scan opens
_READ_COUNTERS = ("bytes_touched", "bytes_decoded", "cells_decoded", "cells_skipped",
                  "blocks_decompressed", "cache_hits", "cache_misses", "cache_evictions",
                  "bytes_served_from_cache")


@dataclass
class ScanStats:
    """Aggregated instrumentation across a scan — the paper's Table 1 columns."""

    bytes_io: int = 0  # column-file bytes opened (disk reads)
    bytes_touched: int = 0  # bytes actually traversed by readers
    bytes_decoded: int = 0
    cells_decoded: int = 0
    cells_skipped: int = 0
    blocks_decompressed: int = 0
    records_scanned: int = 0
    files_opened: int = 0
    # predicate pushdown accounting (where= scans only; zero otherwise).
    # blocks_pruned_stats: per-column stats blocks the planner excluded
    # before any decode; rows_short_circuited: rows whose predicate
    # evaluated false on the surviving spans, so their remaining projected
    # columns were never materialized.  Both are per-split deterministic,
    # hence bit-identical between serial, batch, and concurrent runs.
    blocks_pruned_stats: int = 0
    rows_short_circuited: int = 0
    # failure accounting (PR 6; zero on clean runs).  The integer counters
    # are deterministic for a given FaultPlan and bit-identical between
    # serial and concurrent schedules (fault decisions key on the replica
    # chain, not the executing worker).  simulated_delay_s is deterministic
    # per split but, as a float sum, only schedule-identical up to
    # summation order.
    checksum_failures: int = 0  # CRC mismatches detected (incl. re-fetches)
    read_retries: int = 0  # read attempts beyond each column's first
    replica_failovers: int = 0  # retries served by a DIFFERENT replica host
    splits_reexecuted: int = 0  # dead-owner steals + retry-exhaustion requeues
    simulated_delay_s: float = 0.0
    # read repair (PR 7): distinct replica copies observed corrupt during
    # the scan, queued for post-job healing — ``cif.repair(root, placement,
    # queue=stats.repair_queue)`` drains them.  Schedule-free like the PR-6
    # counters: enqueue decisions key on the replica chain, entries fold in
    # only when a split COMPLETES, and the queue is a set — bit-identical
    # serial vs concurrent.
    repairs_enqueued: int = 0
    repair_queue: set = field(default_factory=set)  # {(split, column, host)}
    # shared block cache (PR 8; zero without one).  Schedule-free: every
    # (split, column, block) key is touched by exactly one split execution
    # per job, so hit/miss decisions depend only on that execution's own
    # access order — bit-identical serial vs concurrent (evictions are
    # charged to the inserting reader and are zero under a budget that
    # never evicts mid-job).  bytes_served_from_cache records EXACTLY the
    # decode bytes hits avoided, so a cache-off run's bytes_decoded equals
    # a cache-on run's bytes_decoded + bytes_served_from_cache and every
    # other counter above stays bit-identical cache-on vs cache-off.
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    bytes_served_from_cache: int = 0
    # layout-aware scheduling (PR 10; zero without a LayoutSchedule).
    # Per COMPLETING split execution exactly one of the two advances:
    # layout_best_choices when the execution was served by the schedule's
    # top-choice SORTED replica copy, layout_fallbacks otherwise (the
    # insertion-order copy won the cost comparison, or failover rotated
    # the execution onto a lower-preference replica).  Schedule-free like
    # every counter above: the choice is precomputed per split and epochs
    # bump on deterministic requeues, so serial == concurrent.
    layout_best_choices: int = 0
    layout_fallbacks: int = 0

    def absorb(self, c: ReadCounters, file_bytes: int) -> None:
        self.bytes_io += file_bytes
        self.absorb_since(c, ReadCounters())
        self.files_opened += 1

    def absorb_since(self, c: ReadCounters, since: ReadCounters) -> None:
        """Fold what a reader that stays open counted since ``since`` (its
        counters at the last fold): ``absorb`` without the file's bytes_io
        and files_opened, which whoever opened it charges once."""
        for k in _READ_COUNTERS:
            setattr(self, k, getattr(self, k) + getattr(c, k) - getattr(since, k))

    def absorb_failures(self, f: FailureStats) -> None:
        self.checksum_failures += f.checksum_failures
        self.read_retries += f.read_retries
        self.replica_failovers += f.replica_failovers
        self.simulated_delay_s += f.simulated_delay_s
        # set-difference first: a copy already queued (e.g. by an earlier
        # execution epoch absorbed by PromptStore) never counts twice, so
        # ``repairs_enqueued == len(repair_queue)`` is an invariant here
        new = f.repair_queue - self.repair_queue
        self.repair_queue |= new
        self.repairs_enqueued += len(new)


class _LazyReaders(dict):
    """Column readers opened on first access (``lazy_open`` SplitReaders):
    a split whose every block the planner pruned never opens the files of
    the columns it would have projected."""

    def __init__(self, sr: "SplitReader"):
        super().__init__()
        self._sr = sr

    def __missing__(self, name: str) -> ColumnFileReader:
        r = self._sr._open_reader(name)
        self[name] = r
        return r


def _read_file(path: str, column: str) -> bytes:
    """One column file, read whole, under a ``storage.read`` span."""
    with trace.span("storage.read", column=column) as sp, open(path, "rb") as f:
        raw = f.read()
        sp.set_metadata(bytes=len(raw))
    return raw


class SplitReader:
    """RecordReader for one split-directory.

    Fault tolerance (PR 6): with a ``policy`` (and optionally a
    ``placement`` + ``split_id`` naming the replica chain, plus a
    ``fault_plan`` injecting failures), every column-file open runs a
    deterministic retry loop — attempt ``a`` reads from replica host
    ``chain[a % len(chain)]``, corruption found MID-read recovers through
    the same seam — and raises ``SplitRetryExhausted`` past the policy's
    caps, at which point ``run_job`` re-enqueues the split.  All failure
    accounting lands in ``self.fail`` (shared by every reader this split
    opens, so it survives discarded open attempts) and folds into
    ``ScanStats`` only when the split COMPLETES — an abandoned execution
    contributes nothing, which is what keeps faulted-run stats identical
    to the clean run's.
    """

    def __init__(
        self,
        split_dir: str,
        schema: Schema,
        columns: Sequence[str],
        lazy_open: bool = False,
        project: Optional[Sequence[str]] = None,
        *,
        split_id: Optional[int] = None,
        placement: Optional[Placement] = None,
        fault_plan: Optional[FaultPlan] = None,
        policy: Optional[FailurePolicy] = None,
        fail: Optional[FailureStats] = None,
        cache: Optional[Any] = None,
    ):
        self.split_dir = split_dir
        self.schema = schema
        # tracer captured at construction (PR 9): None when tracing is off,
        # so the per-attempt/per-span guards cost one identity test
        self._tr = trace.live()
        # shared decoded-block cache (core.blockcache), threaded into every
        # column reader this split opens; keys derive from the column-file
        # path, so reopened splits serve previously-decoded blocks as hits
        self._cache = cache
        self.columns = list(columns)  # openable (projection + predicate)
        # the caller-requested projection: what batches/records expose.
        # Predicate-only columns stay readable by explicit name but never
        # appear in keys()/iteration, so where= and plain scans of the
        # same reader expose identical column sets.
        self.out_columns = list(project) if project is not None else self.columns
        self.split_id = split_id
        self._placement = placement
        self._fault_plan = fault_plan
        self._policy = policy
        # ``fail=`` lets a caller keep the failure ledger even when THIS
        # CONSTRUCTOR raises (PromptStore: corruption during open would
        # otherwise discard the repair queue with the half-built reader)
        self.fail = fail if fail is not None else FailureStats()
        # attempt numbers restart at epoch * ATTEMPT_STRIDE when a split is
        # re-enqueued; captured once so every column of this execution
        # shares the epoch it was claimed under
        self._attempt_base = attempt_base()
        self._attempts: Dict[str, int] = {}
        # read repair (PR 7): which replica host served each column's
        # CURRENT bytes — the copy to blame (and queue for healing) when a
        # checksum mismatch fires through the ``on_corrupt`` seam
        self._last_served: Dict[str, int] = {}
        mpath = os.path.join(split_dir, "_meta.json")
        try:
            with open(mpath) as f:
                self.meta = json.load(f)
            self.n_records = self.meta["n_records"]
        except json.JSONDecodeError as e:
            raise CorruptFileError(
                mpath, e.pos, f"unreadable _meta.json ({e.msg})"
            ) from e
        except (KeyError, TypeError) as e:
            raise CorruptFileError(mpath, -1, f"malformed _meta.json ({e})") from e
        # planner accounting, folded into ScanStats by finish_stats
        self.blocks_pruned_stats = 0
        self.rows_short_circuited = 0
        # layout-aware scheduling attribution (PR 10): the schedule's open
        # function sets exactly one to 1 before handing the reader to the
        # map task; finish_stats folds them, so — like every counter — an
        # abandoned execution on one replica contributes nothing
        self.layout_best_choices = 0
        self.layout_fallbacks = 0
        # a layout copy's _meta.json carries its descriptor; the base copy
        # has none.  filter_split keys the canonical re-permutation on it.
        self.layout: Optional[Dict[str, Any]] = self.meta.get("layout")
        self._plan: Optional[Tuple[Expr, PruneResult]] = None
        if lazy_open:
            self.readers: Dict[str, ColumnFileReader] = _LazyReaders(self)
        else:
            self.readers = {n: self._open_reader(n) for n in self.columns}

    def _fetch_attempt(self, name: str, path: str) -> bytes:
        """ONE read attempt of a column file: pick the replica host the
        attempt number maps to, read, pass the bytes through the fault
        plan.  Raises ``SplitRetryExhausted`` at the policy's attempt cap
        and ``DeadlineExceeded`` when accumulated (simulated) backoff blows
        the split's deadline.  Serves both the open-retry loop and the
        reader's mid-read recovery seam — they share the attempt counter.
        """
        policy = self._policy
        k = self._attempts.get(name, 0)
        self._attempts[name] = k + 1
        if policy is not None and k >= policy.max_attempts:
            raise SplitRetryExhausted(
                f"column {name!r} of split {self.split_id}: "
                f"{k} attempts exhausted"
            )
        a = self._attempt_base + k
        chain: Tuple[int, ...] = (0,)
        if self._placement is not None and self.split_id is not None:
            chain = self._placement.replicas(self.split_id)
        host = chain[a % len(chain)]
        if k > 0:
            self.fail.read_retries += 1
            if host != chain[self._attempt_base % len(chain)] and len(chain) > 1:
                self.fail.replica_failovers += 1
            if policy is not None:
                d = policy.backoff_s(f"{self.split_id}:{name}", k)
                self.fail.simulated_delay_s += d
                if policy.real_sleep:  # pragma: no cover - opt-in only
                    time.sleep(d)
                if (
                    policy.split_deadline is not None
                    and self.fail.simulated_delay_s > policy.split_deadline
                ):
                    raise DeadlineExceeded(
                        f"split {self.split_id}: retry-delay budget "
                        f"({policy.split_deadline}s simulated) exhausted"
                    )
        # replica overlay (PR 7): ``core.repair`` persists healed per-host
        # copies under ``_replicas/h<host>/``; when one exists for the host
        # this attempt maps to, it supersedes the (possibly damaged) base
        # copy and reads back clean — repaired media, fresh sectors
        opath = os.path.join(
            self.split_dir, REPLICA_OVERLAY, f"h{host}", os.path.basename(path)
        )
        healed = os.path.exists(opath)
        if self._tr is not None:
            # attempt numbers (epoch-strided) and replica hosts are keyed on
            # the chain, never the executing worker — deterministic args
            self._tr.instant("fetch.attempt", {
                "split": self.split_id, "column": name, "attempt": a,
                "host": host, "healed": healed,
            })
        raw = _read_file(opath if healed else path, name)
        if self._fault_plan is not None:
            raw = self._fault_plan.apply(
                raw, host=host, split=self.split_id or 0, column=name,
                attempt=a, fail=self.fail, healed=healed,
            )
        self._last_served[name] = host
        return raw

    def _enqueue_repair(self, name: str) -> None:
        """The bytes ``_last_served[name]`` handed over are known corrupt:
        queue that replica copy for post-job healing.  Meaningful only when
        a placement names real replica identities."""
        host = self._last_served.get(name)
        if (
            host is not None
            and self.split_id is not None
            and self._placement is not None
        ):
            self.fail.enqueue_repair(self.split_id, name, host)

    def _open_reader(self, name: str) -> ColumnFileReader:
        assert name in self.columns, f"column {name!r} not opened by this split"
        return self._open_reader_typed(name, self.schema.type_of(name))

    def _open_reader_typed(self, name: str, typ: Any) -> ColumnFileReader:
        """Open one column file with an explicit type — the seam that lets
        ``filter_split`` open a layout copy's ``_rowids`` companion (not a
        schema column) through the SAME retry/overlay/fault machinery as
        every real column."""
        path = os.path.join(self.split_dir, f"{name}.col")
        if self._policy is None and self._fault_plan is None:
            # no retry policy: plain open — still graceful typed errors and
            # lazy verification, but corruption raises instead of recovering
            raw = _read_file(path, name)
            return ColumnFileReader(
                raw, typ, path=path, fail=self.fail, cache=self._cache
            )
        verify = self._policy.verify if self._policy is not None else True

        def fetch() -> bytes:
            return self._fetch_attempt(name, path)

        def on_corrupt() -> None:
            self._enqueue_repair(name)

        while True:
            try:
                raw = fetch()  # SplitRetryExhausted propagates to run_job
            except OSError:
                continue  # injected/real IO error: costs one attempt
            try:
                return ColumnFileReader(
                    raw, typ, path=path, fail=self.fail, fetch=fetch,
                    verify=verify, on_corrupt=on_corrupt, cache=self._cache,
                )
            except SplitRetryExhausted:
                raise  # mid-recovery exhaustion inside the constructor
            except (CorruptFileError, OSError) as e:
                if isinstance(e, CorruptFileError):
                    # parse-level damage never reaches a CRC check, so the
                    # on_corrupt seam did not fire — queue the copy here
                    # (enqueue_repair dedups the CRC-detected case)
                    self._enqueue_repair(name)
                continue  # damaged copy: next attempt, next replica

    # -- predicate planning + late materialization ---------------------------
    def _meta_zone(self, name: str) -> Optional[Dict[str, Any]]:
        return self.meta.get("encodings", {}).get(name, {}).get("zone")

    def plan(self, pred: Expr) -> PruneResult:
        """Advisory split plan.

        Stage 1 — split pruning from ``_meta.json`` alone: each predicate
        column's persisted zone summary (exact min/max across the whole
        split, or the exact map-key union for map columns) evaluates
        three-valued; if any column proves no row can match, the split is
        done WITHOUT opening a single column file.
        Stage 2 — block pruning: intersect each predicate column's
        ``ColumnFileReader.prune`` ranges (zone maps + dict pages +
        blooms).  Memoized per predicate instance and charged to the prune
        counters exactly once per split, so the accounting is identical no
        matter how many spans consult it or how many workers run.
        """
        if self._plan is not None and self._plan[0] is pred:
            return self._plan[1]
        pcols = sorted(pred.columns())
        total = pruned = 0
        split_dead = False
        for name in pcols:
            z = self._meta_zone(name)
            if not z:
                continue
            keys = z.get("keys")
            info = ColumnInfo(
                vmin=z.get("min"), vmax=z.get("max"),
                map_keys=frozenset(keys) if keys is not None else None,
            )
            if info.vmin is None and info.map_keys is None:
                continue
            if pred.tri(lambda nm, name=name, info=info:
                        info if nm == name else None) == TRI_NONE:
                split_dead = True
                total += z["blocks"]
                pruned += z["blocks"]
        if split_dead:
            res = PruneResult([], total, pruned)
        else:
            ranges = [(0, self.n_records)] if self.n_records else []
            total = pruned = 0
            for name in pcols:
                pr = self.readers[name].prune(pred, column=name)
                ranges = intersect_ranges(ranges, pr.ranges)
                total += pr.blocks_total
                pruned += pr.blocks_pruned
            res = PruneResult(ranges, total, pruned)
        self._plan = (pred, res)
        self.blocks_pruned_stats += res.blocks_pruned
        if self._tr is not None:
            self._tr.instant("plan.split", {
                "split": self.split_id, "blocks_total": res.blocks_total,
                "blocks_pruned": res.blocks_pruned,
                "split_dead": split_dead,
            })
        return res

    def filter_span(
        self, pred: Expr, start: int, stop: int
    ) -> Optional["FilteredBatchColumns"]:
        """Evaluate ``pred`` exactly over the surviving sub-ranges of
        ``[start, stop)`` and return the matching rows as a late-
        materializing ``FilteredBatchColumns`` (None when nothing matches —
        counters still advance).  Only the predicate columns are decoded
        here; everything else waits for the map function to ask.

        Map-key leaves late-materialize ONLY the referenced key: a DCSL map
        column serves them through ``lookup_many`` (skip-pointer jumps +
        single-entry decodes), so the full map cells of candidate rows are
        never built.  The two exceptions decode whole cells once and derive
        every key from them: a map column that is also PROJECTED (its
        monotone reader must not be consumed twice over the same rows) and
        a predicate referencing several keys of one map column.
        """
        sub = clip_ranges(self.plan(pred).ranges, start, stop)
        if not sub:
            return None
        ids = np.concatenate([np.arange(a, b, dtype=np.int64) for a, b in sub])
        ids_list = ids.tolist()
        # group leaf refs by base column: {name: set of keys (None = whole)}
        by_col: Dict[str, set] = {}
        for leaf in pred.iter_leaves():
            by_col.setdefault(leaf.name, set()).add(leaf.key)
        decoded: Dict[Any, Any] = {}  # leaf.ref -> decoded values
        full_cells: Dict[str, Any] = {}  # map columns decoded whole
        for name in sorted(by_col):
            keys = by_col[name]
            if keys == {None}:  # plain column leaf (the pre-map-key path)
                decoded[name] = self.readers[name].read_many(ids_list)
                continue
            # whole-column + map-key refs cannot mix on one column:
            # validate_predicate rejects whole-map comparisons up front
            assert None not in keys, name
            if len(keys) > 1 or name in self.out_columns:
                cells = self.readers[name].read_many(ids_list)
                full_cells[name] = cells
                for key in keys:
                    decoded[(name, key)] = [
                        c.get(key) if isinstance(c, dict) else None
                        for c in cells
                    ]
            else:
                (key,) = keys
                decoded[(name, key)] = self.readers[name].lookup_many(
                    ids_list, key
                )
        mask = pred.mask(lambda ref: decoded[ref], len(ids))
        n_match = int(mask.sum())
        self.rows_short_circuited += len(ids) - n_match
        if self._tr is not None:
            self._tr.instant("filter.span", {
                "split": self.split_id, "start": start, "stop": stop,
                "rows_in": len(ids), "rows_matched": n_match,
            })
        if n_match == 0:
            return None
        # pre-decoded values the filtered span can serve from cache: whole
        # predicate columns, plus projected map columns decoded above
        pred_vals = {
            name: _compress(decoded[name], mask)
            for name in by_col if by_col[name] == {None}
        }
        for name, cells in full_cells.items():
            if name in self.out_columns:
                pred_vals[name] = _compress(cells, mask)
        return FilteredBatchColumns(self, ids[mask], pred_vals, start, stop)

    def filter_split(self, pred: Expr) -> Optional["BatchColumns"]:
        """Whole-split predicate evaluation in CANONICAL record order — the
        layout-aware read path (PR 10).

        On the insertion-order base copy this is exactly one
        ``filter_span`` over the full split.  On a sorted layout copy the
        matched rows come back in SORT order, so they are re-permuted by
        the copy's ``_rowids`` companion column (the canonical record id of
        each sorted row) into a ``CanonicalBatchColumns`` whose ``rows``,
        iteration order, and late-materialized values are bit-identical to
        what the base copy produces — which is what lets a job mix replicas
        of different layouts (choice, failover) and still fold one
        deterministic output.  One span per split by construction: the
        permutation needs every matching row of the split at once.
        """
        fb = self.filter_span(pred, 0, self.n_records)
        if fb is None or self.layout is None:
            return fb
        # _rowids opens through the full retry seam (keyed as its own
        # column) and its IO lands in self.readers, so finish_stats charges
        # the canonicalization honestly
        if ROWIDS_COLUMN not in self.readers:
            self.readers[ROWIDS_COLUMN] = self._open_reader_typed(
                ROWIDS_COLUMN, INT64()
            )
        canon = np.asarray(
            self.readers[ROWIDS_COLUMN].read_many(fb.rows.tolist()), np.int64
        )
        perm = np.argsort(canon, kind="stable")
        return CanonicalBatchColumns(fb, canon, perm)

    def iter_lazy(self) -> Iterator[LazyRecord]:
        rec = LazyRecord(self.readers)
        for _ in range(self.n_records):
            rec._advance()
            yield rec

    def read_range(self, start: int, stop: int) -> Dict[str, Any]:
        """Columnar batch over records ``[start, stop)``: one bulk decode
        per projected column."""
        return {n: self.readers[n].read_range(start, stop) for n in self.out_columns}

    def read_batch(self, indices: Sequence[int]) -> Dict[str, Any]:
        """Columnar batch over a sorted strictly-increasing index set
        (monotone readers: contiguous runs decode in single passes)."""
        return {n: self.readers[n].read_many(indices) for n in self.out_columns}

    def iter_eager(self, chunk: int = EAGER_CHUNK) -> Iterator[EagerRecord]:
        """Eager scan on the batch path: each column decodes ``chunk``
        records per pass; records are materialized from the column chunks
        (NumPy scalars converted back to native Python via ``tolist``)."""
        for start in range(0, self.n_records, chunk):
            stop = min(start + chunk, self.n_records)
            cols = {}
            for name in self.out_columns:
                v = self.readers[name].read_range(start, stop)
                cols[name] = v.tolist() if isinstance(v, np.ndarray) else v
            for i in range(stop - start):
                yield EagerRecord({n: cols[n][i] for n in self.out_columns})

    def finish_stats(self, stats: ScanStats) -> None:
        # per-split delta counter event (PR 9): computed from this split's
        # OWN numbers — never by diffing the cumulative stats, whose float
        # fields depend on summation order.  Only the completing execution
        # reaches here, so summing every split.stats event reproduces the
        # final ScanStats exactly (the trace-reconciliation acceptance).
        delta = ScanStats() if self._tr is not None else None
        for name, r in self.readers.items():
            stats.absorb(r.counters, r.file_bytes)
            if delta is not None:
                delta.absorb(r.counters, r.file_bytes)
        stats.records_scanned += self.n_records
        stats.blocks_pruned_stats += self.blocks_pruned_stats
        stats.rows_short_circuited += self.rows_short_circuited
        stats.layout_best_choices += self.layout_best_choices
        stats.layout_fallbacks += self.layout_fallbacks
        stats.absorb_failures(self.fail)
        if delta is not None:
            delta.records_scanned += self.n_records
            delta.blocks_pruned_stats += self.blocks_pruned_stats
            delta.rows_short_circuited += self.rows_short_circuited
            delta.layout_best_choices += self.layout_best_choices
            delta.layout_fallbacks += self.layout_fallbacks
            delta.absorb_failures(self.fail)
            payload: Dict[str, Any] = {
                f.name: getattr(delta, f.name)
                for f in dataclass_fields(ScanStats)
                if f.name != "repair_queue"
            }
            payload["split"] = self.split_id
            self._tr.counter("split.stats", payload)


def _compress(vals: Any, mask: np.ndarray) -> Any:
    """Filter a decoded column batch down to the mask's rows (zero-copy
    views where the representation allows)."""
    if isinstance(vals, (np.ndarray, RaggedColumn)):
        return vals[np.flatnonzero(mask)]
    return [v for v, m in zip(vals, mask) if m]


class BatchColumns:
    """Column-lazy view of one record span ``[start, stop)`` of a split —
    the ``columns`` argument handed to batch map functions.

    Acts like a ``Dict[str, array]``: ``cols["url"]`` bulk-decodes that
    column's span on FIRST access (projection pushdown at column-batch
    granularity — a column a map function never touches is never decoded),
    returning a NumPy array / ``RaggedColumn`` / list per the ``read_range``
    contract.  ``sparse(name, rows[, key])`` point-reads a row subset of an
    untouched column through ``read_many`` (and the DCSL single-key
    ``lookup`` when ``key`` is given) — the lazy-materialization analog for
    batch mode: decode the predicate column vectorized, then fetch the
    payload column only where the predicate hit.
    """

    __slots__ = ("_sr", "start", "stop", "_cache")

    prefiltered = False

    def __init__(self, sr: "SplitReader", start: int, stop: int):
        self._sr = sr
        self.start = start
        self.stop = stop
        self._cache: Dict[str, Any] = {}

    @property
    def n_rows(self) -> int:
        return self.stop - self.start

    def keys(self):
        return list(self._sr.out_columns)

    def __iter__(self):
        return iter(self._sr.out_columns)

    def __contains__(self, name: str) -> bool:
        return name in self._sr.out_columns

    def __getitem__(self, name: str) -> Any:
        v = self._cache.get(name)
        if v is None:
            r = self._sr.readers[name]
            assert r.position <= self.start, (
                f"column {name!r} already read past this span "
                "(sparse() then full access is not supported)"
            )
            v = r.read_range(self.start, self.stop)
            self._cache[name] = v
        return v

    def get(self, name: str, default: Any = None) -> Any:
        return self[name] if name in self._sr.out_columns else default

    def sparse(self, name: str, rows: Sequence[int], key: Optional[str] = None) -> List[Any]:
        """Fetch ``rows`` (span-relative, strictly increasing) of ``name``.

        With ``key`` on a DCSL map column only that key's entry is decoded
        per row (the paper's §5.3 fast path); otherwise the rows decode via
        ``read_many``.  Skipped rows cost skip-list jumps, not decodes.
        """
        ids = [self.start + int(r) for r in rows]
        assert all(b > a for a, b in zip(ids, ids[1:])), "rows must be strictly increasing"
        assert not ids or (self.start <= ids[0] and ids[-1] < self.stop), "rows outside span"
        return self._sparse_abs(name, ids, key)

    def _sparse_abs(self, name: str, ids: List[int], key: Optional[str]) -> List[Any]:
        r = self._sr.readers[name]
        if key is not None:
            return r.lookup_many(ids, key)
        vals = r.read_many(ids)
        return vals.tolist() if isinstance(vals, np.ndarray) else list(vals)

    def filter(self, pred: Expr) -> Optional["FilteredBatchColumns"]:
        """Predicate pushdown over this span (what ``run_job(where=)``
        calls): prune via the split plan, evaluate ``pred`` exactly on the
        survivors, and return the matching rows as a late-materializing
        view — or None when no row matches (planner/evaluation counters
        still advance)."""
        missing = sorted(c for c in pred.columns() if c not in self._sr.columns)
        assert not missing, (
            f"predicate references unopened columns {missing}; include them "
            "in the reader's columns or pass where= to job_inputs()"
        )
        validate_predicate(pred, self._sr.schema.type_of)
        return self._sr.filter_span(pred, self.start, self.stop)


class FilteredBatchColumns(BatchColumns):
    """A ``BatchColumns`` span already filtered by a predicate: only the
    matching rows exist.  Predicate columns arrive pre-decoded (sliced from
    the exact evaluation); every other column late-materializes on first
    access via ``read_many`` over just the matching rows — the batch analog
    of the paper's lazy record construction, applied automatically.

    ``rows`` holds the absolute record ids that matched (strictly
    increasing); ``n_rows`` is their count; ``sparse(name, rows)`` indexes
    into the MATCHING rows.  ``prefiltered`` marks the span so map
    functions (and ``filter`` itself) can tell it apart from a raw span.
    """

    __slots__ = ("rows",)

    prefiltered = True

    def __init__(
        self,
        sr: "SplitReader",
        rows: np.ndarray,
        pred_values: Dict[str, Any],
        start: int,
        stop: int,
    ):
        super().__init__(sr, start, stop)
        self.rows = rows
        self._cache.update(pred_values)

    @property
    def n_rows(self) -> int:
        return len(self.rows)

    def __getitem__(self, name: str) -> Any:
        v = self._cache.get(name)
        if v is None:
            r = self._sr.readers[name]
            assert r.position <= int(self.rows[0]), (
                f"column {name!r} already read past this span"
            )
            tr = self._sr._tr
            if tr is not None:
                tr.instant("materialize", {
                    "split": self._sr.split_id, "column": name,
                    "rows": len(self.rows),
                })
            v = r.read_many(self.rows.tolist())
            self._cache[name] = v
        return v

    def sparse(self, name: str, rows: Sequence[int], key: Optional[str] = None) -> List[Any]:
        idx = np.asarray(list(rows), np.int64)
        ids = [int(i) for i in self.rows[idx]]
        assert all(b > a for a, b in zip(ids, ids[1:])), "rows must be strictly increasing"
        return self._sparse_abs(name, ids, key)

    def filter(self, pred: Expr) -> Optional["FilteredBatchColumns"]:
        raise AssertionError(
            "span is already predicate-filtered — pass where= to either "
            "job_inputs() or run_job(), not both"
        )


class CanonicalBatchColumns:
    """Matched rows of a SORTED replica copy, re-permuted into canonical
    (insertion) order — what ``SplitReader.filter_split`` yields off a
    layout copy (PR 10).

    Wraps the copy's ``FilteredBatchColumns`` (whose ``rows`` are sorted-
    copy positions) with the permutation derived from ``_rowids``:
    ``rows`` here are the CANONICAL record ids, strictly increasing, and
    every column access permutes the underlying values to match — so map
    functions observe exactly the view the insertion-order base copy would
    have produced, and job output folds bit-identically no matter which
    replica (or mix of replicas, under failover) served each split.
    Late materialization is preserved: an untouched column still decodes
    only on first access, reading the SORTED copy's rows monotonically
    before permuting.
    """

    __slots__ = ("_fb", "_perm", "rows", "start", "stop", "_cache")

    prefiltered = True

    def __init__(
        self, fb: FilteredBatchColumns, canon: np.ndarray, perm: np.ndarray
    ):
        self._fb = fb
        self._perm = perm
        self.rows = canon[perm]
        assert len(self.rows) == 0 or bool(
            np.all(self.rows[1:] > self.rows[:-1])
        ), "duplicate canonical row ids — corrupt _rowids companion"
        self.start = 0
        self.stop = fb._sr.n_records
        self._cache: Dict[str, Any] = {}

    @property
    def n_rows(self) -> int:
        return len(self.rows)

    def keys(self):
        return self._fb.keys()

    def __iter__(self):
        return iter(self._fb)

    def __contains__(self, name: str) -> bool:
        return name in self._fb

    def __getitem__(self, name: str) -> Any:
        v = self._cache.get(name)
        if v is None:
            raw = self._fb[name]
            if isinstance(raw, (np.ndarray, RaggedColumn)):
                v = raw[self._perm]
            else:
                v = [raw[int(i)] for i in self._perm]
            self._cache[name] = v
        return v

    def get(self, name: str, default: Any = None) -> Any:
        return self[name] if name in self._fb else default

    def sparse(self, name: str, rows: Sequence[int], key: Optional[str] = None) -> List[Any]:
        """Index into the MATCHING rows (canonical order).  The underlying
        sorted-copy fetch must be monotone, so the request is routed
        through the permutation, served in sorted-copy order, and the
        results un-permuted back."""
        idx = np.asarray(list(rows), np.int64)
        fbi = self._perm[idx]
        order = np.argsort(fbi, kind="stable")
        vals = self._fb.sparse(name, fbi[order].tolist(), key)
        out: List[Any] = [None] * len(idx)
        for j, o in enumerate(order.tolist()):
            out[o] = vals[j]
        return out

    def filter(self, pred: Expr) -> Optional["FilteredBatchColumns"]:
        raise AssertionError(
            "span is already predicate-filtered — pass where= to either "
            "job_inputs() or run_job(), not both"
        )


@dataclass(frozen=True)
class LayoutCandidate:
    """One replica copy a split's ``where=`` scan could be served from:
    the insertion-order base copy (``sort_by is None``) or a host's sorted
    layout copy — with the real planner's verdict against THAT copy's zone
    maps (probed without decoding a cell)."""

    host: int
    sort_by: Optional[str]
    dir: str
    blocks_total: int
    blocks_pruned: int
    candidate_rows: int
    chain_pos: int  # position in the split's replica chain

    @property
    def blocks_scanned(self) -> int:
        return self.blocks_total - self.blocks_pruned

    @property
    def is_fallback(self) -> bool:
        return self.sort_by is None


class LayoutSchedule:
    """A layout-aware plan for one ``where=`` predicate (PR 10).

    Built by ``CIFReader.schedule_layouts``: per split, every serveable
    replica copy (base + registered layouts) probed with the REAL planner,
    then ordered best-first — the HAIL cost step, picking ``(replica,
    host)`` jointly.  The decision rule: minimize ``(blocks_scanned,
    candidate_rows, chain_pos)``; ties go to the earlier chain position,
    so the insertion-order base copy (chain position 0) wins whenever
    sorting buys nothing — which guarantees the chosen copy never scans
    more blocks than the fallback.

    ``candidate_for(split, epoch)`` rotates through the preference chain
    on re-execution epochs: attempt-ladder exhaustion on the best copy
    requeues the split, and the next execution is served by the next
    replica — whose layout may differ — composing the PR 6 failover chain
    with heterogeneous layouts.  ``placement`` exposes the same chains to
    the WorkQueue so the executing host always holds the copy it reads.
    ``force(k)`` pins every split to chain position ``k`` (single-entry
    preference chains) — the differential harness's replica-forcing knob.
    """

    def __init__(
        self,
        root: str,
        where: Expr,
        base: Placement,
        prefs: Dict[int, List[LayoutCandidate]],
    ):
        self.root = root
        self.where = where
        self.base = base
        self.prefs = prefs

    def chosen(self, split_id: int) -> LayoutCandidate:
        return self.prefs[split_id][0]

    def fallback(self, split_id: int) -> LayoutCandidate:
        for c in self.prefs[split_id]:
            if c.is_fallback:
                return c
        raise AssertionError(
            f"split {split_id}: no insertion-order candidate in the "
            "preference chain (base copy unserveable?)"
        )

    def candidate_for(self, split_id: int, epoch: int) -> LayoutCandidate:
        pref = self.prefs[split_id]
        return pref[epoch % len(pref)]

    @property
    def placement(self) -> ScheduledPlacement:
        return ScheduledPlacement(
            self.base,
            {s: tuple(c.host for c in pref) for s, pref in self.prefs.items()},
        )

    def force(self, chain_pos: int) -> "LayoutSchedule":
        prefs: Dict[int, List[LayoutCandidate]] = {}
        for s, pref in self.prefs.items():
            match = [c for c in pref if c.chain_pos == chain_pos]
            assert match, (
                f"split {s}: no serveable candidate at chain position "
                f"{chain_pos}"
            )
            prefs[s] = match
        return LayoutSchedule(self.root, self.where, self.base, prefs)


class CIFReader:
    """Scans a COF dataset with projection pushdown.

    lazy=True  -> LazyRecord (paper §5; columns decode on first get())
    lazy=False -> EagerRecord (all projected columns decoded per record)
    """

    def __init__(
        self,
        root: str,
        columns: Optional[Sequence[str]] = None,
        lazy: bool = True,
        *,
        fault_plan: Optional[FaultPlan] = None,
        failure_policy: Optional[FailurePolicy] = None,
        cache: Optional[Any] = None,
    ):
        self.root = root
        self.schema = read_schema(root)
        self.columns = list(columns) if columns is not None else self.schema.names()
        for c in self.columns:
            assert c in self.schema, f"unknown column {c}"
        self.lazy = lazy
        self.fault_plan = fault_plan
        self.failure_policy = failure_policy
        # shared decoded-block cache (core.blockcache.BlockCache): scans
        # consult it before decoding and report the reuse in ScanStats;
        # outputs and all pre-cache counters stay bit-identical cache-off
        self.cache = cache
        self.stats = ScanStats()
        self._stats_lock = threading.Lock()

    # getSplits() analog — optionally restricted to an assigned subset so a
    # distributed scan can honor the placement policy (placement.py).
    def splits(self, split_ids: Optional[Sequence[int]] = None) -> List[Tuple[int, str]]:
        all_splits = list_splits(self.root)
        if split_ids is None:
            return all_splits
        want = set(split_ids)
        return [(i, d) for i, d in all_splits if i in want]

    def shard_splits(
        self,
        host: int,
        n_hosts: Optional[int] = None,
        placement: Optional[Placement] = None,
    ) -> List[Tuple[int, str]]:
        """The splits ``host`` primarily owns under the CPP analog.

        Disjoint across hosts and jointly exhaustive: the union of every
        host's shard is the full split list, each split exactly once, and
        each shard is local to its host by Placement's construction.
        """
        all_splits = list_splits(self.root)
        placement = placement or Placement(
            n_splits=len(all_splits), n_hosts=n_hosts if n_hosts is not None else 1
        )
        assert placement.n_splits == len(all_splits), "placement/dataset mismatch"
        assert 0 <= host < placement.n_hosts, (
            f"host {host} outside placement of {placement.n_hosts} hosts "
            "(a miswired host id would silently scan an empty shard)"
        )
        own = set(placement.splits_of(host))
        return [sd for idx, sd in enumerate(all_splits) if idx in own]

    def _scan_splits(
        self,
        split_ids: Optional[Sequence[int]],
        host: Optional[int],
        n_hosts: Optional[int],
        placement: Optional[Placement],
    ) -> List[Tuple[int, str]]:
        if host is None:
            return self.splits(split_ids)
        assert split_ids is None, "pass either split_ids or host/n_hosts, not both"
        return self.shard_splits(host, n_hosts, placement)

    def open_split(
        self,
        split_dir: str,
        extra_columns: Sequence[str] = (),
        lazy_open: bool = False,
        *,
        split_id: Optional[int] = None,
        placement: Optional[Placement] = None,
    ) -> SplitReader:
        cols = list(self.columns)
        for c in extra_columns:
            assert c in self.schema, f"unknown predicate column {c}"
            if c not in cols:
                cols.append(c)
        return SplitReader(split_dir, self.schema, cols, lazy_open=lazy_open,
                           project=self.columns, split_id=split_id,
                           placement=placement, fault_plan=self.fault_plan,
                           policy=self.failure_policy, cache=self.cache)

    def _where_columns(self, where: Expr) -> List[str]:
        cols = sorted(where.columns())
        assert cols, "where= predicate references no columns"
        validate_predicate(where, self.schema.type_of)
        return cols

    def absorb_stats(self, sr: SplitReader) -> None:
        """Fold a finished split's counters into ``stats`` (thread-safe, so
        concurrent per-host shard scans may share this reader)."""
        with self._stats_lock:
            sr.finish_stats(self.stats)

    def scan(
        self,
        split_ids: Optional[Sequence[int]] = None,
        *,
        host: Optional[int] = None,
        n_hosts: Optional[int] = None,
        placement: Optional[Placement] = None,
    ) -> Iterator[Record]:
        for idx, sdir in self._scan_splits(split_ids, host, n_hosts, placement):
            sr = self.open_split(sdir, split_id=idx, placement=placement)
            it = sr.iter_lazy() if self.lazy else sr.iter_eager()
            for rec in it:
                yield rec
            self.absorb_stats(sr)

    def scan_batches(
        self,
        batch_size: int = EAGER_CHUNK,
        split_ids: Optional[Sequence[int]] = None,
        *,
        host: Optional[int] = None,
        n_hosts: Optional[int] = None,
        placement: Optional[Placement] = None,
        where: Optional[Expr] = None,
    ) -> Iterator[Dict[str, Any]]:
        """Columnar scan: yields ``{column: values}`` dicts of up to
        ``batch_size`` records (arrays for numeric/bool columns, zero-copy
        ``RaggedColumn`` views for string/bytes, lists otherwise), with
        projection pushdown and ``ScanStats`` accounting identical to a
        record-at-a-time eager scan.  With ``host=`` (plus ``n_hosts=`` or
        ``placement=``) the scan covers only that host's CPP-local shard —
        per-host iterators partition the dataset exactly.

        ``where=`` pushes a predicate down the whole read path: splits then
        blocks are pruned via zone maps / dict pages / blooms, the
        predicate evaluates vectorized on only its own columns over the
        surviving ranges, and the remaining projected columns materialize
        for just the matching rows.  Batches then hold exactly the matching
        rows (possibly fewer than ``batch_size``; empty batches are never
        yielded), bit-identical to filtering an unpruned scan post hoc.
        """
        if where is None:
            for idx, sdir in self._scan_splits(split_ids, host, n_hosts, placement):
                sr = self.open_split(sdir, split_id=idx, placement=placement)
                for start in range(0, sr.n_records, batch_size):
                    yield sr.read_range(start, min(start + batch_size, sr.n_records))
                self.absorb_stats(sr)
            return
        pcols = self._where_columns(where)
        for idx, sdir in self._scan_splits(split_ids, host, n_hosts, placement):
            sr = self.open_split(sdir, extra_columns=pcols, lazy_open=True,
                                 split_id=idx, placement=placement)
            plan = sr.plan(where)
            for a, b in plan.ranges:
                for start in range(a, b, batch_size):
                    fb = sr.filter_span(where, start, min(start + batch_size, b))
                    if fb is not None:
                        yield {c: fb[c] for c in self.columns}
            self.absorb_stats(sr)

    # -- layout-aware scheduling (PR 10) -------------------------------------
    def schedule_layouts(self, where: Any, placement: Placement) -> LayoutSchedule:
        """The HAIL cost step: probe every replica copy of every split —
        the insertion-order base plus each layout registered in the split's
        ``_layout.json`` — with the real planner, and order the candidates
        best-first.

        Probes are throwaway lazy readers over the predicate columns only:
        they read zone maps / dict pages / blooms, never decode a cell, and
        their counters are DISCARDED (scheduling cost is not scan cost — a
        run's ScanStats stay comparable with and without a schedule).  A
        physically damaged copy fails its probe and drops out of the
        candidate list; injected (fault-plan) damage is invisible here and
        is handled at read time by the ladder + epoch rotation.  Splits are
        asserted to keep at least their base candidate — an unprobeable
        base copy is repair's problem, not the scheduler's.
        """
        pred = parse_predicate(where) if isinstance(where, str) else where
        pcols = self._where_columns(pred)
        tr = trace.live()
        prefs: Dict[int, List[LayoutCandidate]] = {}
        for idx, sdir in self.splits():
            chain = placement.replicas(idx)
            layouts = read_layouts(sdir)
            cands: List[LayoutCandidate] = []
            seen_base = False
            for pos, host in enumerate(chain):
                if host in layouts:
                    cdir = host_layout_dir(sdir, host)
                    sort_by: Optional[str] = layouts[host]["descriptor"].sort_by
                else:
                    if seen_base:
                        continue  # every layout-less host serves the same base
                    seen_base = True
                    cdir = sdir
                    sort_by = None
                try:
                    probe = SplitReader(
                        cdir, self.schema, pcols, lazy_open=True, split_id=idx
                    )
                    plan = probe.plan(pred)
                except (CorruptFileError, OSError):
                    continue  # damaged copy: not a candidate
                cands.append(LayoutCandidate(
                    host=host, sort_by=sort_by, dir=cdir,
                    blocks_total=plan.blocks_total,
                    blocks_pruned=plan.blocks_pruned,
                    candidate_rows=ranges_rows(plan.ranges),
                    chain_pos=pos,
                ))
            assert cands, (
                f"split {idx}: every replica copy failed its planning probe "
                "— run cif.repair before scheduling"
            )
            best = min(
                cands,
                key=lambda c: (c.blocks_scanned, c.candidate_rows, c.chain_pos),
            )
            prefs[idx] = [best] + [c for c in cands if c is not best]
            if tr is not None:
                fb = next((c for c in cands if c.is_fallback), None)
                tr.instant("layout.choose", {
                    "split": idx, "host": best.host, "sort_by": best.sort_by,
                    "blocks_scanned": best.blocks_scanned,
                    "candidate_rows": best.candidate_rows,
                    "fallback_blocks_scanned":
                        fb.blocks_scanned if fb is not None else None,
                    "candidates": len(cands),
                })
        return LayoutSchedule(self.root, pred, placement, prefs)

    def _open_candidate(
        self, split_id: int, cand: LayoutCandidate, pcols: Sequence[str]
    ) -> SplitReader:
        """A SplitReader over one candidate copy, pinned to its host: every
        attempt of the PR 6 ladder reads THIS host's copy (mixing sorted
        and insertion-order bytes mid-execution would interleave rows of
        different records), so failover to a differently-laid-out replica
        happens only between execution epochs via the schedule."""
        cols = list(self.columns)
        for c in pcols:
            if c not in cols:
                cols.append(c)
        return SplitReader(
            cand.dir, self.schema, cols, lazy_open=True, project=self.columns,
            split_id=split_id, placement=PinnedPlacement(cand.host),
            fault_plan=self.fault_plan, policy=self.failure_policy,
            cache=self.cache,
        )

    # -- MapReduce adapters (run_job inputs) ---------------------------------
    def job_inputs(
        self,
        batch_size: int = EAGER_CHUNK,
        *,
        where: Optional[Expr] = None,
        placement: Optional[Placement] = None,
        schedule: Optional[LayoutSchedule] = None,
    ) -> Tuple[List[int], Callable[[int], Iterator[BatchColumns]]]:
        """``(split_ids, open_split_batches)`` for batch-mode ``run_job``.

        Each task opens its own ``SplitReader`` (no shared mutable reader
        state between concurrent map tasks) and yields lazy ``BatchColumns``
        spans; stats absorption is serialized via ``absorb_stats``.

        With ``where=`` the spans arrive predicate-filtered
        (``FilteredBatchColumns``): splits/blocks prune against the zone
        maps before any decode, only the predicate columns of survivors are
        evaluated, and map functions see just the matching rows (empty
        spans are never yielded).  Equivalent to ``run_job(where=...)`` but
        saves opening the projection columns of fully-pruned splits.

        With ``schedule=`` (a ``schedule_layouts`` result; mutually
        exclusive with ``where=``, which the schedule embeds) each split is
        served from the replica copy its execution epoch maps to — the
        chosen layout on epoch 0, rotating down the preference chain on
        requeues — and yields exactly ONE canonical-order span per split
        (``SplitReader.filter_split``), so output and counters are
        bit-identical no matter which replica served.  Pair it with
        ``run_job(..., placement=schedule.placement)`` and NO ``where=``.
        """
        if schedule is not None:
            assert where is None, (
                "schedule= already embeds the predicate — don't pass where="
            )
            return self._layout_job_inputs(schedule)
        split_map = dict(self.splits())
        pcols = self._where_columns(where) if where is not None else ()

        def open_split_batches(split_id: int) -> Iterator[BatchColumns]:
            if where is None:
                sr = self.open_split(split_map[split_id], split_id=split_id,
                                     placement=placement)
                for start in range(0, sr.n_records, batch_size):
                    yield BatchColumns(sr, start, min(start + batch_size, sr.n_records))
            else:
                sr = self.open_split(
                    split_map[split_id], extra_columns=pcols, lazy_open=True,
                    split_id=split_id, placement=placement,
                )
                for a, b in sr.plan(where).ranges:
                    for start in range(a, b, batch_size):
                        fb = sr.filter_span(where, start, min(start + batch_size, b))
                        if fb is not None:
                            yield fb
            self.absorb_stats(sr)

        return sorted(split_map), open_split_batches

    def _layout_job_inputs(
        self, sched: LayoutSchedule
    ) -> Tuple[List[int], Callable[[int], Iterator[BatchColumns]]]:
        """The layout-aware ``(split_ids, open_split_batches)``: each
        execution opens the replica copy ``sched.candidate_for(split,
        current_epoch())`` names — so a requeued split's retry lands on the
        next replica in the preference chain, layouts and all — and yields
        one canonical-order span.  Attribution: the completing execution
        counts as a ``layout_best_choices`` when it was served by the
        schedule's top choice AND that choice is a sorted layout, else as a
        ``layout_fallbacks`` (insertion-order won the cost step, or
        failover rotated past the best copy)."""
        pred = sched.where
        pcols = self._where_columns(pred)
        split_ids = sorted(sched.prefs)

        def open_split_batches(split_id: int) -> Iterator[BatchColumns]:
            cand = sched.candidate_for(split_id, current_epoch())
            sr = self._open_candidate(split_id, cand, pcols)
            if cand is sched.prefs[split_id][0] and not cand.is_fallback:
                sr.layout_best_choices = 1
            else:
                sr.layout_fallbacks = 1
            fb = sr.filter_split(pred)
            if fb is not None:
                yield fb
            self.absorb_stats(sr)

        return split_ids, open_split_batches

    def job_records(
        self,
        *,
        where: Optional[Expr] = None,
        placement: Optional[Placement] = None,
    ) -> Tuple[List[int], Callable[[int], Iterator[Tuple[Any, Record]]]]:
        """``(split_ids, open_split)`` for record-at-a-time ``run_job`` —
        the compatibility path (lazy or eager per this reader's flag).

        ``where=`` filters records here, with the predicate VALIDATED
        against this reader's schema (``run_job(where=)`` also accepts a
        record-mode predicate but is schema-agnostic, so a type-mismatched
        literal there silently matches nothing — prefer passing it here).
        Lazy records decode only the referenced columns; map-key leaves
        ride the single-key ``get_map_value`` path.
        """
        if where is not None:
            self._where_columns(where)  # validates against the schema
        split_map = dict(self.splits())

        def open_split(split_id: int) -> Iterator[Tuple[Any, Record]]:
            sr = self.open_split(split_map[split_id], split_id=split_id,
                                 placement=placement)
            it = sr.iter_lazy() if self.lazy else sr.iter_eager()
            for rec in it:
                if where is None or where.matches_record(rec):
                    yield None, rec
            self.absorb_stats(sr)

        return sorted(split_map), open_split


# ---------------------------------------------------------------------------
# Corpus integrity: the public faces of core.repair (PR 7)
# ---------------------------------------------------------------------------


def fsck(root: str):
    """Audit-only integrity walk of the PHYSICAL corpus (base files plus
    any healed ``_replicas`` overlays): verify every committed split
    against its manifest (size + whole-file CRC per column file,
    structural parse of ``_meta.json``) and report damage without writing
    anything.  Returns a deterministic ``RepairReport``; a corpus a writer
    crashed into mid-split audits CLEAN — the torn build directory is
    invisible debris, not damage."""
    from .repair import fsck as _fsck  # late import: repair sits above cif

    return _fsck(root)


def repair(
    root: str,
    placement: Placement,
    *,
    fault_plan: Optional[FaultPlan] = None,
    queue: Optional[set] = None,
):
    """Scrub every replica copy (splits × ``placement.replicas``) through
    the same read seam jobs use — ``fault_plan`` included, so repair is
    testable under injected faults — classify each copy
    (clean / corrupt / torn / missing), re-replicate damaged copies
    byte-for-byte from a clean replica under the whole-file-CRC acceptance
    rule, and quarantine splits with zero clean copies.  ``queue=`` (a
    ``ScanStats.repair_queue``) restricts the scrub to the copies a scan
    observed corrupt — the read-repair drain.  Returns a ``RepairReport``.
    """
    from .repair import repair as _repair

    return _repair(root, placement, fault_plan=fault_plan, queue=queue)


# ---------------------------------------------------------------------------
# EXPLAIN: the planner's decision tree without decoding anything (PR 9)
# ---------------------------------------------------------------------------


@dataclass
class ColumnExplain:
    """One predicate column's block-prune verdict for one split."""

    column: str
    blocks_total: int
    blocks_pruned: int
    # {source-label: blocks pruned by it} — "zone-map" / "dict-page" /
    # "stats-tag" / "bloom" / "combined" (see ColumnFileReader.prune)
    sources: Dict[str, int]


@dataclass
class SplitExplain:
    split_id: int
    n_records: int
    # predicate columns whose _meta.json zone summary alone proved the
    # split dead (empty = the split survived to block planning)
    pruned_by_meta: List[str]
    blocks_total: int
    blocks_pruned: int
    columns: List[ColumnExplain]
    ranges: List[Tuple[int, int]]
    candidate_rows: int
    # layout-aware scheduling (PR 10; populated only by explain(placement=)
    # over a corpus with materialized layouts): which replica copy the
    # schedule chose for this split and the full candidate slate as
    # (host, sort_by, blocks_scanned) — sort_by None = the insertion-order
    # base copy.  The plan numbers above are THAT copy's, so
    # report.blocks_pruned matches the layout-aware scan's counter.
    layout_host: Optional[int] = None
    layout_sort_by: Optional[str] = None
    layout_candidates: List[Tuple[int, Optional[str], int]] = field(
        default_factory=list
    )


@dataclass
class ExplainReport:
    """What a ``where=`` scan WOULD do, derived purely from metadata.

    The numbers are exact, not estimates: ``blocks_pruned`` per split is
    the same memoized ``SplitReader.plan`` a real scan charges to
    ``ScanStats.blocks_pruned_stats``, so ``report.blocks_pruned`` equals
    the counter a subsequent scan reports.  ``stats`` are the explain
    pass's OWN ScanStats — ``bytes_decoded``/``cells_decoded`` are
    asserted zero, the "without decoding anything" guarantee.
    """

    root: str
    predicate: str
    projection: List[str]
    predicate_columns: List[str]
    late_columns: List[str]
    splits: List[SplitExplain]
    stats: ScanStats

    @property
    def splits_total(self) -> int:
        return len(self.splits)

    @property
    def splits_pruned(self) -> int:
        return sum(1 for s in self.splits if s.pruned_by_meta)

    @property
    def blocks_total(self) -> int:
        return sum(s.blocks_total for s in self.splits)

    @property
    def blocks_pruned(self) -> int:
        return sum(s.blocks_pruned for s in self.splits)

    @property
    def candidate_rows(self) -> int:
        return sum(s.candidate_rows for s in self.splits)

    @property
    def total_rows(self) -> int:
        return sum(s.n_records for s in self.splits)

    def source_totals(self) -> Dict[str, int]:
        """Aggregated prune attribution; meta-pruned splits' blocks are
        charged to "split-meta" (the ``_meta.json`` zone summary)."""
        out: Dict[str, int] = {}
        for s in self.splits:
            if s.pruned_by_meta:
                out["split-meta"] = out.get("split-meta", 0) + s.blocks_pruned
                continue
            for c in s.columns:
                for k, v in c.sources.items():
                    out[k] = out.get(k, 0) + v
        return out

    def format(self) -> str:
        lines = [
            f"EXPLAIN scan of {self.root}",
            f"  where: {self.predicate}",
            f"  projection: {', '.join(self.projection)}",
            f"  predicate columns (decoded over surviving ranges): "
            f"{', '.join(self.predicate_columns)}",
            f"  late-materialized (decoded only for matching rows): "
            f"{', '.join(self.late_columns) or '(none)'}",
            f"  splits: {self.splits_total} total, {self.splits_pruned} "
            f"pruned by _meta.json zone summary",
        ]
        src = ", ".join(f"{k} {v}" for k, v in sorted(self.source_totals().items()))
        lines.append(
            f"  blocks: {self.blocks_total} total, {self.blocks_pruned} "
            f"pruned ({src or 'nothing pruned'})"
        )
        lines.append(
            f"  candidate rows: {self.candidate_rows} of {self.total_rows}"
        )
        for s in self.splits:
            if s.pruned_by_meta:
                lines.append(
                    f"  split {s.split_id} ({s.n_records} rows): PRUNED by "
                    f"_meta.json zone summary "
                    f"[{', '.join(s.pruned_by_meta)}] — no column file opened"
                )
                continue
            lines.append(
                f"  split {s.split_id} ({s.n_records} rows): "
                f"{s.blocks_total} stats blocks, {s.blocks_pruned} pruned"
                f" -> {s.candidate_rows} candidate rows in "
                f"{len(s.ranges)} range(s)"
            )
            if s.layout_host is not None:
                slate = ", ".join(
                    f"h{h}:{sb or 'insertion-order'}={bs}blk"
                    for h, sb, bs in s.layout_candidates
                )
                lines.append(
                    f"      layout: host {s.layout_host} "
                    f"({s.layout_sort_by or 'insertion-order'}) "
                    f"chosen of [{slate}]"
                )
            for c in s.columns:
                csrc = ", ".join(
                    f"{k} {v}" for k, v in sorted(c.sources.items())
                )
                lines.append(
                    f"      {c.column}: {c.blocks_pruned}/{c.blocks_total} "
                    f"blocks pruned ({csrc or 'none'})"
                )
        lines.append(
            f"  explain decoded nothing: bytes_decoded="
            f"{self.stats.bytes_decoded}, cells_decoded="
            f"{self.stats.cells_decoded} "
            f"(files opened for metadata: {self.stats.files_opened})"
        )
        return "\n".join(lines)


def explain(
    root: str,
    where: Any,
    columns: Optional[Sequence[str]] = None,
    *,
    placement: Optional[Placement] = None,
) -> ExplainReport:
    """Render the planner's decision tree for ``where=`` over ``root``
    WITHOUT decoding a single cell.

    Runs the real planner — the same ``_meta.json`` stage-1 check and the
    same memoized ``SplitReader.plan`` a scan would use — then re-evaluates
    each pruned block against each stats source in isolation to attribute
    it (zone map / dict page / bloom / stats-tag).  ``where`` is an
    ``Expr`` or a ``parse_predicate`` string; ``columns`` the projection
    (defaults to the full schema).  The returned report's prune counts are
    exactly what a subsequent scan reports in ``blocks_pruned_stats``, and
    its own ``stats.bytes_decoded`` is asserted zero.

    With ``placement=`` the report is LAYOUT-AWARE (PR 10): the same
    ``schedule_layouts`` cost step a scheduled job runs picks each split's
    replica copy, the plan numbers are derived from THAT copy's zone maps,
    and each ``SplitExplain`` names the chosen ``(host, sort_by)`` plus
    the full candidate slate — so ``report.blocks_pruned`` equals the
    ``blocks_pruned_stats`` a clean ``job_inputs(schedule=...)`` run
    charges.
    """
    pred = parse_predicate(where) if isinstance(where, str) else where
    reader = CIFReader(root, columns=columns)
    pcols = reader._where_columns(pred)
    late = [c for c in reader.columns if c not in pcols]
    sched = (
        reader.schedule_layouts(pred, placement)
        if placement is not None else None
    )
    splits_expl: List[SplitExplain] = []
    for idx, sdir in reader.splits():
        chosen = sched.chosen(idx) if sched is not None else None
        sr = reader.open_split(
            chosen.dir if chosen is not None else sdir,
            extra_columns=pcols, lazy_open=True, split_id=idx,
        )
        # stage-1 re-derivation (mirrors SplitReader.plan): which predicate
        # columns' persisted zone summaries alone prove the split dead
        meta_dead: List[str] = []
        for name in pcols:
            z = sr._meta_zone(name)
            if not z:
                continue
            keys = z.get("keys")
            info = ColumnInfo(
                vmin=z.get("min"), vmax=z.get("max"),
                map_keys=frozenset(keys) if keys is not None else None,
            )
            if info.vmin is None and info.map_keys is None:
                continue
            if pred.tri(lambda nm, name=name, info=info:
                        info if nm == name else None) == TRI_NONE:
                meta_dead.append(name)
        plan = sr.plan(pred)  # THE accounting a real scan charges
        cols_expl: List[ColumnExplain] = []
        if not meta_dead:
            for name in pcols:
                src: Dict[str, int] = {}
                pr = sr.readers[name].prune(pred, column=name, sources=src)
                cols_expl.append(
                    ColumnExplain(name, pr.blocks_total, pr.blocks_pruned, src)
                )
        splits_expl.append(SplitExplain(
            split_id=idx,
            n_records=sr.n_records,
            pruned_by_meta=meta_dead,
            blocks_total=plan.blocks_total,
            blocks_pruned=plan.blocks_pruned,
            columns=cols_expl,
            ranges=list(plan.ranges),
            candidate_rows=ranges_rows(plan.ranges),
            layout_host=chosen.host if chosen is not None else None,
            layout_sort_by=chosen.sort_by if chosen is not None else None,
            layout_candidates=[
                (c.host, c.sort_by, c.blocks_scanned)
                for c in sched.prefs[idx]
            ] if sched is not None else [],
        ))
        reader.absorb_stats(sr)
    assert reader.stats.bytes_decoded == 0 and reader.stats.cells_decoded == 0, (
        "explain decoded data — the planner stopped being metadata-only"
    )
    return ExplainReport(
        root=root,
        predicate=repr(pred),
        projection=list(reader.columns),
        predicate_columns=list(pcols),
        late_columns=late,
        splits=splits_expl,
        stats=reader.stats,
    )
