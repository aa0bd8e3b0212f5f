"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, client loop and per-layer metric
readers are found by name (``spec.py``). A run refuses any device but a TPU,
and fewer chips than the cell asks for. It writes the cell's corpus from the
seed through the program's writer, warms up every shape its window uses,
measures for ``--seconds``, and then checks what the window delivered against
the corpus made again from the seed. A program compiled inside the window
makes the run not correct. With ``--trace 1`` the window is traced and the
line holds the per-layer metrics; otherwise it holds the end-to-end metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import corpus as corpus_mod  # noqa: E402
from bench import reference, spec, stats, tracing  # noqa: E402
from bench.window import Window  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``.jax_cache`` at the checkout's root (a fixed path: it is part of the
    cache's key). Every program is kept, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip_devices(chips: int, require_chip: bool) -> List:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                     f"this benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds {len(devs)}")
    return devs[:chips]


def split_facts(corpus) -> Dict:
    """Dictionary lengths and code widths of the splits, as written."""
    lens, bits = [], set()
    for sid in corpus.split_ids():
        sp = corpus.open_split(sid)
        lens.append(len(sp.dictionary))
        bits.add(int(sp.bits))
    return {"dict_len_mean": sum(lens) / len(lens), "dict_len_min": min(lens),
            "dict_len_max": max(lens), "bits": max(bits), "bits_all": sorted(bits)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, loop: Optional[Callable] = None,
             root: str = ROOT, compile_cache: bool = True) -> Dict:
    """One run of one cell; returns the result line's object. ``loop``
    replaces the ``run`` of the traffic's loop (the control and the fault
    tests); ``root`` is the checkout whose ``BENCHMARK.json`` and files name
    the cell; the tests run on the CPU without the persistent compile
    cache."""
    cell = spec.find_cell(workload, root=root)
    cfg, traffic = cell.config, cell.traffic
    client = spec.load_loop(traffic["loop"], root)
    e2e = dict(client.END_TO_END, setup_s=lambda r: r["setup_s"])
    missing = [m.name for m in cell.end_to_end if m.name not in e2e]
    if missing:
        raise spec.SpecError(f"loop {traffic['loop']!r} gives no {missing}")
    readers = {m.name: spec.load_reader(m.name, root) for m in cell.per_layer}
    import jax

    cache_dir = use_compile_cache() if compile_cache else None
    devices = chip_devices(cell.chips, require_chip)
    compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, d, **kw: compiles.append(name) if name in COMPILE_EVENTS else None)
    from repro.data.tokens import TokenCorpus

    work = tempfile.mkdtemp(prefix="bench-")
    try:
        corpus_dir = os.path.join(work, "corpus")
        wrote = corpus_mod.write_corpus(cfg, seed, corpus_dir)
        corpus = TokenCorpus(corpus_dir)
        facts = split_facts(corpus)
        win = Window(seconds, os.path.join(work, "trace") if trace else None)
        run = loop or client.run
        at_open: List[int] = []
        win.on_open = lambda: at_open.append(len(compiles))
        res = run(cfg, traffic, corpus, seed, win, devices)
        res["setup_s"] = res["setup_end"] - T_START
        compiles_in_window = len(compiles) - at_open[0]
        mem = [d.memory_stats() or {} for d in devices]
        peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
        events = tracing.load_events(win.trace_dir) if trace else None

        # the check, once the window's state is freed
        t_ref = time.perf_counter()
        splits = corpus_mod.reference_corpus(cfg, seed)
        counts = client.check(res, splits, cfg)
        counts["compiles_in_window"] = compiles_in_window
        correct, checks = reference.verdict(counts, dict(client.LIMITS, compiles_in_window=0))
        gc.collect()
        ref_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work, ignore_errors=True)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out: Dict = {"correct": bool(correct), "attempted": int(res["attempted"]),
                 "failed": int(res["failed"]), "metrics": {}, "device": device}
    if trace:
        busy_s, window_s = tracing.busy_seconds(events, len(devices))
        device["busy_s"], device["window_s"] = busy_s, window_s
        ctx = dict(res, **facts, events=events, busy_s=busy_s, window_s=window_s,
                   device_kind=d0.device_kind, n_devices=len(devices))
        for m in cell.per_layer:
            v = readers[m.name](ctx)
            if v is not None:
                out["metrics"][m.name] = {"value": float(v), "unit": m.unit}
        out["breakdown"] = {"device_ops": tracing.top_ops(events),
                            "idle_gaps": tracing.idle_by_host(events)}
    else:
        for m in cell.end_to_end:
            out["metrics"][m.name] = {"value": stats.finite(e2e[m.name](res)),
                                      "unit": m.unit}
    out["checks"] = checks

    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)}")
    log(f"corpus: {wrote['n_sequences']} sequences, tokens.col {wrote['tokens_col_bytes']} B, "
        f"written in {wrote['write_s']!r} s; code bits {facts['bits_all']}, dictionary "
        f"{facts['dict_len_min']}-{facts['dict_len_max']} entries")
    log(f"compile cache {cache_dir}; compiles in window: {compiles_in_window}")
    log(f"window {res['window_s']!r} s, attempted {res['attempted']}, failed {res['failed']}; "
        f"setup_s {res['setup_s']!r}; check {ref_s!r} s")
    log(f"counters {res['counters']} over {res['tokens_made']} tokens made")
    if "batches_by_tenth" in res:
        log(f"batches in each tenth of the window {res['batches_by_tenth']}")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"no program under {os.path.join(ROOT, 'src')}: run from a checkout")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
