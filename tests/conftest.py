import os
import sys

# smoke tests and benches must see ONE device; only launch/dryrun.py (run as
# a separate process) sets the 512-device flag.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import random

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def rnd():
    return random.Random(0)


def make_crawl_records(n, seed=0, content_bytes=256):
    from repro.launch.load_data import synth_crawl_records

    return list(synth_crawl_records(n, seed=seed, content_bytes=content_bytes))


def profiled_host_events(log_dir, fn):
    """``(fn(), host events)``: the events of the host planes of a
    ``jax.profiler`` capture taken around ``fn``, each with its plane and
    line, name and start and end in nanoseconds."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # annotations only, no Python call tracing
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)[0]
    events = [dict(line=(plane.name, line.name), name=ev.name, t0=ev.start_ns,
                   t1=ev.start_ns + ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for ev in line.events]
    return out, events
