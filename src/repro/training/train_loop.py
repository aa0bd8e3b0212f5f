"""Training driver: columnar pipeline -> pjit train step -> async checkpoints.

Fault tolerance: every run begins with `Checkpointer.latest_step()`; if a
checkpoint exists (including one written by a run that was later killed),
state AND data order resume from it.  Kill the process at any point and
rerun the same command — tested in tests/test_training.py.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from ..configs.base import ModelConfig, ShapeConfig
from ..data.pipeline import HostPipeline, PipelineState
from ..distributed.sharding import ShardingConfig, named
from ..distributed.steps import StepOptions, build_train_step, init_state, state_pspecs
from .checkpoint import Checkpointer


@dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    max_keep: int = 3
    seed: int = 0


def fit(
    cfg: ModelConfig,
    mesh: Mesh,
    sh: ShardingConfig,
    shape: ShapeConfig,
    pipeline: HostPipeline,
    loop: TrainLoopConfig,
    opts: StepOptions = StepOptions(),
    on_metrics: Optional[Callable[[int, Dict[str, float]], None]] = None,
) -> Dict[str, Any]:
    step_fn, (sp, bp) = build_train_step(cfg, sh, mesh, shape, opts)
    state_sh = named(sp, mesh)
    batch_sh = named(bp, mesh)
    if pipeline.decode == "device":
        # each chip decodes its own rows: batches arrive on the chips, placed
        pipeline.sharding = batch_sh["tokens"]

    ckpt = Checkpointer(loop.ckpt_dir, loop.max_keep) if loop.ckpt_dir else None
    start = 0
    state = None
    if ckpt is not None and ckpt.latest_step() is not None:
        from ..distributed.steps import abstract_state

        start, state, data_state = ckpt.restore(abstract_state(cfg), shardings=state_sh)
        if data_state:
            pipeline.sampler.state = PipelineState.from_json(data_state).sampler
        print(f"[restore] resumed from step {start}")
    if state is None:
        # built straight into its shardings: no one device holds the whole
        # state on the way (a 1B model's float32 state and Adam moments are
        # most of a chip)
        with mesh:
            state = jax.jit(partial(init_state, cfg), out_shardings=state_sh)(
                jax.random.PRNGKey(loop.seed)
            )

    history = []
    it = iter(pipeline)
    compiled = None
    compile_s = 0.0
    for step in range(start, loop.steps):
        # host arrays are copied to the chips; arrays already placed under
        # ``batch_sh`` are passed through as they are
        batch = jax.device_put(
            {k: v for k, v in next(it).items() if k in ("tokens", "labels", "loss_mask")},
            batch_sh,
        )
        if compiled is None:
            # compile before the first step, so compile time is reported
            # apart from step times
            tc = time.perf_counter()
            with mesh:
                compiled = step_fn.lower(state, batch).compile()
            compile_s = time.perf_counter() - tc
            t0 = time.perf_counter()
        with mesh:
            state, metrics = compiled(state, batch)
        if (step + 1) % loop.log_every == 0 or step == start:
            jax.block_until_ready(state)
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step + 1
            # seconds since compilation ended, up to this step's completion
            m["wall_s"] = time.perf_counter() - t0
            history.append(m)
            if on_metrics:
                on_metrics(step + 1, m)
            else:
                print(
                    f"step {step+1:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                    f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e}"
                )
        if ckpt is not None and (step + 1) % loop.ckpt_every == 0:
            ckpt.save_async(step + 1, state, pipeline.consumed_state().to_json())
    if ckpt is not None:
        ckpt.save_async(loop.steps, state, pipeline.consumed_state().to_json())
        ckpt.wait()
    pipeline.stop()
    return {"state": state, "history": history, "compile_s": compile_s, "compiled": compiled}
