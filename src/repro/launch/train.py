"""Training launcher.

Single-process CPU demo runs use --host-mesh; on a real pod this script is
launched once per host (jax.distributed handles process groups) with the
production mesh.  XLA latency-hiding flags are set for TPU targets.

Example (CPU, tiny model, full stack: columnar corpus -> pipeline -> pjit):
    PYTHONPATH=src python -m repro.launch.train \
        --corpus /tmp/corpus --arch tinyllama-1.1b --reduced \
        --steps 50 --batch 8 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--corpus", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="per-host batch")
    ap.add_argument("--seq-len", type=int, default=0, help="0 = corpus seq len")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--decode", default="np", choices=["py", "np", "device"],
                    help="token decode path of the input pipeline; 'device' "
                         "decodes each chip's rows of a batch on that chip")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (requires 256 devices)")
    ap.add_argument("--tpu-flags", action="store_true",
                    help="set XLA latency-hiding scheduler flags (TPU)")
    args = ap.parse_args()

    if args.tpu_flags:
        os.environ.setdefault(
            "LIBTPU_INIT_ARGS",
            "--xla_tpu_enable_latency_hiding_scheduler=true "
            "--xla_tpu_enable_async_collective_fusion=true",
        )

    import dataclasses

    import jax

    from ..configs.base import ShapeConfig, get_config, reduced
    from ..data.pipeline import HostPipeline
    from ..data.tokens import TokenCorpus
    from ..distributed.sharding import default_sharding
    from ..training.train_loop import TrainLoopConfig, fit
    from .compile_cache import enable_compile_cache
    from .mesh import make_host_mesh, make_production_mesh

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    corpus = TokenCorpus(args.corpus)
    first = corpus.open_split(corpus.split_ids()[0])
    seq_len = args.seq_len or first.seq_len
    corpus_vocab = corpus.vocab_size or int(first.dictionary.max()) + 1
    if cfg.vocab_size < corpus_vocab:
        cfg = dataclasses.replace(cfg, vocab_size=corpus_vocab)

    mesh = (
        make_production_mesh() if args.production_mesh
        else make_host_mesh(model=args.model_parallel)
    )
    sh = default_sharding(cfg)
    shape = ShapeConfig("train", seq_len, args.batch, "train")
    pipeline = HostPipeline(corpus, batch_per_host=args.batch, decode=args.decode)
    loop = TrainLoopConfig(
        steps=args.steps, ckpt_every=args.ckpt_every,
        log_every=args.log_every, ckpt_dir=args.ckpt_dir,
    )
    out = fit(cfg, mesh, sh, shape, pipeline, loop)
    print(f"done: {len(out['history'])} log points; final loss "
          f"{out['history'][-1]['loss']:.4f}" if out["history"] else "done")


if __name__ == "__main__":
    main()
