"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \\
        --steps 100 --workdir runs/granite --trace

Runs the reduced (smoke-scale) config of the chosen architecture on the
local devices — the full configs are exercised via the dry-run
(`repro.launch.dryrun`); at real TPU scale this same entry point receives
the full config plus a mesh (the Trainer is mesh-agnostic).  Auto-resumes
from the newest checkpoint in --workdir, installs the preemption handler,
and (with --trace) writes Paraver + Chrome traces beside the checkpoints.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

from repro import core as xtrace
from repro.configs import all_arch_names, get_config, reduced
from repro.configs.base import ShapeSpec, TrainConfig
from repro.launch.cache import use_compile_cache
from repro.train.trainer import Trainer


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-8b", choices=all_arch_names())
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--workdir", default="runs/default")
    p.add_argument("--checkpoint-every", type=int, default=25)
    p.add_argument("--trace", action="store_true")
    p.add_argument("--sample-hz", type=float, default=0.0,
                   help="statistical sampler frequency (0 = off)")
    p.add_argument("--full-config", action="store_true",
                   help="use the full architecture config (TPU-scale!)")
    args = p.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced(cfg)
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 1),
                       checkpoint_every=args.checkpoint_every)

    tracer = xtrace.init(f"train-{args.arch}") if args.trace else None
    if tracer and args.sample_hz > 0:
        tracer.start_sampler(period_s=1.0 / args.sample_hz,
                             jitter_s=0.2 / args.sample_hz)

    trainer = Trainer(cfg, tcfg, shape, args.workdir, tracer=tracer)
    trainer.install_preemption_handler()
    hist = trainer.run(args.steps)

    print(f"[train] {args.arch}: {trainer.model.param_count() / 1e6:.1f}M params, "
          f"{len(hist)} steps, loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    print(f"[train] checkpoints: {trainer.ckpt.all_steps()} in {args.workdir}/ckpt")
    if tracer:
        trace = xtrace.finish()
        out = pathlib.Path(args.workdir)
        paths = xtrace.write_prv(trace, out / "trace")
        xtrace.write_chrome_trace(trace, out / "trace.chrome.json")
        print(f"[train] trace: {paths['prv']}  ({trace.summary()})")
        if args.sample_hz > 0:
            from repro.core.folding import fold

            prof = fold(trace)
            print(f"[train] folded profile over {prof.num_instances} steps, "
                  f"{prof.num_samples} samples; top functions:")
            for name, frac in prof.top_functions():
                print(f"    {frac * 100:5.1f}%  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
