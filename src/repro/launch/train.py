"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch fairsquare-demo \
        --steps 200 --global-batch 8 --seq 256 --ckpt-dir /tmp/fs_ckpt

Auto-resumes from the newest checkpoint in --ckpt-dir.  The run is one
process on one device: it builds no mesh and shards nothing, whatever the
number of visible devices (``launch/dryrun.py`` compiles the sharded
production layout, but no training run uses it yet).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import build_model
from repro.obs import trace as obs_trace
from repro.obs.export import write_chrome_trace
from repro.optim import adamw
from repro.train import step as step_mod
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fairsquare-demo")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--matmul-mode", default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale reduction of --arch")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    # observability (docs/observability.md)
    ap.add_argument("--metrics-file", default=None,
                    help="write the trainer's registry snapshot (step "
                         "counters/latency percentiles, checkpoint commit "
                         "events, contraction audit) as JSON")
    ap.add_argument("--trace-out", default=None,
                    help="enable structured tracing and write a Chrome "
                         "trace_event JSON (Perfetto-loadable)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_out:
        obs_trace.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.matmul_mode:
        cfg = dataclasses.replace(cfg, matmul_mode=args.matmul_mode)

    model = build_model(cfg)
    print(f"arch={cfg.name} params={model.n_params():,} "
          f"(active {model.n_active_params():,}) mode={cfg.matmul_mode}")

    params = model.init(jax.random.PRNGKey(0))
    opt_state = adamw.adamw_init(params)
    tcfg = step_mod.TrainConfig(
        opt=adamw.AdamWConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                              total_steps=args.steps),
        microbatch=args.microbatch,
        grad_compression=args.grad_compression)
    train_step = jax.jit(step_mod.make_train_step(model, tcfg),
                         donate_argnums=(0, 1))

    data = SyntheticLM(DataConfig(global_batch=args.global_batch,
                                  seq_len=args.seq, vocab=cfg.vocab), cfg)
    trainer = Trainer(TrainerConfig(total_steps=args.steps,
                                    ckpt_every=args.ckpt_every,
                                    ckpt_dir=args.ckpt_dir),
                      train_step, params, opt_state, data)
    if trainer.maybe_resume():
        print(f"resumed from step {trainer.step}")
    out = trainer.run()
    for m in out["metrics"][-5:]:
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in m.items()})
    print(f"done at step {out['final_step']} "
          f"(stragglers observed: {len(out['stragglers'])})")
    if args.metrics_file:
        with open(args.metrics_file, "w") as f:
            json.dump(trainer.obs_snapshot(), f, indent=1, sort_keys=True)
        print(f"metrics snapshot -> {args.metrics_file}")
    if args.trace_out:
        tr = obs_trace.get_tracer()
        write_chrome_trace(tr, args.trace_out)
        print(f"trace -> {args.trace_out} ({len(tr.records())} records, "
              f"{tr.dropped} dropped)")
    return out


if __name__ == "__main__":
    main()
