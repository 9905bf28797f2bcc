"""Training launcher: fault-tolerant loop with checkpoint/auto-resume.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --steps 50 \\
      --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container use --reduced (smoke-sized config); on a TPU fleet
drop --reduced and the production mesh is built from the visible devices.
``--optimizer newton_pcg`` trains with the paper's deep-pipelined CG as a
second-order method (the technique as a first-class training feature).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCHS, get_config, get_reduced
from repro.launch.mesh import make_mesh_for
from repro.launch.steps import build_train_step
from repro.models import init_params, loss_fn
from repro.models import sharding as shd
from repro.training import (AdamWConfig, CheckpointManager, NewtonPCGConfig,
                            NewtonPCGTrainer, Prefetcher, StragglerMonitor,
                            adamw_init)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adamw8bit", "newton_pcg"])
    ap.add_argument("--pipeline-depth", default="2",
                    help="p(l)-CG depth for newton_pcg: an int, or 'auto' "
                         "to calibrate against measured HVP latency")
    ap.add_argument("--inner-comm", default=None,
                    choices=["blocking", "overlap", "ring", "auto"],
                    help="reduction policy of the newton_pcg inner solve "
                         "on a mesh")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    ndev = len(jax.devices())
    mesh = (make_mesh_for(ndev, model_parallel=args.model_parallel)
            if ndev > 1 else None)
    if mesh is not None and args.optimizer != "newton_pcg":
        # newton_pcg keeps the global sharding context UNSET: its GGN
        # mesh operator runs the model shard-locally inside shard_map
        # (where global sharding constraints cannot apply) and shards
        # the flat parameter vector along the FSDP axis itself
        shd.set_mesh(mesh)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    monitor = StragglerMonitor(
        heartbeat_path=(f"{args.ckpt_dir}/heartbeat.json"
                        if args.ckpt_dir else None))
    start_step = 0

    if args.optimizer == "newton_pcg":
        depth = (args.pipeline_depth if args.pipeline_depth == "auto"
                 else int(args.pipeline_depth))
        ncfg = NewtonPCGConfig(l=depth, lr=args.lr)
        lf = lambda p, b: loss_fn(cfg, p, b, remat=args.remat)  # noqa: E731
        trainer = NewtonPCGTrainer(lf, ncfg, mesh=mesh,
                                   comm=args.inner_comm, monitor=monitor)
        step_fn = trainer.step
        opt_state = None
        if ckpt and ckpt.latest_step() is not None:
            start_step, tree, _ = ckpt.restore()
            params = tree["params"]
            print(f"resumed from step {start_step}")
    else:
        ocfg = AdamWConfig(lr=args.lr,
                           eightbit=args.optimizer == "adamw8bit")
        opt_state = adamw_init(params, ocfg)
        train_step = build_train_step(cfg, ocfg, remat=args.remat,
                                      microbatches=args.microbatches)
        step_fn = jax.jit(train_step)
        if ckpt and ckpt.latest_step() is not None:
            start_step, tree, _ = ckpt.restore()
            params, opt_state = tree["params"], tree["opt"]
            print(f"resumed from step {start_step}")

    pf = Prefetcher(cfg, args.batch, args.seq, start_step=start_step,
                    seed=args.seed)
    it = iter(pf)
    try:
        for _ in range(args.steps - start_step):
            step, batch = next(it)
            t0 = time.time()
            if args.optimizer == "newton_pcg":
                params, stats = step_fn(params, batch)
                loss = float(stats["loss"])
            else:
                params, opt_state, aux = step_fn(params, opt_state, batch)
                loss = float(aux["loss"])
            dt = time.time() - t0
            slow = monitor.record(step, dt)
            print(f"step {step:5d} loss {loss:9.4f} {dt*1e3:8.1f} ms"
                  + ("  [straggler]" if slow else ""), flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                tree = {"params": params}
                if opt_state is not None:
                    tree["opt"] = opt_state
                ckpt.save_async(step + 1, tree)
        if ckpt:
            tree = {"params": params}
            if opt_state is not None:
                tree["opt"] = opt_state
            ckpt.wait()
            ckpt.save(args.steps, tree)
    finally:
        pf.close()
    print(f"done: {args.steps} steps, mean {monitor.mean_step_s*1e3:.1f} "
          f"ms/step, stragglers flagged: {monitor.flagged}")
    return params


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
