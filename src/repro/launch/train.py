"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

The end-to-end driver (deliverable b): builds the model, the sharding
policy, the LUMORPH gradient-communication backend, the deterministic data
stream, and runs a checkpointed training loop with automatic restart from
the latest checkpoint.  ``--smoke`` selects the reduced config for CPU
runs; without it the published widths run (``chip_smoke.py`` drives
BERT-large this way on one TPU v5e chip).

Example (paper's regime — BERT, data-parallel, LUMORPH-4 collectives):
  PYTHONPATH=src python -m repro.launch.train --arch bert-large --smoke \
      --comm lumorph4 --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, stream
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.adamw import AdamWConfig
from repro.sharding.policy import make_policy


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--comm", default="xla",
                    choices=["xla", "ring", "lumorph2", "lumorph4", "auto"])
    ap.add_argument("--compress", action="store_true", help="int8 grad collectives")
    ap.add_argument("--overlap", type=int, default=1, metavar="CHUNKS",
                    help="chunked/pipelined grad collectives: split every "
                         "bucket into CHUNKS waves overlapped with compute "
                         "(LUMORPH backends only; 1 = monolithic)")
    ap.add_argument("--bucket-mb", type=int, default=25)
    ap.add_argument("--wire-dtype", default="bfloat16",
                    choices=["bfloat16", "float32"],
                    help="gradient collective payload dtype")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="host mesh dp width (0 = all devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh == "host":
        dp = args.data_parallel or jax.device_count()
        mesh = make_host_mesh(data=dp, model=jax.device_count() // dp)
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    policy = make_policy(cfg, mesh)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 20))
    import jax.numpy as jnp
    if args.overlap > 1 and args.comm == "xla":
        raise SystemExit("--overlap needs a LUMORPH comm backend "
                         "(ring/lumorph2/lumorph4/auto), not xla")
    train_step = steps_lib.make_train_step(
        cfg, policy, opt_cfg, comm=args.comm,
        bucket_bytes=args.bucket_mb * 1024 * 1024, compress=args.compress,
        wire_dtype=jnp.dtype(args.wire_dtype), overlap_chunks=args.overlap)

    rng = jax.random.PRNGKey(args.seed)
    params, opt_state = steps_lib.init_sharded_state(
        cfg, policy, rng, init_ef=args.compress and args.comm != "xla")

    start_step = 0
    if args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start_step = ckpt_lib.restore(
            args.ckpt_dir, (params, opt_state))
        print(f"[train] restored checkpoint at step {start_step}", flush=True)

    data = DataConfig(seed=args.seed, global_batch=args.batch, seq_len=args.seq)
    losses, step_s = [], []
    t_start = time.time()
    for step, batch in stream(cfg, data, start_step):
        if step >= args.steps:
            break
        t0 = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, batch)
        losses.append(float(loss))  # float() waits for the step to finish
        step_s.append(time.perf_counter() - t0)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step:5d} loss={float(loss):.4f} "
                  f"({(time.time()-t_start)/max(step-start_step+1,1):.2f}s/step)",
                  flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt_lib.save(args.ckpt_dir, step + 1, (params, opt_state))
    result = {"final_loss": losses[-1] if losses else None,
              "first_loss": losses[0] if losses else None,
              "steps": len(losses), "comm": args.comm,
              "overlap": args.overlap, "losses": losses, "step_s": step_s,
              # devices that hold the trained parameters
              "param_devices": len(set().union(
                  *(leaf.sharding.device_set for leaf in jax.tree.leaves(params))))}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    use_compile_cache()
    main()
