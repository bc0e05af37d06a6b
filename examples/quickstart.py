"""Quickstart: the LUMORPH stack in five minutes (CPU-only friendly).

1. model a LIGHTPATH rack and allocate two tenants (no fragmentation),
2. build + validate a LUMORPH-4 circuit schedule for tenant 1's ALLREDUCE,
3. price it with the α–β model vs Ring on an ideal electrical switch,
4. run the *executable* LUMORPH collectives on 8 simulated devices and
   check exactness vs psum,
5. train a tiny LM for a few steps with LUMORPH gradient collectives.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import cost_model as cm
from repro.core.collectives import make_all_reduce
from repro.core.rack import default_rack
from repro.core.allocator import LumorphAllocator
from repro.core.scheduler import build_schedule, fiber_demand, order_for_locality


def main():
    # -- 1. rack + tenants ---------------------------------------------------
    # LUMORPH-4's high-stride rounds open up to 2·(chips/server)·(r−1)
    # circuits across a server pair — provision fibers accordingly (§3:
    # "given enough fibers between servers").
    rack = default_rack(n_chips=64, tiles_per_server=8,
                        fibers_per_server_pair=32)
    alloc = LumorphAllocator(64, tiles_per_server=8)
    t1 = alloc.allocate("tenant-1", 16)
    t2 = alloc.allocate("tenant-2", 6)  # non-power-of-two: Ring tenant
    print(f"tenant-1 chips: {t1.chips}")
    print(f"tenant-2 chips: {t2.chips} (6 chips → Ring ALLREDUCE)")

    # -- 2. circuit schedule ---------------------------------------------------
    chips = order_for_locality(t1.chips, tiles_per_server=8)
    sched = build_schedule("lumorph4", chips, n_bytes=8 << 20)
    sched.validate(rack)
    print(f"LUMORPH-4 over 16 chips: {len(sched.rounds)} rounds, "
          f"{sched.reconfigurations()} MZI reconfigurations, "
          f"peak fiber demand {fiber_demand(sched, 8)}/pair")

    # -- 3. α–β pricing --------------------------------------------------------
    ours = sched.cost(cm.LUMORPH_LINK)
    ring = cm.algorithm_cost("ring", 8 << 20, 16, cm.IDEAL_SWITCH)
    print(f"8MB ALLREDUCE: LUMORPH-4 {ours*1e6:.1f}µs vs ideal-switch Ring "
          f"{ring*1e6:.1f}µs → {1 - ours/ring:.0%} faster")

    # -- 4. executable collectives --------------------------------------------
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = np.random.RandomState(0).randn(8, 1000).astype(np.float32)
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data", None)))
    for algo in ("ring", "lumorph2", "lumorph4"):
        out = make_all_reduce(mesh, "data", algo)(xs)
        ok = np.allclose(np.asarray(out)[0], x.sum(0), rtol=1e-5, atol=1e-5)
        print(f"executable {algo:9s} == psum: {ok}")

    # -- 5. tiny training run --------------------------------------------------
    from repro.launch.train import main as train_main
    print("\ntraining bert-large (smoke config) with LUMORPH-4 gradients …")
    train_main(["--arch", "bert-large", "--smoke", "--steps", "10",
                "--batch", "8", "--seq", "64", "--comm", "lumorph4",
                "--data-parallel", "8", "--log-every", "5"])


if __name__ == "__main__":
    main()
