"""Train-loop driver: one training cell, set up, checked and timed.

The step sequence is the one ``repro.launch.train`` runs: the jitted step
from ``steps.make_train_step`` over parameters and optimizer state placed
by the sharding policy (as ``steps.init_sharded_state`` places them), one
seeded Zipf batch per step made on the host, and the loss read on the
host after every step.  The weights are the benchmark's own, drawn on the
device from the seed (``reference.common.init_tree``), so the reference
can draw the same ones without touching the program.

Set-up runs the cell's first ``check.steps`` steps through that same step
and feed, reading the losses, the first gradient (from Adam's first
moment after step 1) and the parameters' change after the last of them.
Those steps also compile every program the window uses.  After the window
the program's state is freed and the float32 reference repeats the same
steps from the same seed; :func:`compare` turns the two into the numbers
that decide ``correct``.
"""

from __future__ import annotations

import contextlib
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import F32, init_tree, layout_shapes, leaf_norms, seed_key, train_readings

SPAN = "chipbench."


def zipf_tokens(seed: int, step: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """The global batch for ``step``: a copy of ``repro.data.pipeline.batch_at``
    (a seeded log-uniform rank mixture over a seeded vocabulary permutation)."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, step]))
    ranks = rng.permutation(vocab)
    u = rng.random((batch, seq))
    zipf = (vocab ** u - 1) / (vocab - 1)
    return ranks[np.clip((zipf * vocab).astype(np.int64), 0, vocab - 1)]


def annotate(on: bool):
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


class TrainCell:
    """The program's train step and state for one cell, plus its reference."""

    def __init__(self, config: dict, traffic: dict, chips: int, family):
        from repro.configs.base import ModelConfig
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as tf
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.sharding.policy import make_policy

        self.model = config["model"]
        self.traffic = traffic
        self.family = family
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        self.cfg = ModelConfig(name=config["name"], **self.model)
        self.opt = traffic["optimizer"]
        mesh = make_host_mesh(data=chips, model=1)
        policy = make_policy(self.cfg, mesh)
        self.step = steps_lib.make_train_step(
            self.cfg, policy, AdamWConfig(**self.opt), comm=traffic["comm"],
            bucket_bytes=traffic["bucket_mb"] * 1024 * 1024,
            wire_dtype=jnp.dtype(traffic["wire_dtype"]),
            overlap_chunks=traffic["overlap"])
        shapes = tf.param_shapes(self.cfg)
        self.layout = family.layout(self.model)
        want = jax.tree.map(lambda s: tuple(s.shape), shapes)
        if want != layout_shapes(self.layout):
            raise SystemExit("chipbench: the program's parameter tree no longer "
                             "matches the reference layout of this family")
        p_sh = jax.tree.map(policy.named, policy.param_specs(shapes))
        o_sh = jax.tree.map(policy.named,
                            policy.opt_specs(steps_lib.opt_shapes(self.cfg, shapes)))
        layout = self.layout
        self._init = jax.jit(lambda key: init_tree(layout, key), out_shardings=p_sh)
        self._opt_init = jax.jit(init_opt_state, out_shardings=o_sh)
        b1 = self.opt["b1"]
        self._m_norms = jax.jit(
            lambda m: {k: v / (1 - b1) for k, v in leaf_norms(m).items()})
        self._change = jax.jit(lambda p, key: leaf_norms(
            jax.tree.map(jnp.subtract, p, init_tree(layout, key))))
        self.params = self.opt_state = None

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def feed(self, seed: int, i: int) -> dict:
        toks = zipf_tokens(seed, i, self.batch, self.seq, self.model["vocab_size"])
        return {"tokens": jnp.asarray(toks, jnp.int32)}

    def check_steps(self, seed: int) -> dict:
        """Make the state from ``seed`` and run the first steps; their readings."""
        key = seed_key(seed)
        self.params = self._init(key)
        self.opt_state = self._opt_init(self.params)
        losses, grads = [], None
        for i in range(self.traffic["check"]["steps"]):
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, self.feed(seed, i))
            losses.append(float(loss))
            if i == 0:
                grads = jax.device_get(self._m_norms(self.opt_state["m"]))
        change = jax.device_get(self._change(self.params, key))
        return {"loss": losses, "grad": _floats(grads), "change": _floats(change)}

    def window(self, seed: int, seconds: float, trace: bool) -> dict:
        """Run steps for ``seconds``; the window's counts and clock."""
        span = annotate(trace)
        first = self.traffic["check"]["steps"]
        n, bad = 0, 0
        with span(SPAN + "window"):
            t0 = time.perf_counter()
            while True:
                with span(SPAN + "data"):
                    batch = self.feed(seed, first + n)
                with span(SPAN + "step"):
                    self.params, self.opt_state, loss = self.step(
                        self.params, self.opt_state, batch)
                with span(SPAN + "loss_read"):
                    loss = float(loss)
                n += 1
                bad += not np.isfinite(loss)
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready((self.params, self.opt_state))
            t1 = time.perf_counter()
        return {"steps": n, "failed": bad, "window_s": t1 - t0,
                "tokens": n * self.tokens_per_step}

    def free(self):
        for leaf in jax.tree.leaves((self.params, self.opt_state)):
            leaf.delete()
        self.params = self.opt_state = None

    def reference(self, seed: int, mat=F32, rows: tuple | None = None) -> dict:
        """The float32 reference's readings of the same first steps, on one chip.

        ``mat`` and ``rows`` put the control (float8 products) or a planted
        fault (rows left out) in the program's place."""
        chk = self.traffic["check"]
        toks = np.stack([zipf_tokens(seed, i, self.batch, self.seq,
                                     self.model["vocab_size"])
                         for i in range(chk["steps"])]).astype(np.int32)
        fam, m, layout = self.family, self.model, self.layout
        dev = jax.devices()[0]
        with jax.default_matmul_precision("highest"), jax.default_device(dev):
            losses, grads, change = jax.device_get(train_readings(
                lambda p, t: fam.loss_sum(p, t, m, mat, chk["q_block"]),
                lambda k: init_tree(layout, k), seed_key(seed),
                [jnp.asarray(t) for t in toks], self.opt, chk["row_block"], rows))
        return {"loss": [float(x) for x in losses], "grad": _floats(grads),
                "change": _floats(change)}


def build(config: dict, traffic: dict, chips: int, family) -> TrainCell:
    return TrainCell(config, traffic, chips, family)


def _floats(d: dict) -> dict:
    return {k: float(v) for k, v in d.items()}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared with their limits, and where each is worst.

    ``loss_gap``: the largest relative gap of a step's loss.  ``grad_gap``
    and ``change_gap``: over the leaves (one per layer for stacked
    weights), the largest gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's.  Leaves whose reference gradient is under a thousandth
    of the median leaf's are left out of both: they move by round-off.
    """
    loss_gap = max(abs(p - r) / abs(r) if np.isfinite(p) else np.inf
                   for p, r in zip(prog["loss"], ref["loss"]))
    med_g = statistics.median(ref["grad"].values())
    keep = [k for k, v in ref["grad"].items() if v >= 1e-3 * med_g]
    out = {"loss_gap": (loss_gap, "")}
    for name in ("grad", "change"):
        r = ref[name]
        med = statistics.median(r[k] for k in keep)
        gaps = {k: abs(prog[name][k] - r[k]) / max(r[k], med) for k in keep}
        gaps = {k: (g if np.isfinite(g) else np.inf) for k, g in gaps.items()}
        worst = max(gaps, key=gaps.get)
        out[name + "_gap"] = (gaps[worst], worst)
    return out


def run(cell: "TrainCell", seed: int, seconds: float, trace: bool, t_start: float,
        limits: dict) -> dict:
    """One benchmark run of a training cell (set-up, window, reference)."""
    import trace_reduce

    prog = cell.check_steps(seed)
    setup_s = time.perf_counter() - t_start
    compiles = trace_reduce.CompileCounter()
    reduced = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        with compiles, jax.profiler.trace(tdir, profiler_options=trace_reduce.options()):
            win = cell.window(seed, seconds, True)
        reduced = trace_reduce.reduce(trace_reduce.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        with compiles:
            win = cell.window(seed, seconds, False)
    peak = trace_reduce.memory_peak_bytes()
    cell.free()
    ref = cell.reference(seed)
    nums = compare(prog, ref)
    return {
        "setup_s": setup_s, "window": win, "compiles_in_window": compiles.count,
        "memory_peak_bytes": peak, "trace": reduced,
        "checks": {k: (v, limits[k], where) for k, (v, where) in nums.items()},
        "e2e": {"train_tokens_per_s": win["tokens"] / win["window_s"]},
        "attempted": win["steps"], "failed": win["failed"],
    }
