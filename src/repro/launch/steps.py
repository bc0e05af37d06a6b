"""Jitted step builders: train / prefill / decode, for both comm backends.

Two training-communication backends:

  * ``comm="xla"``     — pure pjit: GSPMD inserts the gradient all-reduces.
    Supports ZeRO-1/3 via the sharding policy.  This is the *ideal-switch
    baseline* in system form and the path the 40-cell dry-run uses.
  * ``comm="ring" | "lumorph2" | "lumorph4" | "auto"`` — hybrid shard_map:
    the data axes are manual (our ppermute circuit schedules move the
    gradients — the paper's technique), the model axis stays auto (GSPMD
    TP).  ``auto`` picks per-bucket algorithms from the α–β cost model.

Both produce steps with identical signatures:
  train_step(params, opt_state, batch) → (params, opt_state, loss)
  prefill(params, batch)               → logits
  decode(params, caches, tokens, pos)  → (logits, caches)
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.optim import grad_comm
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.sharding.policy import ShardingPolicy

PyTree = Any


# ---------------------------------------------------------------------------
# shape helpers (ShapeDtypeStruct factories — no allocation)
# ---------------------------------------------------------------------------

def batch_shapes(cfg: ModelConfig, seq_len: int, global_batch: int) -> dict:
    out = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)}
    if cfg.kind == "vlm":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    if cfg.kind == "encdec":
        out["frames"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.enc_seq_len, cfg.d_model), jnp.float32)
    return out


def input_specs(cfg: ModelConfig, policy: ShardingPolicy, seq_len: int,
                global_batch: int) -> tuple[dict, dict]:
    """(ShapeDtypeStructs with shardings, raw specs) for a batch."""
    shapes = batch_shapes(cfg, seq_len, global_batch)
    specs = policy.batch_specs(shapes)
    with_sh = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                       sharding=policy.named(specs[k]))
               for k, v in shapes.items()}
    return with_sh, specs


def sharded_struct(tree: PyTree, spec_tree: PyTree, policy: ShardingPolicy) -> PyTree:
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=policy.named(sp)),
        tree, spec_tree)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def opt_shapes(cfg: ModelConfig, params_shape: PyTree) -> PyTree:
    return jax.eval_shape(init_opt_state, params_shape)


def make_train_step(cfg: ModelConfig, policy: ShardingPolicy,
                    opt_cfg: Optional[AdamWConfig] = None,
                    comm: str = "xla",
                    bucket_bytes: int = grad_comm.DEFAULT_BUCKET_BYTES,
                    compress: bool = False,
                    donate: bool = True,
                    wire_dtype=None,
                    microbatches: int = 1,
                    overlap_chunks: int = 1):
    """Build the jitted train step (decode which comm backend to use).

    ``microbatches > 1``: gradient accumulation — the global batch is split
    along its leading dim and scanned, cutting peak activation memory
    ~microbatches× for the cost of re-reading weights per chunk.

    ``overlap_chunks > 1`` (LUMORPH backends only): the ``--overlap`` step
    mode — every gradient bucket's collective is lowered as that many
    chunked waves (``grad_comm.all_reduce_grads(overlap_chunks=…)``) so the
    scheduler can pipeline the ppermute rounds against compute instead of
    executing one blocking monolith.  Ignored by ``comm="xla"`` (GSPMD owns
    those collectives).
    """
    opt_cfg = opt_cfg or AdamWConfig()
    mesh = policy.mesh
    params_shape = tf.param_shapes(cfg)
    p_specs = policy.param_specs(params_shape)
    o_specs = policy.opt_specs(opt_shapes(cfg, params_shape))

    def grad_fn(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(lambda p: tf.loss_fn(p, batch, cfg))(params)

        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])

        chunks = jax.tree.map(split, batch)

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(lambda p: tf.loss_fn(p, mb, cfg))(params)
            g_acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32), g_acc, g)
            return (loss_acc + loss, g_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        carry = (jnp.zeros((), jnp.float32), g0)
        if cfg.unroll_layers:
            # roofline mode: python loop — scan bodies are cost-counted once
            for i in range(microbatches):
                carry, _ = body(carry, jax.tree.map(lambda x: x[i], chunks))
            loss_sum, g_sum = carry
        else:
            (loss_sum, g_sum), _ = jax.lax.scan(body, carry, chunks)
        inv = 1.0 / microbatches
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    if comm == "xla":
        def step(params, opt_state, batch):
            # the mesh in context lets a Pallas kernel map itself over it
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                loss, grads = grad_fn(params, batch)
            params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
            return params, opt_state, loss

        jitted = jax.jit(
            step,
            in_shardings=(jax.tree.map(policy.named, p_specs),
                          jax.tree.map(policy.named, o_specs),
                          None),
            out_shardings=(jax.tree.map(policy.named, p_specs),
                           jax.tree.map(policy.named, o_specs),
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else ())
        return jitted

    # ---- LUMORPH path: manual dp axes, auto model axis --------------------
    dp_axes = policy.axes.data

    def body(params, opt_state, batch):
        loss, grads = grad_fn(params, batch)
        ef = opt_state.get("ef")
        kw = {} if wire_dtype is None else {"wire_dtype": wire_dtype}
        grads, new_ef, _ = grad_comm.all_reduce_grads(
            grads, dp_axes, algo=comm, bucket_bytes=bucket_bytes,
            compress=compress, error_feedback=ef, mean=True,
            overlap_chunks=overlap_chunks, **kw)
        loss = jax.lax.pmean(loss, dp_axes)
        core_opt = {k: v for k, v in opt_state.items() if k != "ef"}
        params, core_opt = adamw_update(params, grads, core_opt, opt_cfg)
        if new_ef is not None:
            core_opt["ef"] = new_ef
        return params, core_opt, loss

    # params/opt replicated over dp in this path (the paper's DP regime);
    # model-axis TP continues to apply through the auto axis.
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    batch_spec_fn = lambda shapes: {
        k: policy.batch_spec(k, tuple(v.shape)) for k, v in shapes.items()}

    def step(params, opt_state, batch):
        specs_b = batch_spec_fn(batch)
        o_spec = rep({k: v for k, v in opt_state.items()})
        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=(rep(params), o_spec, specs_b),
            out_specs=(rep(params), o_spec, P()),
            axis_names=set(dp_axes), check_vma=False)
        return sm(params, opt_state, batch)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def init_sharded_state(cfg: ModelConfig, policy: ShardingPolicy, rng,
                       init_ef: bool = False) -> tuple[PyTree, PyTree]:
    """Materialize params + opt state directly into their shardings."""
    params_shape = tf.param_shapes(cfg)
    p_sh = jax.tree.map(policy.named, policy.param_specs(params_shape))
    params = jax.jit(functools.partial(tf.init_params, cfg=cfg),
                     out_shardings=p_sh)(rng)
    o_shape = opt_shapes(cfg, params_shape)
    o_sh = jax.tree.map(policy.named, policy.opt_specs(o_shape))
    opt = jax.jit(init_opt_state, out_shardings=o_sh)(params)
    if init_ef:
        opt["ef"] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return params, opt


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def make_prefill(cfg: ModelConfig, policy: ShardingPolicy):
    def prefill(params, batch):
        with jax.sharding.use_abstract_mesh(policy.mesh.abstract_mesh):
            logits, _ = tf.forward_logits(params, batch, cfg)
        return logits

    params_shape = tf.param_shapes(cfg)
    p_sh = jax.tree.map(policy.named, policy.param_specs(params_shape))
    return jax.jit(prefill, in_shardings=(p_sh, None))


def make_decode_step(cfg: ModelConfig, policy: ShardingPolicy, batch: int,
                     max_len: int):
    params_shape = tf.param_shapes(cfg)
    p_sh = jax.tree.map(policy.named, policy.param_specs(params_shape))
    cache_shape = jax.eval_shape(lambda: tf.init_caches(cfg, batch, max_len))
    c_specs = policy.cache_specs(cache_shape)
    c_sh = jax.tree.map(policy.named, c_specs)

    def decode(params, caches, tokens, position):
        return tf.decode_step(params, caches, tokens, position, cfg)

    return jax.jit(decode,
                   in_shardings=(p_sh, c_sh, None, None),
                   out_shardings=(None, c_sh),
                   donate_argnums=(1,))
