"""Plain float32 pieces shared by the reference families.

Everything here is written from the published descriptions (pre-norm
transformer blocks, scaled dot-product attention, AdamW with decoupled
weight decay, global-norm clipping and a warmup-cosine schedule) in
``jax.numpy`` at ``Precision.HIGHEST``.  Nothing here imports the program
under test, and nothing takes an array the program made: the weights come
from :func:`init_tree`, a function of the seed alone.

``Mat`` is the matrix product every reference family routes through.  The
benchmark's reference is ``F32``; the precision control (the reference
computed one precision step below the configuration's bfloat16 compute) is
``FP8``, which rounds each operand, and each cotangent in the backward
pass, to float8 e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


# ---------------------------------------------------------------------------
# matrix products: float32 (the reference) and float8 (the control)
# ---------------------------------------------------------------------------

def f32_einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round_fp8(x):
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round_fp8(x)


_fp8_operand.defvjp(lambda x: (_round_fp8(x), None), lambda _, ct: (ct,))


@jax.custom_vjp
def _fp8_cotangent(x):
    return x


_fp8_cotangent.defvjp(lambda x: (x, None), lambda _, ct: (_round_fp8(ct),))


def fp8_einsum(spec: str, a, b):
    return _fp8_cotangent(f32_einsum(spec, _fp8_operand(a), _fp8_operand(b)))


F32 = f32_einsum
FP8 = fp8_einsum
Mat = Callable[[str, jax.Array, jax.Array], jax.Array]


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Init:
    """How one weight array is drawn: ``normal`` × std, or a constant."""
    shape: tuple
    kind: str = "normal"  # normal | ones | zeros
    std: float = 0.0


def fan_in_normal(shape: tuple, fan_in: int) -> Init:
    return Init(tuple(shape), "normal", 1.0 / math.sqrt(fan_in))


def _is_init(x) -> bool:
    return isinstance(x, Init)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_tree(layout, key: jax.Array):
    """Draw every array of ``layout`` (a tree of :class:`Init`) from ``key``.

    Leaf ``i`` in flattening order uses ``fold_in(key, i)``, so one seed
    gives the same weights wherever this runs."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_init)
    out = []
    for i, spec in enumerate(leaves):
        if spec.kind == "ones":
            out.append(jnp.ones(spec.shape, jnp.float32))
        elif spec.kind == "zeros":
            out.append(jnp.zeros(spec.shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), spec.shape,
                                         jnp.float32) * spec.std)
    return jax.tree.unflatten(treedef, out)


def layout_shapes(layout):
    return jax.tree.map(lambda s: s.shape, layout, is_leaf=_is_init)


# ---------------------------------------------------------------------------
# attention, loss
# ---------------------------------------------------------------------------

def attention(q, k, v, window: int | None, q_block: int, mat: Mat = F32):
    """Causal (optionally windowed) softmax attention, grouped-query aware.

    q [B,S,H,D], k/v [B,S,KV,D].  Queries go in blocks of ``q_block``
    rows, each over only the keys it can see, so the score working set is
    [B,H,q_block,≤S]; each block is rematerialised in the backward pass.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    scale = 1.0 / math.sqrt(d)
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        lo = 0 if window is None else max(0, start - window + 1)

        @jax.checkpoint
        def block(qb, kb, vb, start=start, stop=stop, lo=lo):
            qg = qb.reshape(b, stop - start, kvh, rep, d)
            sc = mat("bqgrd,bkgd->bgrqk", qg, kb) * scale
            qpos = jnp.arange(start, stop)[:, None]
            kpos = jnp.arange(lo, stop)[None, :]
            ok = kpos <= qpos
            if window is not None:
                ok = ok & (qpos - kpos < window)
            sc = jnp.where(ok, sc, NEG)
            p = jax.nn.softmax(sc, axis=-1)
            o = mat("bgrqk,bkgd->bqgrd", p, vb)
            return o.reshape(b, stop - start, h, d)

        outs.append(block(q[:, start:stop], k[:, lo:stop], v[:, lo:stop]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def token_nll_sum(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# ---------------------------------------------------------------------------
# AdamW (Loshchilov & Hutter), global-norm clipping, warmup + cosine
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = step / max(opt["warmup_steps"], 1)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    floor = opt["min_lr_ratio"]
    cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return opt["lr"] * jnp.where(step < opt["warmup_steps"], warm, cos)


def adamw(params, grads, m, v, step: int, opt: dict):
    """One AdamW update at 1-based ``step``; returns (params, m, v, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    lr = learning_rate(opt, step)
    stepf = jnp.asarray(step, jnp.float32)
    c1, c2 = 1 - b1 ** stepf, 1 - b2 ** stepf

    def upd(p, m_, v_):
        return p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + opt["eps"])
                         + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


# ---------------------------------------------------------------------------
# the training readings that decide ``correct``
# ---------------------------------------------------------------------------

def leaf_norms(tree, stacked_prefix: str = "segments"):
    """{path: norm}; leaves under ``stacked_prefix`` (a leading layer axis)
    give one norm per layer, under ``path[i]``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        x = leaf.astype(jnp.float32)
        if name.startswith(stacked_prefix):
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"{name}[{i}]"] = n[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def _grad_over_blocks(loss_sum_fn, params, tokens, row_block: int):
    """Summed loss and gradient over the rows of ``tokens``, ``row_block``
    rows at a time."""
    b, s = tokens.shape
    rb = min(row_block, b)
    if rb == b:
        return jax.value_and_grad(loss_sum_fn)(params, tokens)

    def acc(carry, blk):
        tot, g = carry
        l, gb = jax.value_and_grad(loss_sum_fn)(params, blk)
        return (tot + l, jax.tree.map(jnp.add, g, gb)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    return jax.lax.scan(acc, zero, tokens.reshape(b // rb, rb, s))[0]


def make_step(loss_sum_fn, opt: dict, row_block: int,
              rows: tuple[slice, slice] | None = None):
    """The jitted reference training step
    ``(params, m, v, tokens, step) → (params, m, v, mean loss, clipped-gradient leaf norms)``.

    ``loss_sum_fn(params, tokens)`` is the summed next-token NLL of a block
    of rows; the gradient is accumulated over blocks of ``row_block`` rows
    and divided by the token count, which is the mean loss's gradient.
    ``rows`` (a row and a position slice) restricts every batch to part
    of it, to plant the faults "half the batch left out" and "no exchange
    between chips".
    """
    def step(params, m, v, tokens, i):
        if rows is not None:
            tokens = tokens[rows]
        b, s = tokens.shape
        tot, g = _grad_over_blocks(loss_sum_fn, params, tokens, row_block)
        n_tok = b * (s - 1)
        g = jax.tree.map(lambda x: x / n_tok, g)
        params, m, v, g = adamw(params, g, m, v, i, opt)
        return params, m, v, tot / n_tok, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(loss_sum_fn, init_fn, key, batches, opt: dict, row_block: int,
                   rows: tuple[slice, slice] | None = None):
    """``len(batches)`` AdamW steps of a reference model from ``init_fn(key)``.

    Returns the per-step mean losses, the clipped first gradient's leaf
    norms, and the leaf norms of the parameters' change over all steps.
    """
    step = make_step(loss_sum_fn, opt, row_block, rows)
    params = jax.jit(init_fn)(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        params, m, v, loss, g = step(params, m, v, tokens, jnp.int32(i + 1))
        losses.append(loss)
        first = g if i == 0 else first
    del m, v
    change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(jnp.subtract, p, init_fn(k))))(
        params, key)
    return jnp.stack(losses), first, change
