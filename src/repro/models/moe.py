"""Mixture of experts: a dropless layer over the routed experts held here.

Under expert parallelism each chip holds ``held`` of the router's experts,
the ids ``[first_expert, first_expert + held)``.  ``apply_moe`` routes every
token over the router's full width and returns the part of the layer's
result that the held experts give, plus the shared experts' (which every
chip computes alike), so the parts of all the chips, with the shared part
counted once, sum to the whole layer.  On one chip that holds every expert
the part is the whole.  The exchange that brings a chip the tokens routed
to it from other chips is not here.

No token is dropped: every assignment to a held expert is computed.  The
assignments are sorted by expert and run as one grouped product per
projection (``jax.lax.ragged_dot``) over a buffer of ``tokens × top_k``
rows, room for a token that sends all its choices here.  The rows of
assignments to experts held elsewhere sit past the groups, where the
grouped product's result is undefined (on TPU it is whatever the buffer
held, NaN included): they are held at zero on the way in and out of each
product, so that neither they nor their cotangents reach the result.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

Array = jax.Array


def init_moe(rng: Array, d: int, d_ff: int, n_held: int, n_router: int,
             n_shared: int = 0, shared_d_ff: int | None = None,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, n_router), scale=0.1, dtype=jnp.float32),
        "wi": dense_init(ks[1], (n_held, d, d_ff), dtype=dtype),
        "wg": dense_init(ks[2], (n_held, d, d_ff), dtype=dtype),
        "wo": dense_init(ks[3], (n_held, d_ff, d), dtype=dtype),
    }
    if n_shared:
        sdf = shared_d_ff or n_shared * d_ff
        sub = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(sub[0], (d, sdf), dtype=dtype),
            "wg": dense_init(sub[1], (d, sdf), dtype=dtype),
            "wo": dense_init(sub[2], (sdf, d), dtype=dtype),
        }
    return p


def apply_moe(p: dict, x: Array, top_k: int, norm_topk: bool,
              first_expert: int = 0) -> tuple[Array, Array]:
    """x: [B, S, D] → (y, aux_loss).

    Routing is in float32: a softmax over all the router's outputs, the
    ``top_k`` largest taken greedily as gates, renormalised to sum to one
    only where ``norm_topk``.  ``aux_loss`` is DeepSeek-V2's per-sequence
    balance loss over the router's E outputs, averaged over the rows:
    Σ_i f_i·P_i with f_i = E/(top_k·S) × the times expert i was chosen in
    the row and P_i its mean score there (weighted by the config's
    ``aux_loss_weight`` in the loss).
    """
    b, s, d = x.shape
    dt = x.dtype
    held = p["wi"].shape[0]
    n = b * s * top_k
    with jax.named_scope("route"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"],
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)  # [B,S,E]
        e = probs.shape[-1]
        gates, idx = jax.lax.top_k(probs, top_k)  # [B,S,k]
        if norm_topk:
            gates = gates / gates.sum(-1, keepdims=True)
        chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=(1, 2))  # [B,E]
        aux = jnp.mean(jnp.sum(chosen * (e / (top_k * s)) * probs.mean(axis=1), axis=-1))

    with jax.named_scope("dispatch"):
        # held experts' assignments sorted by expert; the others (id
        # ``held``) after them
        local = idx.reshape(n) - first_expert
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        grouped = (jnp.arange(n) < jnp.sum(sizes))[:, None]  # rows in some group
        rows = jnp.broadcast_to(x.reshape(b * s, 1, d), (b * s, top_k, d)).reshape(n, d)
        rows = jnp.where(grouped, rows.at[order].get(unique_indices=True), 0)  # [n, D]

    with jax.named_scope("experts"):
        def product(a, w):
            return jnp.where(grouped, jax.lax.ragged_dot(a, w.astype(dt), sizes), 0)

        h = jax.nn.silu(product(rows, p["wg"])) * product(rows, p["wi"])
        y = product(h, p["wo"])  # [n, D]

    with jax.named_scope("combine"):
        g = jnp.where(local < held, gates.reshape(n), 0.0)[order]
        y = (y * g[:, None].astype(dt)).at[jnp.argsort(order)].get(unique_indices=True)
        out = y.reshape(b, s, top_k, d).sum(axis=2)

    if "shared" in p:
        with jax.named_scope("shared"):
            sp = p["shared"]
            hs = jax.nn.silu(x @ sp["wg"].astype(dt)) * (x @ sp["wi"].astype(dt))
            out = out + hs @ sp["wo"].astype(dt)
    return out, aux
