"""Model assembly: block pattern → init / forward / loss / decode.

Layers are grouped into **segments** of consecutive identical block kinds;
each segment's params are stacked along a leading layer axis so homogeneous
stacks can run under ``lax.scan`` (small HLO, fast multi-pod compiles) or be
unrolled layer-by-layer (exact per-layer cost accounting for the roofline
pass) — switched by ``cfg.unroll_layers``.

Zamba2's weight-shared attention block is interposed *between* segments
every ``shared_attn_every`` layers; whisper adds an encoder stack and
cross-attention; paligemma prepends stub image embeddings under a prefix-LM
mask.  One code path serves all ten assigned architectures.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm
from repro.models.layers import (apply_mlp, apply_norm, cross_entropy,
                                 dense_init, embed_init, init_mlp, init_norm,
                                 sinusoidal_positions)

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------

def segments_of(cfg: ModelConfig) -> list[tuple[str, int]]:
    """Group the block pattern into (kind, count) runs, splitting at shared-
    attention interposition points (zamba2)."""
    segs: list[tuple[str, int]] = []
    for i, kind in enumerate(cfg.block_pattern):
        boundary = (cfg.shared_attn_every
                    and i % cfg.shared_attn_every == 0 and i > 0)
        if segs and segs[-1][0] == kind and not boundary:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def _init_block(rng: Array, kind: str, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    pd = cfg.pdtype
    ks = jax.random.split(rng, 4)
    if kind in ("dense", "moe", "mla_dense", "mla_moe"):
        if kind.startswith("mla"):
            a = attn.init_mla(ks[0], d, cfg.n_heads, cfg.mla_kv_lora_rank,
                              cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim, cfg.mla_v_dim, pd)
        else:
            a = attn.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.qkv_bias, pd)
        p = {"ln1": init_norm(cfg.norm, d), "attn": a, "ln2": init_norm(cfg.norm, d)}
        if kind.endswith("dense"):
            p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_style, pd)
        else:
            p["moe"] = moe_lib.init_moe(ks[1], d, cfg.moe_d_ff, cfg.moe_experts,
                                        cfg.moe_router_experts, cfg.moe_shared_experts,
                                        cfg.moe_shared_experts * cfg.moe_d_ff or None, pd)
        return p
    if kind == "mamba2":
        return {"ln1": init_norm(cfg.norm, d),
                "mix": ssm.init_mamba2(ks[0], d, cfg.ssm_state, cfg.ssm_headdim,
                                       cfg.ssm_expand, dtype=pd)}
    if kind == "mlstm":
        return {"ln1": init_norm(cfg.norm, d),
                "mix": ssm.init_mlstm(ks[0], d, cfg.n_heads, cfg.xlstm_expand, dtype=pd)}
    if kind == "slstm":
        return {"ln1": init_norm(cfg.norm, d),
                "mix": ssm.init_slstm(ks[0], d, cfg.n_heads, dtype=pd)}
    raise ValueError(f"unknown block kind {kind!r}")


def _init_cross_block(rng: Array, cfg: ModelConfig) -> dict:
    """Whisper decoder block: self-attn + cross-attn + mlp."""
    d = cfg.d_model
    pd = cfg.pdtype
    ks = jax.random.split(rng, 3)
    return {
        "ln1": init_norm(cfg.norm, d),
        "attn": attn.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.qkv_bias, pd),
        "ln_x": init_norm(cfg.norm, d),
        "xattn": attn.init_attention(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim, cfg.qkv_bias, pd),
        "ln2": init_norm(cfg.norm, d),
        "mlp": init_mlp(ks[2], d, cfg.d_ff, cfg.mlp_style, pd),
    }


def init_params(rng: Array, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    params: dict = {"embed": embed_init(ks[0], (cfg.vocab_size, d), cfg.pdtype)}
    segs = segments_of(cfg)
    seg_params = []
    for i, (kind, count) in enumerate(segs):
        layer_rngs = jax.random.split(jax.random.fold_in(ks[1], i), count)
        stacked = jax.vmap(lambda r: _init_block(r, kind, cfg))(layer_rngs)
        seg_params.append(stacked)
    params["segments"] = seg_params
    params["final_norm"] = init_norm(cfg.norm, d)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (d, cfg.vocab_size), dtype=cfg.pdtype)
    if cfg.shared_attn_every:
        params["shared_block"] = _init_block(ks[3], "dense", cfg)
        params["shared_proj"] = dense_init(ks[4], (2 * d, d), dtype=cfg.pdtype)
    if cfg.kind == "encdec":
        enc_rngs = jax.random.split(ks[5], cfg.enc_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda r: _init_block(r, "dense", cfg))(enc_rngs),
            "final_norm": init_norm(cfg.norm, d),
        }
        # decoder cross blocks replace the plain segment stack
        dec_rngs = jax.random.split(ks[6], cfg.n_layers)
        params["segments"] = [jax.vmap(lambda r: _init_cross_block(r, cfg))(dec_rngs)]
    return params


def param_shapes(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStructs of all params (no allocation) — dry-run input."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# per-block forward (full sequence)
# ---------------------------------------------------------------------------

def _block_forward(kind: str, p: dict, x: Array, positions: Array,
                   cfg: ModelConfig, mask_kind: str, prefix_len: int) -> tuple[Array, Array]:
    """One block over the full sequence, under the device-side scope
    ``block`` (its own ops: norms, residual adds) with ``attention``,
    ``mlp`` and ``moe`` inside it: the names a profiler trace gives the
    block's ops, forward, backward and recomputed alike."""
    with jax.named_scope("block"):
        aux = jnp.zeros((), jnp.float32)
        if kind in ("dense", "moe", "mla_dense", "mla_moe"):
            h = apply_norm(cfg.norm, p["ln1"], x)
            with jax.named_scope("attention"):
                if kind.startswith("mla"):
                    a = attn.mla_forward(p["attn"], h, positions, cfg)
                else:
                    a = attn.attention_forward(p["attn"], h, positions, cfg, mask_kind,
                                               prefix_len)
            x = x + a
            h = apply_norm(cfg.norm, p["ln2"], x)
            if kind.endswith("dense"):
                with jax.named_scope("mlp"):
                    y = apply_mlp(p["mlp"], h, cfg.mlp_style)
            else:
                with jax.named_scope("moe"):
                    y, aux = moe_lib.apply_moe(p["moe"], h, cfg.moe_top_k,
                                               cfg.norm_topk_prob)
            x = x + y
        elif kind == "mamba2":
            h = apply_norm(cfg.norm, p["ln1"], x)
            x = x + ssm.mamba2_forward(p["mix"], h, cfg.ssm_state, cfg.ssm_headdim,
                                       cfg.ssm_expand, cfg.ssm_chunk)
        elif kind == "mlstm":
            h = apply_norm(cfg.norm, p["ln1"], x)
            x = x + ssm.mlstm_forward(p["mix"], h, cfg.n_heads, cfg.xlstm_expand)
        elif kind == "slstm":
            h = apply_norm(cfg.norm, p["ln1"], x)
            x = x + ssm.slstm_forward(p["mix"], h, cfg.n_heads)
        else:
            raise ValueError(kind)
        return x, aux


def _shared_block_forward(params: PyTree, x: Array, x0: Array, positions: Array,
                          cfg: ModelConfig) -> Array:
    """Zamba2: weight-shared attention block over concat(x, x0)."""
    h = jnp.concatenate([x, x0], axis=-1) @ params["shared_proj"].astype(x.dtype)
    h, _ = _block_forward("dense", params["shared_block"], h, positions, cfg,
                          "causal", 0)
    return x + h


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------

def _run_segment(seg_params: PyTree, kind: str, count: int, x: Array, aux: Array,
                 positions: Array, cfg: ModelConfig, mask_kind: str,
                 prefix_len: int) -> tuple[Array, Array]:
    def _maybe_remat(fwd):
        if not cfg.remat:
            return fwd
        if cfg.remat_policy == "dots":
            return jax.checkpoint(
                fwd, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fwd)

    if cfg.unroll_layers or count == 1 or kind == "slstm":
        for i in range(count):
            p_i = jax.tree.map(lambda a: a[i], seg_params)
            fwd = _maybe_remat(lambda xx, pp: _block_forward(
                kind, pp, xx, positions, cfg, mask_kind, prefix_len))
            x, a = fwd(x, p_i)
            aux = aux + a
        return x, aux

    def body(carry, p_i):
        xx, acc = carry
        fwd = _maybe_remat(lambda xc, pp: _block_forward(
            kind, pp, xc, positions, cfg, mask_kind, prefix_len))
        xx, a = fwd(xx, p_i)
        return (xx, acc + a), None

    (x, aux), _ = jax.lax.scan(body, (x, aux), seg_params)
    return x, aux


def forward_logits(params: PyTree, batch: dict, cfg: ModelConfig) -> tuple[Array, Array]:
    """Full-sequence forward → (logits [B,S,V], aux_loss).

    ``batch``: {"tokens": [B,S]} (+ "image_embeds" for vlm, "frames" for
    encdec).  Positions are 0..S−1 (+image offset for vlm).
    """
    cdt = cfg.cdtype
    tokens = batch["tokens"]
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cdt)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    prefix_len = 0
    mask_kind = "causal"
    if cfg.kind == "vlm":
        img = batch["image_embeds"].astype(cdt)  # [B, T_img, D] (stub frontend)
        x = jnp.concatenate([img, x], axis=1)
        prefix_len = cfg.num_image_tokens
        mask_kind = "prefix"
        s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    enc_out = None
    if cfg.kind == "encdec":
        enc_out = encoder_forward(params["encoder"], batch["frames"], cfg)
        x = x + sinusoidal_positions(tokens.shape[1], cfg.d_model).astype(cdt)[None]
        return _decoder_cross_forward(params, x, enc_out, positions, cfg)

    aux = jnp.zeros((), jnp.float32)
    x0 = x
    layer_idx = 0
    for seg_params, (kind, count) in zip(params["segments"], segments_of(cfg)):
        if (cfg.shared_attn_every and layer_idx > 0
                and layer_idx % cfg.shared_attn_every == 0):
            x = _shared_block_forward(params, x, x0, positions, cfg)
        x, aux = _run_segment(seg_params, kind, count, x, aux, positions, cfg,
                              mask_kind, prefix_len)
        layer_idx += count
    with jax.named_scope("head"):
        x = apply_norm(cfg.norm, params["final_norm"], x)
        logits = _unembed(params, x, cfg)
    return logits, aux


def _unembed(params: PyTree, x: Array, cfg: ModelConfig) -> Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    return x @ params["lm_head"].astype(x.dtype)


def encoder_forward(enc: PyTree, frames: Array, cfg: ModelConfig) -> Array:
    """Whisper encoder over precomputed frame embeddings (conv stem stubbed)."""
    cdt = cfg.cdtype
    b, s, _ = frames.shape
    x = frames.astype(cdt) + sinusoidal_positions(s, cfg.d_model).astype(cdt)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, p_i):
        fwd = lambda xx, pp: _block_forward("dense", pp, xx, positions, cfg,
                                            "bidirectional", 0)[0]
        if cfg.remat:
            fwd = jax.checkpoint(fwd)
        return fwd(x, p_i), None

    if cfg.unroll_layers:
        for i in range(cfg.enc_layers):
            p_i = jax.tree.map(lambda a: a[i], enc["layers"])
            x, _ = body(x, p_i)
    else:
        x, _ = jax.lax.scan(body, x, enc["layers"])
    return apply_norm(cfg.norm, enc["final_norm"], x)


def _decoder_cross_forward(params: PyTree, x: Array, enc_out: Array,
                           positions: Array, cfg: ModelConfig) -> tuple[Array, Array]:
    b, s = positions.shape
    enc_pos = jnp.broadcast_to(
        jnp.arange(enc_out.shape[1], dtype=jnp.int32)[None], (b, enc_out.shape[1]))

    def block(x, p):
        h = apply_norm(cfg.norm, p["ln1"], x)
        x = x + attn.attention_forward(p["attn"], h, positions, cfg, "causal")
        h = apply_norm(cfg.norm, p["ln_x"], x)
        x = x + attn.attention_forward(p["xattn"], h, positions, cfg,
                                       "bidirectional", 0, xkv=enc_out,
                                       kv_positions=enc_pos)
        h = apply_norm(cfg.norm, p["ln2"], x)
        return x + apply_mlp(p["mlp"], h, cfg.mlp_style)

    seg = params["segments"][0]
    if cfg.unroll_layers:
        for i in range(cfg.n_layers):
            p_i = jax.tree.map(lambda a: a[i], seg)
            x = jax.checkpoint(block)(x, p_i) if cfg.remat else block(x, p_i)
    else:
        def body(xx, p_i):
            fwd = jax.checkpoint(block) if cfg.remat else block
            return fwd(xx, p_i), None
        x, _ = jax.lax.scan(body, x, seg)
    with jax.named_scope("head"):
        x = apply_norm(cfg.norm, params["final_norm"], x)
        logits = _unembed(params, x, cfg)
    return logits, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(params: PyTree, batch: dict, cfg: ModelConfig) -> Array:
    logits, aux = forward_logits(params, batch, cfg)
    if cfg.kind == "vlm":  # image positions carry no LM loss
        logits = logits[:, cfg.num_image_tokens:]
    with jax.named_scope("head"):
        ce = cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])
    return ce + cfg.aux_loss_weight * aux


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def cache_layout(cfg: ModelConfig) -> list[str]:
    """Static tag sequence for the decode cache list: one entry per layer,
    plus one per zamba2 shared-attention call site."""
    if cfg.kind == "encdec":
        return ["cross_dense"] * cfg.n_layers
    tags: list[str] = []
    for i, kind in enumerate(cfg.block_pattern):
        if cfg.shared_attn_every and i > 0 and i % cfg.shared_attn_every == 0:
            tags.append("shared")
        tags.append(kind)
    return tags


def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """One cache pytree per ``cache_layout`` entry (tags are static)."""
    cdt = cfg.cdtype
    kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    caches: list = []
    for tag in cache_layout(cfg):
        kv_dt = "int8" if cfg.kv_cache_dtype == "int8" else cdt
        if tag == "shared":
            caches.append(attn.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                             cfg.head_dim, kv_dt))
        elif tag in ("dense", "moe"):
            caches.append(attn.init_kv_cache(batch, kv_len, cfg.n_kv_heads,
                                             cfg.head_dim, kv_dt))
        elif tag in ("mla_dense", "mla_moe"):
            caches.append(attn.init_mla_cache(batch, max_len, cfg.mla_kv_lora_rank,
                                              cfg.mla_qk_rope_dim, cdt))
        elif tag == "mamba2":
            caches.append(ssm.init_mamba2_state(batch, cfg.d_model, cfg.ssm_state,
                                                cfg.ssm_headdim, cfg.ssm_expand,
                                                dtype=cdt))
        elif tag == "mlstm":
            caches.append(ssm.init_mlstm_state(batch, cfg.d_model, cfg.n_heads,
                                               cfg.xlstm_expand, dtype=cdt))
        elif tag == "slstm":
            caches.append(ssm.init_slstm_state(batch, cfg.d_model, cfg.n_heads))
        elif tag == "cross_dense":
            caches.append({
                "self": attn.init_kv_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim, cdt),
                "cross_k": jnp.zeros((batch, cfg.enc_seq_len, cfg.n_heads, cfg.head_dim), cdt),
                "cross_v": jnp.zeros((batch, cfg.enc_seq_len, cfg.n_heads, cfg.head_dim), cdt),
            })
    return caches


def decode_step(params: PyTree, caches: list, tokens: Array, position: Array,
                cfg: ModelConfig, image_prefix: bool = False) -> tuple[Array, list]:
    """One decode step: tokens [B,1] at absolute ``position`` (scalar)."""
    cdt = cfg.cdtype
    x = params["embed"][tokens].astype(cdt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    if cfg.kind == "encdec":
        x = x + sinusoidal_positions(1, cfg.d_model, position).astype(cdt)[None]
    new_caches: list = []
    x0 = x
    ci = 0
    tags = cache_layout(cfg)
    flat_layers = _flatten_layer_params(params, cfg)
    for kind, p in flat_layers:
        if tags[ci] == "shared":
            # zamba2 shared block call site
            cache = caches[ci]
            h = jnp.concatenate([x, x0], axis=-1) @ params["shared_proj"].astype(cdt)
            sp = params["shared_block"]
            hn = apply_norm(cfg.norm, sp["ln1"], h)
            a, cache = attn.decode_attention(sp["attn"], hn, cache, position, cfg)
            h = h + a
            hn = apply_norm(cfg.norm, sp["ln2"], h)
            h = h + apply_mlp(sp["mlp"], hn, cfg.mlp_style)
            x = x + h
            new_caches.append(cache)
            ci += 1
        x, cache = _decode_block(kind, p, x, caches[ci], position, cfg, params)
        new_caches.append(cache)
        ci += 1
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = _unembed(params, x, cfg)
    return logits, new_caches


def _flatten_layer_params(params: PyTree, cfg: ModelConfig) -> list[tuple[str, dict]]:
    out = []
    if cfg.kind == "encdec":
        seg = params["segments"][0]
        for i in range(cfg.n_layers):
            out.append(("cross_dense", jax.tree.map(lambda a: a[i], seg)))
        return out
    for seg_params, (kind, count) in zip(params["segments"], segments_of(cfg)):
        for i in range(count):
            out.append((kind, jax.tree.map(lambda a: a[i], seg_params)))
    return out


def _decode_block(kind: str, p: dict, x: Array, cache, position: Array,
                  cfg: ModelConfig, params: PyTree):
    if kind in ("dense", "moe", "mla_dense", "mla_moe"):
        h = apply_norm(cfg.norm, p["ln1"], x)
        decode = attn.mla_decode if kind.startswith("mla") else attn.decode_attention
        a, cache = decode(p["attn"], h, cache, position, cfg)
        x = x + a
        h = apply_norm(cfg.norm, p["ln2"], x)
        if kind.endswith("dense"):
            x = x + apply_mlp(p["mlp"], h, cfg.mlp_style)
        else:
            y, _ = moe_lib.apply_moe(p["moe"], h, cfg.moe_top_k, cfg.norm_topk_prob)
            x = x + y
    elif kind == "mamba2":
        h = apply_norm(cfg.norm, p["ln1"], x)
        y, cache = ssm.mamba2_step(p["mix"], h, cache, cfg.ssm_state,
                                   cfg.ssm_headdim, cfg.ssm_expand)
        x = x + y
    elif kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln1"], x)
        y, cache = ssm.mlstm_step(p["mix"], h, cache, cfg.n_heads, cfg.xlstm_expand)
        x = x + y
    elif kind == "slstm":
        h = apply_norm(cfg.norm, p["ln1"], x)
        y, cache = ssm.slstm_step(p["mix"], h, cache, cfg.n_heads)
        x = x + y
    elif kind == "cross_dense":
        h = apply_norm(cfg.norm, p["ln1"], x)
        a, self_cache = attn.decode_attention(p["attn"], h, cache["self"], position, cfg)
        x = x + a
        h = apply_norm(cfg.norm, p["ln_x"], x)
        # cross-attn over the fixed encoder KV
        b = x.shape[0]
        pos_b = jnp.broadcast_to(position[None], (b,))[:, None]
        enc_pos = jnp.broadcast_to(
            jnp.arange(cache["cross_k"].shape[1], dtype=jnp.int32)[None], (b, cache["cross_k"].shape[1]))
        q = jnp.einsum("bsd,dhk->bshk", h, p["xattn"]["wq"].astype(h.dtype))
        mask = attn.build_mask(pos_b, enc_pos, "bidirectional")
        o = attn.dense_attention(q, cache["cross_k"].astype(h.dtype),
                                 cache["cross_v"].astype(h.dtype), mask)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["xattn"]["wo"].astype(h.dtype))
        h = apply_norm(cfg.norm, p["ln2"], x)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_style)
        cache = {"self": self_cache, "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    else:
        raise ValueError(kind)
    return x, cache
