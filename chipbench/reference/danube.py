"""Danube-style family: the float32 reference for H2O-Danube-1.8B.

Follows H2O-Danube-1.8B (arXiv:2401.16818; Llama-style decoder with
Mistral's sliding-window attention): RMSNorm, rotary position embeddings
(theta 10000), grouped-query attention (32 query heads, 8 key/value heads,
head size 80), sliding window 4096, SwiGLU MLP of width 6912, untied
output head, vocabulary 32000.  Departures, which the program makes too:

* RMSNorm's weight is stored as an offset from one (``x̂ · (1 + w)``) and
  its epsilon is 1e-6 (the published config has 1e-5);
* rotary embedding rotates interleaved pairs (dims 0/1, 2/3, …) where the
  Hugging Face implementation rotates the two halves; with random weights
  this is a fixed permutation of each head's query and key dims.

The parameter tree has the program's layout: the layers' weights stacked
along a leading axis under ``segments[0]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import F32, Init, Mat, attention, fan_in_normal, token_nll_sum


def layout(m: dict) -> dict:
    L, d, h, kv, hd, f, V = (m["n_layers"], m["d_model"], m["n_heads"],
                             m["n_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    return {
        "embed": Init((V, d), "normal", 0.02),
        "segments": [{
            "ln1": {"w": Init((L, d), "zeros")},
            "attn": {"wq": fan_in_normal((L, d, h, hd), d),
                     "wk": fan_in_normal((L, d, kv, hd), d),
                     "wv": fan_in_normal((L, d, kv, hd), d),
                     "wo": fan_in_normal((L, h, hd, d), h * hd)},
            "ln2": {"w": Init((L, d), "zeros")},
            "mlp": {"wi": fan_in_normal((L, d, f), d),
                    "wg": fan_in_normal((L, d, f), d),
                    "wo": fan_in_normal((L, f, d), f)},
        }],
        "final_norm": {"w": Init((d,), "zeros")},
        "lm_head": fan_in_normal((d, V), d),
    }


def _rmsnorm(x, w, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, positions, theta: float):
    """Rotate interleaved pairs of x [B,S,H,D] by position."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def hidden(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Final-norm hidden states [B,S,d]."""
    s = tokens.shape[1]
    pos = jnp.arange(s)
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = _rmsnorm(x, p["ln1"]["w"])
        a = p["attn"]
        q, k, v = (mat("bsd,dhk->bshk", h, a[w]) for w in ("wq", "wk", "wv"))
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
        o = attention(q, k, v, m.get("sliding_window"), q_block, mat)
        x = x + mat("bshk,hkd->bsd", o, a["wo"])
        h = _rmsnorm(x, p["ln2"]["w"])
        mp = p["mlp"]
        u = jax.nn.silu(mat("bsd,df->bsf", h, mp["wg"])) * mat("bsd,df->bsf", h, mp["wi"])
        return x + mat("bsf,fd->bsd", u, mp["wo"]), None

    x, _ = jax.lax.scan(layer, x, params["segments"][0])
    return _rmsnorm(x, params["final_norm"]["w"])


def logits(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    return mat("bsd,dv->bsv", hidden(params, tokens, m, mat, q_block), params["lm_head"])


def loss_sum(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Summed next-token NLL over the rows of ``tokens``."""
    return token_nll_sum(logits(params, tokens, m, mat, q_block)[:, :-1], tokens[:, 1:])
