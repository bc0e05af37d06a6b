"""BERT-style family: the float32 reference for ``bert-large``.

Follows Devlin et al. 2018 (BERT-Large: 24 layers, hidden 1024, 16 heads,
intermediate 4096, WordPiece vocabulary 30522, GELU, layer norm) with the
departures that the program under test makes, so that the two compute the
same function:

* trained as a causal language model (next-token loss, causal mask), not
  with the masked-LM and next-sentence objectives;
* pre-norm blocks (norm before attention and before the MLP, a final norm)
  where BERT normalises after each residual add;
* no position or token-type embeddings, and no biases on the projections;
* GELU in its tanh approximation;
* layer norm epsilon 1e-5, where BERT's published config has 1e-12;
* the output head is the transposed token embedding (tied).

The parameter tree has the program's layout: each weight of the 24 layers
stacked along a leading layer axis under ``segments[0]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import F32, Init, Mat, attention, fan_in_normal, token_nll_sum


def layout(m: dict) -> dict:
    L, d, h, hd, f, V = (m["n_layers"], m["d_model"], m["n_heads"],
                         m["head_dim"], m["d_ff"], m["vocab_size"])
    norm = lambda: {"w": Init((L, d), "ones"), "b": Init((L, d), "zeros")}
    return {
        "embed": Init((V, d), "normal", 0.02),
        "segments": [{
            "ln1": norm(),
            "attn": {"wq": fan_in_normal((L, d, h, hd), d),
                     "wk": fan_in_normal((L, d, h, hd), d),
                     "wv": fan_in_normal((L, d, h, hd), d),
                     "wo": fan_in_normal((L, h, hd, d), h * hd)},
            "ln2": norm(),
            "mlp": {"wi": fan_in_normal((L, d, f), d),
                    "wo": fan_in_normal((L, f, d), f)},
        }],
        "final_norm": {"w": Init((d,), "ones"), "b": Init((d,), "zeros")},
    }


def _layernorm(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Final-norm hidden states [B,S,d] of a causal pre-norm BERT."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = _layernorm(x, p["ln1"])
        a = p["attn"]
        q, k, v = (mat("bsd,dhk->bshk", h, a[w]) for w in ("wq", "wk", "wv"))
        o = attention(q, k, v, None, q_block, mat)
        x = x + mat("bshk,hkd->bsd", o, a["wo"])
        h = _layernorm(x, p["ln2"])
        u = _gelu_tanh(mat("bsd,df->bsf", h, p["mlp"]["wi"]))
        return x + mat("bsf,fd->bsd", u, p["mlp"]["wo"]), None

    x, _ = jax.lax.scan(layer, x, params["segments"][0])
    return _layernorm(x, params["final_norm"])


def logits(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    return mat("bsd,vd->bsv", hidden(params, tokens, m, mat, q_block), params["embed"])


def loss_sum(params, tokens, m: dict, mat: Mat = F32, q_block: int = 512):
    """Summed next-token NLL over the rows of ``tokens``."""
    return token_nll_sum(logits(params, tokens, m, mat, q_block)[:, :-1], tokens[:, 1:])
