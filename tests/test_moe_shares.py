"""The dropless expert layer that is told which experts it holds
(``models/moe.apply_moe``): one chip's share of the router's experts, no
token dropped however the router skews, DeepSeek-V2's per-sequence balance
loss, and YaRN's rotary frequencies and softmax scale (``models/layers``,
``models/attention``) against their formulas."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models.attention import mla_softmax_scale
from repro.models.layers import apply_rope, rope_frequencies, yarn_frequencies, yarn_mscale
from repro.models.moe import apply_moe, init_moe

D, F, E, K = 32, 48, 16, 4


def layer(seed=0, shared=2):
    p = init_moe(jax.random.PRNGKey(seed), D, F, E, E, shared, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, D), jnp.float32)
    return p, x


def share(p, first, held, with_shared):
    q = {k: p[k][first:first + held] for k in ("wi", "wg", "wo")}
    q["router"] = p["router"]
    if with_shared:
        q["shared"] = p["shared"]
    return q


def per_token(p, x, norm_topk, first=0):
    """The layer one token at a time, in plain loops."""
    held = p["wi"].shape[0]
    xs = np.asarray(x, np.float64).reshape(-1, D)
    out = np.zeros_like(xs)
    pn = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    for t, v in enumerate(xs):
        logits = v @ pn["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:K]
        gates = probs[top] / (probs[top].sum() if norm_topk else 1.0)
        for g, e in zip(gates, top):
            if first <= e < first + held:
                i = e - first
                u = v @ pn["wg"][i]
                h = u / (1 + np.exp(-u)) * (v @ pn["wi"][i])
                out[t] += g * (h @ pn["wo"][i])
        if "shared" in pn:
            sp = pn["shared"]
            u = v @ sp["wg"]
            out[t] += (u / (1 + np.exp(-u)) * (v @ sp["wi"])) @ sp["wo"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("held", [2, 4, 8])
def test_shares_sum_to_the_uncut_layer(held):
    """E/held chips each hold ``held`` experts; their parts, with the shared
    experts counted once, add up to the layer that holds all E, and every
    chip reads the same balance loss (the router is whole on each)."""
    p, x = layer()
    with jax.default_matmul_precision("highest"):
        whole, aux = apply_moe(p, x, K, False)
        parts = [apply_moe(share(p, f, held, f == 0), x, K, False, first_expert=f)
                 for f in range(0, E, held)]
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    for _, a in parts:
        assert float(a) == pytest.approx(float(aux), rel=1e-6)


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("first,held", [(0, E), (4, 4)])
def test_matches_a_per_token_loop(norm_topk, first, held):
    p, x = layer(seed=3)
    q = share(p, first, held, True)
    with jax.default_matmul_precision("highest"):
        y, _ = apply_moe(q, x, K, norm_topk, first_expert=first)
    np.testing.assert_allclose(np.asarray(y), per_token(q, x, norm_topk, first),
                               rtol=1e-4, atol=1e-5)


def test_skewed_router_drops_no_token():
    """A router under which every token picks the same K experts: each of
    them gets all 48 tokens (a GShard capacity of 1.25 would have kept 9),
    and the result still equals the per-token loop."""
    p, x = layer(seed=5, shared=0)
    x = jnp.abs(x) + 0.1
    router = jnp.zeros((D, E)).at[:, 3:3 + K].set(jnp.linspace(2.0, 1.0, K))
    p = dict(p, router=router)
    with jax.default_matmul_precision("highest"):
        y, aux = apply_moe(p, x, K, False)
    want = per_token(p, x, False)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert np.all(np.abs(want).sum(-1) > 0)  # every token got its experts' output
    assert float(aux) > 3.0  # all load on K of E experts: far above the even 1.0


def test_gradients_match_the_loop_form():
    """Gradients through the grouped products, the gates and the router
    equal those of a dense form of the same layer (every held expert on
    every token, times its gate)."""
    p, x = layer(seed=7)
    q = share(p, 4, 8, True)

    def dense(q, x):
        probs = jax.nn.softmax(x @ q["router"], -1)
        top, idx = jax.lax.top_k(probs, K)
        gate = jnp.einsum("bsk,bske->bse", top, jax.nn.one_hot(idx, E))[..., 4:12]
        u = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, q["wg"])) * jnp.einsum(
            "bsd,edf->bsef", x, q["wi"])
        y = jnp.einsum("bsef,efd->bsd", u * gate[..., None], q["wo"])
        sp = q["shared"]
        return y + (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wi"])) @ sp["wo"]

    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        ga = jax.grad(lambda q, x: jnp.sum(apply_moe(q, x, K, False, 4)[0] * w), (0, 1))(q, x)
        gb = jax.grad(lambda q, x: jnp.sum(dense(q, x) * w), (0, 1))(q, x)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_balance_loss_is_per_sequence():
    """aux = mean over rows of Σ_i f_i·P_i, f_i = E/(K·S)·count_i in the
    row, P_i the row's mean score: by hand, with numpy."""
    p, x = layer(seed=11)
    _, aux = apply_moe(p, x, K, False)
    logits = np.einsum("bsd,de->bse", np.asarray(x, np.float64), np.asarray(p["router"]))
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    b, s, _ = probs.shape
    rows = []
    for r in range(b):
        count = np.zeros(E)
        for t in range(s):
            count[np.argsort(-probs[r, t])[:K]] += 1
        rows.append(np.sum(E / (K * s) * count * probs[r].mean(0)))
    assert float(aux) == pytest.approx(np.mean(rows), rel=1e-5)


def test_decode_token_uses_the_same_layer():
    """A single position (decode) gives that position's row of the
    full-sequence layer: no capacity depends on the sequence length."""
    p, x = layer(seed=13)
    with jax.default_matmul_precision("highest"):
        full, _ = apply_moe(p, x, K, False)
        one, _ = apply_moe(p, x[:, 5:6], K, False)
    np.testing.assert_allclose(np.asarray(one[:, 0]), np.asarray(full[:, 5]),
                               rtol=1e-5, atol=1e-6)


def test_yarn_frequencies_against_the_formula():
    """DeepSeek-V2-Lite's rope dims (64, θ 10⁴) under factor 40 over 4096:
    correction dims ⌊64·ln(4096/(32·2π))/(2 ln 10⁴)⌋ = 10 and
    ⌈64·ln(4096/(2π))/(2 ln 10⁴)⌉ = 23, a linear ramp between."""
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4)))
    assert (lo, hi) == (10, 23)
    base = np.asarray(rope_frequencies(64, 1e4))
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    got = np.asarray(yarn_frequencies(64, 1e4, 40.0, 4096, 32.0, 1.0))
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 40 * ramp, rtol=1e-6)


def test_yarn_softmax_scale_and_plain_configs():
    cfg = get_config("deepseek-v2-lite-16b")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    assert mla_softmax_scale(cfg.replace(rope_yarn_factor=0.0)) == 1 / math.sqrt(192)
    assert yarn_mscale(40.0, 0.0) == 1.0 and yarn_mscale(1.0, 0.707) == 1.0
    # a config without YaRN rotates with RoPE's own frequencies
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, pos, 1e4)),
        np.asarray(apply_rope(x, pos, inv_freq=rope_frequencies(16, 1e4))))


def test_smoke_config_keeps_the_published_mechanisms():
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    full = get_config("deepseek-v2-lite-16b")
    for c in (cfg, full):
        assert not c.norm_topk_prob and c.aux_loss_weight == 0.001
        assert c.rope_yarn_factor == 40.0 and c.rope_yarn_mscale_all_dim == 0.707
    assert full.moe_router_experts == full.moe_experts == 64
    assert get_config("dbrx-132b").norm_topk_prob


def test_scopes_name_the_moe_and_latent_ops():
    """The lowered train loss of DeepSeek's smoke config names its ops
    under ``attention/latent`` and ``moe/{route,dispatch,experts,combine,
    shared}``, forward and backward; the grouped products are ``experts``'."""
    from repro.models import init_params, loss_fn

    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg))).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("attention/latent", "moe/route", "moe/dispatch", "moe/experts",
                  "moe/combine", "moe/shared"):
        assert scope + "/" in text, scope
    ragged = [ln for ln in text.splitlines() if "ragged_dot" in ln and "loc(" in ln]
    assert ragged and all("experts" in ln for ln in ragged)


def test_rows_past_the_groups_never_reach_the_result(monkeypatch):
    """The grouped product leaves the rows past its groups undefined (on
    TPU, whatever the buffer held).  A product that fills them, and the
    rows of its input cotangent, with NaN changes neither the layer's
    output nor any gradient."""
    real = jax.lax.ragged_dot

    def nan_past(a, sizes):
        return jnp.where(jnp.arange(a.shape[0])[:, None] < jnp.sum(sizes), a, jnp.nan)

    @jax.custom_vjp
    def leaky(lhs, rhs, sizes):
        return nan_past(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return leaky(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        da, db = jax.vjp(lambda a, w: real(a, w, sizes), lhs, rhs)[1](g)
        return nan_past(da, sizes), db, None

    leaky.defvjp(fwd, bwd)
    p, x = layer(seed=17)
    q = share(p, 4, 4, True)
    w = jax.random.normal(jax.random.PRNGKey(19), x.shape)

    def run(q, x):
        y, aux = apply_moe(q, x, K, False, first_expert=4)
        return jnp.sum(y * w) + aux

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(run, (0, 1))(q, x)
        monkeypatch.setattr(jax.lax, "ragged_dot", leaky)
        got = jax.value_and_grad(run, (0, 1))(q, x)
    assert np.isnan(np.asarray(leaky(x.reshape(-1, D), q["wi"], jnp.array([1, 0, 2, 0],
                                                                          jnp.int32)))).any()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
