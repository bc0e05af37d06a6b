"""Model FLOP utilisation of the whole training step of a latent-attention,
mixture-of-experts model.

Model FLOPs per token (``flops_mla_moe.train_flops_per_token``: forward and
backward, no recomputation, the routed experts held here at the share of
tokens that routes to them) × tokens trained per second in the window ÷
(chips × the chip's bf16 peak)."""


def read(ctx):
    if ctx["traffic"]["kind"] != "train" or not ctx["peak"]:
        return None
    import flops_mla_moe

    w = ctx["counts"]
    per_token = flops_mla_moe.train_flops_per_token(ctx["model"], ctx["traffic"]["seq_len"])
    rate = w["tokens"] / w["window_s"]
    return 100.0 * per_token * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
