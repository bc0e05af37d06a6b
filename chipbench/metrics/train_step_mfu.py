"""Model FLOP utilisation of the whole training step.

Model FLOPs per token (``flops.train_flops_per_token``: forward and
backward, no recomputation) × tokens trained per second in the window ÷
(chips × the chip's bf16 peak).  Bounds every kernel's gain: a kernel
taken off the path leaves its own roofline silent, not this."""


def read(ctx):
    if ctx["traffic"]["kind"] != "train" or not ctx["peak"]:
        return None
    w = ctx["counts"]
    per_token = ctx["flops"].train_flops_per_token(ctx["model"], ctx["traffic"]["seq_len"])
    rate = w["tokens"] / w["window_s"]
    return 100.0 * per_token * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
