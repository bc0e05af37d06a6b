"""Share of the traced training window in which no op runs on the device.

1 − (union of device op intervals ÷ window), averaged over the chips.
Moves ``train_tokens_per_s``: idle time is host work between steps
(the data feed, the loss read, dispatch)."""


def read(ctx):
    t = ctx["trace"]
    if not t or ctx["traffic"]["kind"] != "train":
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
