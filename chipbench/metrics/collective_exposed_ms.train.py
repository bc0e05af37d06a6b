"""The part of ``collective_ms.train`` in which no other op (a loop around
ops does not count) runs on that chip: the collective time that compute does
not hide, per training step, averaged over the chips.  Reads nothing where
the trace holds no collective."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["collective_s"] or ctx["traffic"]["kind"] != "train":
        return None
    return 1000.0 * t["collective_exposed_s"] / ctx["counts"]["steps"]
