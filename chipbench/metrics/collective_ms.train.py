"""Time per training step in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) is outstanding, from each
``-start`` to its ``-done``, averaged over the chips: an upper bound on the
time its transfers take.  Reads nothing where the trace holds no collective."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["collective_s"] or ctx["traffic"]["kind"] != "train":
        return None
    return 1000.0 * t["collective_s"] / ctx["counts"]["steps"]
