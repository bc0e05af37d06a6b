"""Device time by the program's layers, from a profiler trace.

The program names its layers with ``jax.named_scope``: ``embed``,
``block`` with ``attention``, ``mlp`` and ``moe`` inside it, ``head``,
``optimizer``, and ``grad_comm`` with ``grad_comm/pack``,
``grad_comm/bucket<i>`` and ``grad_comm/unpack``.  The names reach a TPU
trace as each op's HLO ``op_name`` (JAX's name stack), in the
``OP_NAME_STAT`` stat of the op's event metadata.  ``ProfileData`` does not
show metadata stats, so ``op_names`` reads them from the ``.xplane.pb``
itself.

``load`` is ``trace_reduce.load`` with one more key,

    "scoped": {plane: [[scope, direction, start ns, duration ns], ...]}

which lists the plane's non-container ops in the order of its op line,
each with its innermost scope (``scope_of``) and whether it runs in the
forward or the backward pass (``direction_of``).  ``reduce`` works on that
record alone and gives, inside the window span and averaged over devices:

* ``scope_s``: op time by scope (``"none"`` for an op with no scope);
* ``scope_dir_s``: the same split into ``fwd`` and ``bwd``;
* ``grad_comm_compute_s``: the exchange's own compute, ops under
  ``grad_comm`` that are neither collectives nor containers.

Run on the chip, it times one training cell of ``BENCHMARK.json`` by layer:

    python3 chipbench/scope_reduce.py --workload <cell> --seed <n> --seconds <s>

which makes the cell's state, runs an untimed warm-up, an untraced window
and a traced window of ``--seconds`` each, and prints one JSON line: the
step time of both windows and ms per step by scope.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import re
import sys
import tempfile

import trace_reduce
from trace_reduce import COLLECTIVES, CONTAINERS, WINDOW, op_kind

#: the stat that holds a TPU op's HLO ``op_name``, with ``:`` and the op
#: type after it
OP_NAME_STAT = "tf_op"
SCOPES = ("embed", "block", "attention", "mlp", "moe", "head", "optimizer", "grad_comm")
_GRAD_COMM_PART = re.compile(r"^(pack|unpack|bucket\d+)$")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The innermost of the program's scopes (``SCOPES``, and
    ``grad_comm/pack``, ``grad_comm/bucket<i>``, ``grad_comm/unpack``) on an
    op's HLO ``op_name``, or ``"none"``.

    JAX writes a transformation into the name stack as a wrapper of the
    next name: ``jit(step)/transpose(jvp(head))/bsd,vd->bsv/dot_general``
    is the backward of an op under ``head``.  Wrappers are stripped from
    each part before it is compared."""
    scope = "none"
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            scope = part
        elif scope.startswith("grad_comm") and _GRAD_COMM_PART.match(part):
            scope = "grad_comm/" + part
    return scope


def direction_of(op_name: str) -> str:
    """``bwd`` where the op's name stack holds a transpose (the backward
    pass, recomputation under ``jax.checkpoint`` included), else ``fwd``."""
    return "bwd" if "transpose(" in op_name else "fwd"


def load(trace_dir: str) -> dict:
    """``trace_reduce.load`` of the newest ``.xplane.pb`` under
    ``trace_dir``, with ``"scoped"`` beside each TPU op line."""
    from jax.profiler import ProfileData

    rec = trace_reduce.load(trace_dir)
    rec["scoped"] = {}
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return rec
    with open(files[-1], "rb") as f:
        raw = f.read()
    names = op_names(raw)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name not in rec["devices"]:
            continue
        by_event = names.get(plane.name, {})
        for line in plane.lines:
            if line.name != trace_reduce._OP_LINE:
                continue
            scoped = rec["scoped"][plane.name] = []
            for e in line.events:
                if op_kind(trace_reduce.short_name(e.name)) not in CONTAINERS:
                    n = by_event.get(e.name, "")
                    scoped.append([scope_of(n), direction_of(n), e.start_ns, e.duration_ns])
    return rec


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a serialized protobuf
    message; a length-delimited value is a view of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """``{TPU plane: {op event name: HLO op_name}}`` from a serialized
    ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``).

    The name stack sits in the ``OP_NAME_STAT`` stat of each op's event
    metadata (``XPlane.event_metadata``, field 4, whose ``stats`` are field
    5), with its string inline or as a reference to a stat metadata's name
    (``XPlane.stat_metadata``, field 5).  An event name that two programs
    of the plane give different op names maps to ``""``."""
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:  # map entry: key 1, value 2 (XStatMetadata: id 1, name 2)
                for mf, mv in _fields(v):
                    if mf == 2:
                        fields = dict(_fields(mv))
                        stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        if not trace_reduce._DEVICE_PLANE.match(name):
            continue
        by_event: dict[str, str] = {}
        for entry in metas:  # map entry: key 1, value 2 (XEventMetadata)
            for mf, meta in _fields(entry):
                if mf != 2:
                    continue
                ev_name, op_name = "", ""
                for f, v in _fields(meta):
                    if f == 2:
                        ev_name = bytes(v).decode()
                    elif f == 5:  # XStat: metadata_id 1, str_value 5, ref_value 7
                        stat = dict(_fields(v))
                        if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                            op_name = (bytes(stat[5]).decode() if 5 in stat
                                       else stat_names.get(stat.get(7), ""))
                if by_event.setdefault(ev_name, op_name) != op_name:
                    by_event[ev_name] = ""
        out[name] = by_event
    return out


def reduce(rec: dict) -> dict | None:
    """Op time by scope, by scope and direction, and the exchange's own
    compute, inside the window span (as ``trace_reduce.reduce`` takes it),
    averaged over devices.  None when the record holds no device op.

    Raises ValueError where ``rec["scoped"]`` does not list the op line's
    non-container ops in order."""
    devices = {k: v for k, v in rec["devices"].items() if v}
    if not devices:
        return None
    win = [(s, s + d) for n, s, d in rec["host"] if n == WINDOW]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for ev in devices.values() for _, s, _ in ev)
        w1 = max(s + d for ev in devices.values() for _, s, d in ev)
    scopes: dict[str, float] = {}
    scope_dir: dict[str, dict[str, float]] = {}
    compute = 0.0
    for plane, events in devices.items():
        named = [(n, s) for n, s, _ in events if op_kind(n) not in CONTAINERS]
        scoped = rec.get("scoped", {}).get(plane, [])
        if scoped and (len(named) != len(scoped)
                       or any(s != sc[2] for (_, s), sc in zip(named, scoped))):
            raise ValueError(f"{plane}: the scoped ops do not match the op line")
        for (n, _), (scope, direction, s, d) in zip(named, scoped):
            t = min(s + d, w1) - max(s, w0)
            if t <= 0:
                continue
            scopes[scope] = scopes.get(scope, 0.0) + t
            scope_dir.setdefault(scope, {"fwd": 0.0, "bwd": 0.0})[direction] += t
            if scope.startswith("grad_comm") and op_kind(n) not in COLLECTIVES:
                compute += t
    per = 1e-9 / len(devices)
    return {
        "scope_s": {k: v * per for k, v in sorted(scopes.items(), key=lambda kv: -kv[1])},
        "scope_dir_s": {k: {d: v * per for d, v in dv.items()}
                        for k, dv in sorted(scope_dir.items())},
        "grad_comm_compute_s": compute * per,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    import run

    root = run.ROOT
    sys.path.insert(0, str(root / "src"))
    try:
        wl, config, traffic = run.cell_spec(run.load_benchmark(root), args.workload, root)
        if traffic["kind"] != "train":
            raise run.BenchError(f"{args.workload} is not a training cell")
        run.use_compile_cache()
        device = run.device_info(wl["chips"], require_tpu=True)
    except run.BenchError as e:
        print(f"scope_reduce: {e}", file=sys.stderr)
        return 3
    family = importlib.import_module(f"reference.{config['family']}")
    cell = importlib.import_module("train_loop").build(config, traffic, wl["chips"], family)
    cell.check_steps(args.seed)
    cell.window(args.seed, 1.0, False)
    plain = cell.window(args.seed, args.seconds, False)
    with tempfile.TemporaryDirectory(prefix="chipbench-scopes-") as tdir:
        with jax.profiler.trace(tdir, profiler_options=trace_reduce.options()):
            traced = cell.window(args.seed, args.seconds, True)
        rec = load(tdir)
    whole, scoped = trace_reduce.reduce(rec), reduce(rec)
    if scoped is None:
        print("scope_reduce: the trace holds no device op", file=sys.stderr)
        return 3
    ms = 1000.0 / traced["steps"]
    named = sum(scoped["scope_s"].values())
    out = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "step_ms": {"untraced": 1000.0 * plain["window_s"] / plain["steps"],
                    "traced": 1000.0 * traced["window_s"] / traced["steps"]},
        "busy_ms": whole["busy_s"] * ms,
        "scope_ms": {k: v * ms for k, v in scoped["scope_s"].items()},
        "scope_dir_ms": {k: {d: v * ms for d, v in dv.items()}
                         for k, dv in scoped["scope_dir_s"].items()},
        "grad_comm_compute_ms": scoped["grad_comm_compute_s"] * ms,
        "none_pct": 100.0 * scoped["scope_s"].get("none", 0.0) / named if named else None,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
