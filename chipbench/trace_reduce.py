"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a plain record:

    {"devices": {plane: [[op name, start ns, duration ns], ...]},
     "host": [[span name, start ns, duration ns], ...]}

with the op line of every TPU plane and the harness's own host spans
(names starting ``chipbench.``).  ``reduce`` works on that record alone,
so the tests can feed it a small recorded one.  Per device it takes:

* busy time: the union of the op intervals inside the window span;
* op time by name (the ten largest go to ``breakdown.device_ops``);
* collective time: the union of the intervals in which a collective
  (``all-reduce``, ``all-gather``, ``reduce-scatter``,
  ``collective-permute``, ``all-to-all``) is in flight.  An asynchronous
  one shows as a short ``-start`` op and a ``-done`` op that waits for it;
  the two are paired by name, in order, into one interval from the start
  of the one to the end of the other, so the transfer between them counts;
* exposed collective time: the part of those intervals in which no other
  op runs on that device;
* idle gaps, each put down to the host span that overlaps it most.

A TPU's op line runs its ops one after another; the only ops that overlap
others are containers (``while``, ``call``, ``conditional``), which are
listed beside the ops of their bodies.  The union counts each instant
once, and containers cover nothing in the exposed time: compute that
hides a collective is an op of a body, not the loop around it.

Device numbers are averaged over the devices.
"""

from __future__ import annotations

import glob
import os
import re

import jax

WINDOW = "chipbench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
CONTAINERS = ("while", "call", "conditional")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINE = "XLA Ops"


class CompileCounter:
    """Counts backend compiles and cache loads while the context is open."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self._on and event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device of this process."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def op_kind(name: str) -> str:
    """``all-reduce-start.12 f32[8]`` → ``all-reduce``; ``fusion.3`` → ``fusion``."""
    base = re.sub(r"\.\d+$", "", name.split(" ")[0])
    return re.sub(r"-(start|done|update)$", "", base)


def _phase(name: str) -> tuple[str, str]:
    """(pair key, phase) of an op: ``collective-permute-start.4 …`` →
    (``collective-permute.4``, ``start``); an op with no phase → (name, "")."""
    m = re.match(r"^(.*?)-(start|done|update)(\.\d+)?$", name.split(" ")[0])
    if not m:
        return name.split(" ")[0], ""
    return m.group(1) + (m.group(3) or ""), m.group(2)


def collective_intervals(events) -> list:
    """In-flight intervals of the collectives among ``events`` (``[name,
    start, duration]`` of one op line): each ``-start`` paired with the next
    ``-done`` of the same name, a synchronous one by itself.  A ``-done``
    with no start before it begins its interval at its own start; a
    ``-start`` with no done after it ends at its own end."""
    out, open_ = [], {}
    for n, s, d in sorted(events, key=lambda e: e[1]):
        if op_kind(n) not in COLLECTIVES:
            continue
        key, phase = _phase(n)
        if phase == "start":
            open_.setdefault(key, []).append((s, s + d))
        elif phase == "done":
            pending = open_.get(key)
            out.append((pending.pop(0)[0] if pending else s, s + d))
        else:  # synchronous, or an update inside a pair
            out.append((s, s + d))
    out.extend(iv for pending in open_.values() for iv in pending)
    return out


def short_name(hlo: str) -> str:
    """``%fusion.544 = bf16[8,512,4096]{2,1,0:…} fusion(…)`` → ``fusion.544 bf16[8,512,4096]``:
    the op's name and the shape of what it writes (a tuple gives none)."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    shape = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return name.lstrip("%") + (" " + shape.group(0) if shape else "")


def options():
    """Profiler options: no Python call tracing, host spans at level 1 (the
    harness's own ``TraceAnnotation`` spans), which keeps the trace small."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def load(trace_dir: str) -> dict:
    """The plain record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {"devices": {}, "host": []}
    pd = ProfileData.from_file(files[-1])
    rec = {"devices": {}, "host": []}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OP_LINE:
                    rec["devices"][plane.name] = [
                        [short_name(e.name), e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name.startswith("chipbench."))
    return rec


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the (sorted, disjoint) intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def reduce(rec: dict, top: int = 10) -> dict | None:
    """Per-device busy, op, collective and idle numbers inside the window.

    Returns None when the record holds no device op (nothing to read)."""
    devices = {k: v for k, v in rec["devices"].items() if v}
    if not devices:
        return None
    spans = [(n, s, s + d) for n, s, d in rec["host"] if n.startswith("chipbench.")]
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for ev in devices.values() for _, s, _ in ev)
        w1 = max(s + d for ev in devices.values() for _, s, d in ev)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    nd = len(devices)
    busy = coll = exposed = 0.0
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    for events in devices.values():
        clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in events
                   if s + d > w0 and s < w1]
        busy_iv = _union([(s, e) for _, s, e in clipped])
        busy += _length(busy_iv)
        for n, s, e in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s)
        c_iv = _union([(max(s, w0), min(e, w1)) for s, e in collective_intervals(events)
                       if e > w0 and s < w1])
        o_iv = _union([(s, e) for n, s, e in clipped
                       if op_kind(n) not in COLLECTIVES + CONTAINERS])
        coll += _length(c_iv)
        exposed += _length(_subtract(c_iv, o_iv))
        for g0, g1 in _subtract([[w0, w1]], busy_iv):
            best, label = 0.0, "none"
            for n, s, e in inner:
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, label = ov, n
            idle[label] = idle.get(label, 0.0) + (g1 - g0)
    ns = 1e-9
    span_tot: dict[str, list] = {}
    for n, s, e in inner:
        if e > w0 and s < w1:
            c = span_tot.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (min(e, w1) - max(s, w0)) * ns
    by = lambda d: sorted(([k, v * ns / nd] for k, v in d.items()),
                          key=lambda kv: -kv[1])[:top]
    return {
        "devices": nd,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy * ns / nd,
        "collective_s": coll * ns / nd,
        "collective_exposed_s": exposed * ns / nd,
        "device_ops": by(ops),
        "idle_gaps": by(idle),
        "spans": span_tot,
    }
