"""Readings that the limits of a training cell are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 --out <dir>/<cell>.json

In one process, at the cell's own size and through the same set-up and
step as ``run.py`` (no measured window): for every seed, the program's
first steps against the float32 reference; for every control seed, the
control (the reference computed with float8 products, one precision step
below the configuration's bfloat16 compute) and the planted faults
(reference variants put in the program's place) against the reference:

* ``half_batch``: the loss and gradient of half of the batch's rows (of
  the sequence's positions where the batch is one row);
* ``no_exchange`` (cells on several chips): those of one chip's rows alone,
  as a replica sees them when the gradient exchange is left out;
* ``frozen`` (a step that returns its state unchanged) needs no run: it
  reads 1 by the gap measure of ``train_loop.compare``.

Each number's limit is then set from two readings (``limits_from``): the
lower, the largest that the program gives over the seeds; the upper, the
smallest of the control's readings where those are three times the lower
or more, and of each fault's where those are ten times the lower or more
(the frozen state's 1 where that is three times).  The limit is
lower^0.4 · upper^0.6 to two figures: between the two, with more room
above the lower, since fresh seeds read higher than a dozen did.
``--write-limits`` writes them into the cell's traffic file.

The benchmark's own runs never run this.  ``PERF.md`` keeps the readings
and the limits set from them.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import run


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def limits_from(readings: dict) -> tuple[dict, list[str]]:
    """Limits from a calibration's readings, and a report line per number."""
    limits, report = {}, []
    for k in next(iter(readings["program"].values())):
        lower = max(r[k][0] for r in readings["program"].values())
        ups = {}
        ctl = [r[k][0] for r in readings["control"].values()]
        if ctl and min(ctl) >= 3 * lower:
            ups["control"] = min(ctl)
        for fault in ("half_batch", "no_exchange"):
            got = [r[k][0] for r in readings[fault].values()]
            if got and min(got) >= 10 * lower:
                ups[fault] = min(got)
        if k != "loss_gap" and 1.0 >= 3 * lower:
            ups["frozen"] = 1.0
        if not ups:
            report.append(f"{k}: lower {lower:.4g}, no upper reading: no limit holds")
            continue
        by = min(ups, key=ups.get)
        limits[k] = float(f"{lower ** 0.4 * ups[by] ** 0.6:.2g}")
        report.append(f"{k}: lower {lower:.4g}, upper {ups[by]:.4g} ({by}), "
                      f"limit {limits[k]:.4g}; uppers {ups}")
    for kind in ("control", "half_batch", "no_exchange"):
        for seed, r in readings[kind].items():
            failed = [k for k in limits if r[k][0] > limits[k]]
            report.append(f"{kind} seed {seed} fails {failed or 'NOTHING'}")
    return limits, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limits", action="store_true",
                    help="write the limits set from these readings into the traffic file")
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    wl, config, traffic = run.cell_spec(bench, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    device = run.device_info(wl["chips"], require_tpu=True)
    import train_loop
    from reference.common import FP8

    family = importlib.import_module(f"reference.{config['family']}")
    cell = train_loop.build(config, traffic, wl["chips"], family)
    out = {"workload": args.workload, "device": device, "program": {}, "control": {},
           "half_batch": {}, "no_exchange": {}}
    b, s = cell.batch, cell.seq
    half = (slice(0, b // 2), slice(None)) if b > 1 else (slice(None), slice(0, s // 2))

    def record(kind, seed, nums, t0):
        out[kind][seed] = {k: list(v) for k, v in nums.items()}
        print(f"{kind} seed {seed}: " + ", ".join(f"{k}={v[0]:.4g} ({v[1]})"
                                                  for k, v in nums.items())
              + f"  [{time.perf_counter() - t0:.1f}s]", flush=True)

    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        prog = cell.check_steps(seed)
        cell.free()
        ref = cell.reference(seed)
        record("program", seed, train_loop.compare(prog, ref), t0)
        if seed in seeds(args.control_seeds):
            t0 = time.perf_counter()
            record("control", seed, train_loop.compare(cell.reference(seed, mat=FP8), ref), t0)
            t0 = time.perf_counter()
            record("half_batch", seed,
                   train_loop.compare(cell.reference(seed, rows=half), ref), t0)
            if wl["chips"] > 1:
                t0 = time.perf_counter()
                one = (slice(0, b // wl["chips"]), slice(None))
                record("no_exchange", seed,
                       train_loop.compare(cell.reference(seed, rows=one), ref), t0)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    limits, report = limits_from(out)
    out["limits"], out["report"] = limits, report
    print("\n".join(report), flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if args.write_limits:
        path = run.ROOT / "chipbench" / "traffic" / f"{args.workload}.json"
        traffic["check"]["limits"] = limits
        path.write_text(json.dumps(traffic, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
