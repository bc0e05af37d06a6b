"""On-chip benchmark: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for.  Everything is found by name from ``BENCHMARK.json``:

* the cell's traffic mix: ``chipbench/traffic/<cell>.json``, whose
  ``kind`` picks the driver ``chipbench/<kind>_loop.py`` (``train_loop``
  now; a serving driver is a new file beside it);
* its configuration: the ``file`` that ``BENCHMARK.json`` gives, whose
  ``family`` picks the float32 reference ``chipbench/reference/<family>.py``;
* each per-layer metric: ``chipbench/metrics/<metric>.py``, a ``read(ctx)``
  that returns a number or None;
* the chip's peaks: ``chipbench/peaks.json``, keyed by ``device_kind``.

So a later change adds a cell, a configuration or a metric by adding
files and ``BENCHMARK.json`` entries.  The program under test is loaded
from ``src/``.  JAX's persistent compile cache is the program's own
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` when that
is set, else ``.jax_cache`` at the root of the checkout.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with the
reference beside its limit.  The same numbers end standard error.  A run
that finds no TPU, or fewer chips than the cell asks for, exits nonzero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(Exception):
    """The run cannot produce a result; the message says why."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def list_cells(root: Path = ROOT) -> list[str]:
    return [w["name"] for w in load_benchmark(root)["workloads"]]


def cell_spec(bench: dict, name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of a cell."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r}; have {[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic" / f"{name}.json").read_text())
    return wl, config, traffic


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def end_to_end_names(bench: dict, cell: str) -> list[str]:
    return [m["name"] for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer_entries(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["per_layer"] if cell in m["workloads"]]


def load_metric(name: str, root: Path = ROOT):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache() -> str:
    """The program's compile cache (``JAX_COMPILATION_CACHE_DIR``, else
    ``.jax_cache`` in the checkout), keeping every program however small or
    quick to compile, so that a second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return program_cache()


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peak_of(kind: str, require: bool) -> dict | None:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks and require:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks.get(kind)


def measure(args, root: Path = ROOT, require_tpu: bool = True,
            compile_cache: bool = True) -> dict:
    """Run one cell once; the result dictionary (without printing it).

    The tests pass ``require_tpu=False`` to drive a run on the CPU, and
    ``compile_cache=False`` to leave JAX's configuration alone."""
    bench = load_benchmark(root)
    wl, config, traffic = cell_spec(bench, args.workload, root)
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"{root} holds no program (src/repro)")
    for p in (str(HERE), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if compile_cache:
        use_compile_cache()
    device = device_info(wl["chips"], require_tpu)
    peak = peak_of(device["kind"], require_tpu)
    family = importlib.import_module(f"reference.{config['family']}")
    loop = importlib.import_module(f"{traffic['kind']}_loop")
    cell = loop.build(config, traffic, wl["chips"], family)
    trace = bool(args.trace)
    seconds = min(args.seconds, traffic["trace_seconds"]) if trace else args.seconds
    res = loop.run(cell, args.seed, seconds, trace, T_START, traffic["check"]["limits"])
    checks = res["checks"]
    correct = res["failed"] == 0 and all(v <= lim for v, lim, _ in checks.values())
    if trace:
        ctx = {"trace": res["trace"], "counts": res["window"], "model": config["model"],
               "traffic": traffic, "chips": wl["chips"], "peak": peak,
               "flops": importlib.import_module("flops")}
        metrics = {}
        for m in per_layer_entries(bench, args.workload):
            v = load_metric(m["name"], root).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(res["e2e"], setup_s=res["setup_s"])
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {n: {"value": float(values[n]), "unit": units[n]}
                   for n in end_to_end_names(bench, args.workload)}
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": bool(correct), "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if trace and res["trace"]:
        t = res["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    out["notes"] = {"compiles_in_window": res["compiles_in_window"],
                    "window_steps": res["window"]["steps"]}
    out["checks"] = {k: {"value": float(v), "limit": float(lim)}
                     for k, (v, lim, _) in checks.items()}
    out["_where"] = {k: where for k, (_, _, where) in checks.items()}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = measure(args)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    where = out.pop("_where")
    for k, c in out["notes"].items():
        print(f"note {k} {c}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} (worst at {where[k] or '-'})",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
