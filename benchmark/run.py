#!/usr/bin/env python3
"""Run one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/``, its
traffic in ``benchmark/traffic/<traffic>.json``, the limits of its
comparison in ``benchmark/limits/<workload>.json``, and each per-layer
metric in ``benchmark/metrics/<metric>.py``. The configuration's
``entry`` names the module that runs it (``train``).

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` the ``breakdown``, and last ``checks``: each compared
number beside its limit, which also close standard error. Without a
chip, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def load_cell(workload: str) -> tuple[dict, dict, dict, dict, dict]:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise harness.BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((harness.ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return spec, cell, config, traffic, limits


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    module_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def _finite(value) -> bool:
    return value is not None and math.isfinite(value)


def measure(cell: harness.Cell, spec: dict) -> dict:
    """Run the cell and build its result line."""
    runner = importlib.import_module(cell.config["entry"])
    reading = runner.run(cell)
    layer = dict(reading["layer"], workload=cell.workload)
    # every per-layer metric that needs no trace, on an earlier line
    harness.emit({"layers": {m["name"]: metric_reader(m["name"])(layer)
                             for m in spec["per_layer"]
                             if applies(m, cell.workload) and m["source"] != "device_trace"}})
    metrics = {}
    if not cell.trace:
        for m in spec["end_to_end"]:
            if not applies(m, cell.workload):
                continue
            value = (reading["setup"].total if m["name"] == "setup_s"
                     else reading["e2e"][m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if not applies(m, cell.workload):
                continue
            value = metric_reader(m["name"])(layer)
            if _finite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(reading["device"])
    checks = harness.checks(reading["values"], cell.limits)
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": reading["attempted"],
        "failed": reading["failed"],
        "metrics": metrics,
        "device": device,
    }
    if cell.trace:
        trace = reading["trace"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    # strict JSON: a number that is not finite is written as null
    result["checks"] = {k: {"value": c["value"] if _finite(c["value"]) else None,
                            "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--platform", default="tpu",
                    help="cpu: a rehearsal off the chip, whose numbers are no "
                         "device metric")
    ap.add_argument("--fault", choices=["half_batch", "unchanged"], default=None,
                    help="plant a fault in the timed path, to read what the "
                         "comparison gives for it")
    args = ap.parse_args(argv)
    harness.use_cache_here()
    try:
        spec, entry, config, traffic, limits = load_cell(args.workload)
        cell = harness.Cell(workload=args.workload, config=config, traffic=traffic,
                            limits=limits, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), chips=entry["chips"],
                            t_start=T_START, platform=args.platform,
                            fault=args.fault)
        result = measure(cell, spec)
    except (harness.BenchError, FileNotFoundError, KeyError, ImportError) as exc:
        print(f"benchmark: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = _finite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
