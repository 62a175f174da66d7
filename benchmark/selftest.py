#!/usr/bin/env python3
"""The benchmark's own tests, off the chip.

    python3 benchmark/selftest.py          # arithmetic and reductions (seconds)
    python3 benchmark/selftest.py --full   # and whole runs on the CPU (minutes)

The first part checks the operation and byte counts on hand-worked
shapes, the trace reduction on a hand-made trace and on a small trace
recorded on a TPU v5e (testdata/). ``--full`` drives whole runs on
the CPU at a small size, without the harness's look for a chip, and
sees ``correct`` come out true for a sound run and false with the
timed path broken underneath: a train step that returns its state
unchanged, and one that leaves out half of the batch. It also sees the
float8 control fail a limit.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import yardstick  # noqa: E402

FAILURES: list[str] = []


def expect(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what, flush=True)
    if not cond:
        FAILURES.append(what)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def test_counts() -> None:
    # 3 x 12 layers x (8bsd^2 + 4bs^2d + 4bsd.ff) at b=64, s=512, d=1024, ff=4096:
    # 274877906944 + 68719476736 + 549755813888 = 893353197568 per layer
    expect(yardstick.encoder_train_flops(batch=64, seq=512, d_model=1024, d_ff=4096,
                                         n_layers=12) == 32160715112448,
           "train step FLOP at 64 x 512, BERT-large widths, 12 layers")
    # b=256, s=128: 274877906944 + 17179869184 + 549755813888 = 841813590016
    # per layer
    expect(yardstick.encoder_train_flops(batch=256, seq=128, d_model=1024, d_ff=4096,
                                         n_layers=12) == 30305289240576,
           "train step FLOP at 256 x 128")
    c = yardstick.flash_pair_counts(batch=64, heads=16, seq=512, d_head=64)
    # forward 4*64*16*512^2*64, backward twice that; 8 bf16 tensors of
    # 64*16*512*64 elements (67108864 bytes each) and an f32 logsumexp
    expect(c["fwd_flops"] == 68719476736 and c["bwd_flops"] == 137438953472,
           "flash forward and backward FLOP")
    expect(c["bytes"] == 8 * 67108864 + 2097152, "flash bytes")
    t, bound = yardstick.least_seconds(c["fwd_flops"] + c["bwd_flops"], c["bytes"],
                                       "TPU v5 lite")
    expect(bound == "flops" and close(t, 206158430208 / 197e12), "flash least time at 512")
    c = yardstick.flash_pair_counts(batch=256, heads=16, seq=128, d_head=64)
    t, bound = yardstick.least_seconds(c["fwd_flops"] + c["bwd_flops"], c["bytes"],
                                       "TPU v5 lite")
    expect(bound == "bytes" and close(t, c["bytes"] / 819e9), "flash least time at 128")
    try:
        yardstick.peak("TPU v9 imaginary")
        expect(False, "an unknown device kind raises")
    except ValueError:
        expect(True, "an unknown device kind raises")


def test_reduce_handmade() -> None:
    # two device ops in a 100 us window with one 50 us gap, a host event
    # covering the gap
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 0.0, 20_000.0], ["fusion.2", 10_000.0, 20_000.0],
            ["jvp__.3", 80_000.0, 20_000.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["np.asarray(jax.Array)", 25_000.0, 60_000.0],
            ["PjitFunction(step)", 0.0, 5_000.0]]}]},
    ]}
    r = devtrace.reduce(trace)
    expect(close(r["window_s"], 100e-6) and close(r["busy_s"], 50e-6),
           "busy is the union of op intervals")
    expect(r["ops"]["fusion"]["count"] == 2 and close(r["ops"]["fusion"]["seconds"], 40e-6),
           "ops grouped without XLA's suffix")
    expect(r["idle_gaps"][0][0] == "np.asarray_jax.Array_" and close(r["idle_gaps"][0][1], 50e-6),
           "the longest gap is named by the host event that overlaps it most")


def test_reduce_recorded() -> None:
    path = BENCH / "testdata" / "v5e_train_trace.json"
    if not path.exists():
        expect(False, f"recorded trace {path.name} present")
        return
    sample = json.loads(path.read_text())
    r = devtrace.reduce(sample["trace"])
    want = sample["expect"]
    expect(close(r["busy_s"], want["busy_s"], 1e-9) and close(r["window_s"], want["window_s"], 1e-9),
           "recorded v5e trace: busy and window seconds")
    for name, count in want["op_counts"].items():
        expect(r["ops"].get(name, {}).get("count") == count,
               f"recorded v5e trace: {count} events of {name}")
    layer = {"train": want["train"], "trace": r}
    share = run_metric("flash_roofline.train", layer)
    expect(share is not None and 0 < share <= 100 and close(share, want["flash_roofline"], 1e-9),
           "recorded v5e trace: flash roofline share")


def run_metric(name: str, layer: dict):
    import run
    return run.metric_reader(name)(layer)


# -- whole runs on the CPU -----------------------------------------------------

def tiny_train_cell(workload: str, seed: int, fault: str | None = None) -> harness.Cell:
    import run
    spec, entry, config, traffic, limits = run.load_cell(workload)
    config = copy.deepcopy(config)
    # the heads keep the cell's 64 dimensions; at this size the float8
    # control's update_gap reads 0.022-0.050 on seeds 31-33 and the
    # program's under 0.003
    config["model"].update(hidden_size=128, num_attention_heads=2, intermediate_size=512,
                           num_hidden_layers=4, vocab_size=1024)
    traffic = dict(traffic, batch=16, seq_len=64)
    return harness.Cell(workload=workload, config=config, traffic=traffic, limits=limits,
                        seed=seed, seconds=1.0, trace=False, chips=1, t_start=0.0,
                        platform="cpu", fault=fault)


def train_cells() -> list[str]:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    return [w["name"] for w in spec["workloads"]
            if json.loads((harness.ROOT / configs[w["config"]]["file"]).read_text())
            ["entry"] == "train"]


def test_runs() -> None:
    import run
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    name = train_cells()[0]
    got = run.measure(tiny_train_cell(name, 5), spec)
    expect(got["correct"], f"train: a sound run is correct ({got['checks']})")
    got = run.measure(tiny_train_cell(name, 5, "half_batch"), spec)
    expect(not got["correct"], f"train: half of the batch left out is not correct ({got['checks']})")
    got = run.measure(tiny_train_cell(name, 5, "unchanged"), spec)
    expect(not got["correct"], f"train: a state left unchanged is not correct ({got['checks']})")


def test_controls() -> None:
    import control
    for name in train_cells():
        cell = tiny_train_cell(name, 31)
        for seed, values in control.readings(cell, "control", [31]):
            checks = harness.checks(values, cell.limits)
            expect(not all(c["ok"] for c in checks.values()),
                   f"{name}: the float8 control fails a limit "
                   f"({ {k: c['value'] for k, c in checks.items()} })")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    test_counts()
    test_reduce_handmade()
    test_reduce_recorded()
    if args.full:
        os.environ["JAX_PLATFORMS"] = "cpu"
        test_controls()
        test_runs()
    print(f"{len(FAILURES)} failed" if FAILURES else "all passed")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
