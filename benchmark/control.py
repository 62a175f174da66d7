#!/usr/bin/env python3
"""The readings that a cell's limits are set from, over many seeds in
one process, at the cell's own sizes.

    python3 benchmark/control.py --workload <name> --seeds 11,12,13 [--sides control]

prints one JSON line per side and seed with the compared numbers, the
cell's limits and whether they fail one. ``--sides`` lists, with
commas, what stands in the program's place against the float32
reference:

- ``control`` (the default): the reference itself computed in float8
  (e4m3, one scale per tensor) where the configuration states bfloat16
  operands. Its numbers have to fail a limit.
- ``program``: the program's train step, as the timed window drives it.
  The largest of its numbers over a dozen seeds or more is the lower
  reading of each limit.
- ``half_batch``: the program's step with half of the batch left out.

It needs no measured window. ``--platform cpu`` is a rehearsal off the
chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import run  # noqa: E402

SIDES = ("control", "program", "half_batch")


def readings(cell: harness.Cell, side: str, seeds: list[int]):
    """Yield (seed, compared numbers) for each seed, ``side`` against
    the float32 reference."""
    harness.use_program_env_here()
    import jax

    import reference
    import train
    devices = jax.devices()
    harness.check_device(devices[0].platform, len(devices), cell)
    parts = train.make_parts(cell)
    ref = reference.make_reference_step(parts.m, batch=parts.batch, lr=parts.lr)
    if side == "control":
        step = reference.make_reference_step(parts.m, batch=parts.batch, lr=parts.lr,
                                             precision="fp8")
    else:
        step = train.planted(None if side == "program" else side, parts.make_step(), parts)
    for seed in seeds:
        key = reference.key_from_seed(seed)
        _, losses, grad, change, _ = train.three_steps(step, parts, key)
        _, r_losses, r_grad, r_change, _ = train.three_steps(ref, parts, key)
        yield seed, {"losses": losses, "reference_losses": r_losses,
                     **train.compare(losses, grad, change, r_losses, r_grad, r_change)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sides", default="control",
                    help=f"any of {', '.join(SIDES)}, with commas")
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    harness.use_cache_here()
    spec, entry, config, traffic, limits = run.load_cell(args.workload)
    cell = harness.Cell(workload=args.workload, config=config, traffic=traffic,
                        limits=limits, seed=0, seconds=spec["run_seconds"], trace=False,
                        chips=entry["chips"], t_start=0.0, platform=args.platform)
    seeds = [int(s) for s in args.seeds.split(",")]
    sides = args.sides.split(",")
    if not set(sides) <= set(SIDES):
        ap.error(f"--sides takes {', '.join(SIDES)}")
    for side in sides:
        for seed, values in readings(cell, side, seeds):
            checks = harness.checks(values, limits)
            print(json.dumps({"workload": args.workload, "side": side, "seed": seed,
                              **values, "checks": checks,
                              "fails_a_limit": not all(c["ok"] for c in checks.values())}),
                  flush=True)


if __name__ == "__main__":
    main()
