"""What every cell shares: where things live, the compile cache, the
set-up clock, and the checks that decide ``correct``."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: everything a run writes lives under here (gitignored)
OUT = ROOT / ".bench"
#: a fixed path inside the checkout: the path is part of the cache key
CACHE = OUT / "jax_cache"


class BenchError(Exception):
    """The run cannot measure: no result is printed and the exit code
    is not 0."""


def cache_env() -> dict[str, str]:
    """JAX's persistent compilation cache in the checkout, with no
    minimum compile time or entry size, so that small compiles are
    kept as well, and with no size limit: a limit set in the machine's
    environment turns on JAX's eviction, whose bookkeeping files went
    missing on the chip and made every later write fail. And no TPU runtime logs, which would otherwise go to a
    fixed path under /tmp."""
    return {
        "JAX_COMPILATION_CACHE_DIR": str(CACHE),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "JAX_COMPILATION_CACHE_MAX_SIZE": "-1",
        "TPU_LOG_DIR": "disabled",
    }


def use_cache_here() -> None:
    """Put this process's own compiles (the reference's) in the same
    cache. Call before JAX is imported."""
    os.environ.update(cache_env())


def use_program_env_here() -> None:
    """The environment the program runs in, in this process: the
    program's own settings at their defaults (no ``TASKSRUNNER_*``
    variable comes through), the cache in the checkout. Call before JAX
    is imported."""
    for k in [k for k in os.environ if k.startswith("TASKSRUNNER_")]:
        del os.environ[k]
    os.environ.update(cache_env())
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))


@dataclasses.dataclass
class Cell:
    workload: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float
    #: the self-test's switches: run on the CPU, plant a fault
    platform: str = "tpu"
    fault: str | None = None


class Setup:
    """The set-up clock: from the process's start to the window's
    opening, with its parts named."""

    def __init__(self, t_start: float) -> None:
        self.t_start = t_start
        self.parts: dict[str, float] = {}
        self.total: float | None = None

    @contextlib.contextmanager
    def part(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + time.perf_counter() - t0

    def close(self) -> float:
        self.total = time.perf_counter() - self.t_start
        return self.total

    def line(self) -> dict:
        return {"setup_s": self.total,
                "parts": {k: round(v, 4) for k, v in self.parts.items()}}


def emit(record: dict) -> None:
    """An earlier line of standard output (the result is the last)."""
    print(json.dumps(record), flush=True)


def memory_peak(stats: list[dict]) -> int:
    """The peak on the fullest device, from ``memory_stats()``. On a TPU
    the compiled programs' scratch space is reported as reserved bytes
    beside ``peak_bytes_in_use``, so both peaks count."""
    return max(int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0))
               for s in stats)


def check_device(platform: str, count: int, cell: Cell) -> None:
    if platform != cell.platform:
        raise BenchError(f"the program runs on {platform!r}; this cell needs "
                         f"{cell.platform!r}")
    if count < cell.chips:
        raise BenchError(f"{count} device(s) visible; the cell asks for {cell.chips}")


def checks(values: dict[str, float], limits: dict[str, float]) -> dict:
    """Each compared number beside its limit; a number that is missing,
    not finite, or above its limit fails."""
    out = {}
    for name, limit in limits.items():
        value = values.get(name)
        ok = value is not None and math.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": ok}
    return out


def worst_gap(program: dict[str, float], reference: dict[str, float],
              keep: list[str]) -> float:
    """The largest gap between two norms over the leaves in ``keep``,
    each against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    ref = sorted(reference[k] for k in keep)
    median = ref[len(ref) // 2]
    return max(abs(program[k] - reference[k]) / max(reference[k], median)
               for k in keep)
