"""On-chip sweep of the flash kernels' head-block sizes and backward
variant (BASELINE.md: "bwd keeps the same heuristic pending a finer
sweep").

Every variant is numerically interchangeable (pinned by
tests/test_ml_extension.py::test_flash_backward_variants_match_einsum),
so this sweep is purely a clock question. Each variant runs in its OWN
python process (a chip belongs to one process at a time, and each
variant is a different trace) with the variant expressed as env
overrides:

* TASKSRUNNER_FLASH_HBLK_FWD / _BWD — heads folded per grid program;
* TASKSRUNNER_FLASH_BWD_DELTA=precompute — Δ=Σ(dO∘O) outside the
  kernel, dropping the ``o`` stream (flash-v2 arrangement).

Usage (on the chip):  python scripts/sweep_flash_bwd.py
Without a chip:       python scripts/sweep_flash_bwd.py --cpu
  (interpret-mode run at a small config — ranks per-program overhead
  and stream count, not VMEM pressure; its times are the
  interpreter's, never a device figure)
Results: ranked table on stdout + build/sweep_flash_bwd.json.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
OUT = REPO / "build" / "sweep_flash_bwd.json"

#: (label, env overrides). The baseline row is the SHIPPED default —
#: heuristic blocks (4 at bench shapes) and, since the sweep promoted
#: it (flash.py module docstring), Δ precomputed outside the kernel.
#: ``delta_fused`` rows restore the round-4 in-kernel Δ for A/B.
VARIANTS: list[tuple[str, dict[str, str]]] = [
    ("baseline(heuristic+delta_pre)", {}),
    ("bwd_hblk=2", {"TASKSRUNNER_FLASH_HBLK_BWD": "2"}),
    ("bwd_hblk=8", {"TASKSRUNNER_FLASH_HBLK_BWD": "8"}),
    ("delta_fused", {"TASKSRUNNER_FLASH_BWD_DELTA": "fused"}),
    ("delta_fused+bwd8", {"TASKSRUNNER_FLASH_BWD_DELTA": "fused",
                          "TASKSRUNNER_FLASH_HBLK_BWD": "8"}),
    ("delta_fused+bwd2", {"TASKSRUNNER_FLASH_BWD_DELTA": "fused",
                          "TASKSRUNNER_FLASH_HBLK_BWD": "2"}),
    ("fwd_hblk=8", {"TASKSRUNNER_FLASH_HBLK_FWD": "8"}),
    ("fwd8+bwd8", {"TASKSRUNNER_FLASH_HBLK_FWD": "8",
                   "TASKSRUNNER_FLASH_HBLK_BWD": "8"}),
]


def child(cpu: bool = False) -> None:
    """One timing run under the current env. Bench-sized config;
    ``--cpu`` shrinks to an interpret-mode-feasible shape (n_heads=8
    so every hblk variant still divides) and fewer iterations."""
    import jax

    from tasksrunner.ml.model import (
        BENCH_BATCH, BENCH_CONFIG, ModelConfig, init_params, make_train_step,
    )

    if cpu:
        cfg = ModelConfig(vocab=1024, seq_len=128, d_model=128,
                          n_heads=8, d_ff=256, n_layers=2)
        batch, n = 4, 5
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("the on-chip sweep needs a TPU; use --cpu")
        cfg, batch, n = BENCH_CONFIG, BENCH_BATCH, 20
    key = jax.random.key(0)
    import jax.numpy as jnp
    tokens = jax.random.randint(key, (batch, cfg.seq_len), 0, cfg.vocab,
                                dtype=jnp.int32)
    labels = jax.random.randint(key, (batch,), 0, cfg.n_classes,
                                dtype=jnp.int32)
    params = init_params(cfg, key)
    step = make_train_step(cfg)
    t0 = time.perf_counter()
    params, loss = jax.block_until_ready(step(params, tokens, labels))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        params, loss = step(params, tokens, labels)
    jax.block_until_ready(loss)
    print(json.dumps({"step_ms": (time.perf_counter() - t0) / n * 1000.0,
                      "compile_s": compile_s}))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--cpu", action="store_true",
                        help="interpret-mode sweep at a small config "
                             "(no chip required)")
    parser.add_argument("--timeout", type=int, default=600)
    args = parser.parse_args()
    if args.child:
        child(cpu=args.cpu)
        return

    child_cmd = [sys.executable, str(pathlib.Path(__file__)), "--child"]
    child_env = dict(os.environ)
    if args.cpu:
        child_cmd.append("--cpu")
        child_env["JAX_PLATFORMS"] = "cpu"

    results = []
    for label, env in VARIANTS:
        print(f"[{label}] ...", flush=True)
        try:
            proc = subprocess.run(
                child_cmd,
                capture_output=True, text=True, timeout=args.timeout,
                env={**child_env, **env}, cwd=str(REPO))
        except subprocess.TimeoutExpired:
            print(f"[{label}] TIMED OUT", flush=True)
            results.append({"variant": label, "env": env, "error": "timeout"})
            continue
        if proc.returncode != 0:
            tail = proc.stderr.strip()[-300:]
            print(f"[{label}] FAILED: {tail}", flush=True)
            results.append({"variant": label, "env": env, "error": tail})
            continue
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        row = {"variant": label, "env": env, **row}
        print(f"[{label}] step {row['step_ms']:.2f} ms "
              f"(compile {row['compile_s']:.1f} s)", flush=True)
        results.append(row)

    ok = [r for r in results if "step_ms" in r]
    ok.sort(key=lambda r: r["step_ms"])
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps({"results": results, "ranked": ok}, indent=1))
    if ok:
        print("\nranked:")
        for r in ok:
            print(f"  {r['step_ms']:8.2f} ms  {r['variant']}")
        best = ok[0]
        exports = " ".join(f"{k}={v}" for k, v in best["env"].items())
        suffix = (f" — export {exports}" if best["env"]
                  else " (baseline: no overrides)")
        print(f"\nbest: {best['variant']}{suffix}")
    print(f"\nwrote {OUT}")


if __name__ == "__main__":
    sys.path.insert(0, str(REPO))
    main()
