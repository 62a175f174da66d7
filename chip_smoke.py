#!/usr/bin/env python3
"""Chip smoke: the priority scorer served on a TPU through its normal
entry point, checked against a plain reference.

    python chip_smoke.py              # one chip: scorer, reference, train step
    python chip_smoke.py --chips 4    # the scorer's ("dp",) mesh over four chips,
                                      # against a one-device forward, nothing else

Phases:

1. scorer — ``python -m tasksrunner host tasksrunner.ml.service:make_app``
   runs as a child with the committed components
   (samples/priority_scorer/components). The parent waits for
   ``/ml/stats`` to report ready, sends concurrent ``score`` invokes
   through the sidecar (each must echo its taskId), publishes tasks on
   ``tasksavedtopic`` and reads every score back from the ``scores``
   store, checks the jit cache did not grow after warmup, and stops
   the child. The parent touches no JAX until the child has exited: a
   chip belongs to one process at a time.
2. reference — in the parent, the same token rows through the plain
   einsum core at "highest" matmul precision (one chip), or through a
   one-device forward (four chips). Served probabilities must agree
   within PROB_ATOL, and the served label must be the reference's
   argmax unless the reference's top two are within 2 * PROB_ATOL
   (a near-tie that bf16 may order either way; counted and printed).
3. train step (one chip only) — a few steps of the bench train step
   (tasksrunner.ml.model.BENCH_CONFIG) with the flash core and with the
   einsum core: losses finite, and each step's loss within LOSS_RTOL of
   the einsum core's. Every loss after the first reads the previous
   step's gradients, so this checks the backward kernels too. (Plain
   SGD at this width is unstable: the losses rise on both cores alike.)

Each phase prints one JSON line with its device, compile and wall
seconds. The last line is ``{"ok": true, "device": {...}}``. Any
failure, or a platform other than ``tpu``, exits non-zero and prints
no result. Nothing here times the chip: these are pass/fail checks.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent
COMPONENTS = REPO / "samples" / "priority_scorer" / "components"

#: bf16 activations, f32 softmax: the served probabilities and the
#: reference's differ by a few 1e-3 (the confidence is also rounded
#: to 4 decimals on the wire)
PROB_ATOL = 1e-2
LOSS_RTOL = 2e-2

N_INVOKES = 64
CONCURRENCY = 64          # all at once: enough to fill bucket 32
N_EVENTS = 16
TRAIN_STEPS = 3
READY_TIMEOUT_S = 600
#: one visible chip for the default phases (libtpu's own variables)
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}


class SmokeError(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def make_tasks(seed: int, n: int, prefix: str) -> list[dict]:
    import random
    rng = random.Random(seed)
    verbs = ["fix", "write", "review", "deploy", "migrate", "debug", "plan"]
    things = ["login page", "billing job", "docs", "search index",
              "release notes", "prod outage", "unit tests", "dashboard"]
    return [{"taskId": f"{prefix}-{i}",
             "taskName": f"{rng.choice(verbs)} the {rng.choice(things)} "
                         f"{rng.randrange(1000)}",
             "taskCreatedBy": f"user{rng.randrange(50)}@example.com",
             "taskAssignedTo": f"dev{rng.randrange(20)}@example.com"}
            for i in range(n)]


# -- phase 1: the scorer, served by its own process ------------------------

def _http(method: str, port: int, path: str, body=None,
          timeout: float = 60.0) -> tuple[int, object]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"content-type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        status, raw = exc.code, exc.read()
    try:
        return status, json.loads(raw) if raw else None
    except ValueError:
        return status, raw.decode(errors="replace")


def _wait_for_ports(proc: subprocess.Popen, log: pathlib.Path) -> int:
    """The host prints ``ready app=... sidecar_port=N`` once started,
    which includes the model's warmup."""
    deadline = time.monotonic() + READY_TIMEOUT_S
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"scorer exited rc={proc.returncode} before binding:\n"
              f"{log.read_text()[-4000:]}")
        for line in log.read_text().splitlines():
            if line.startswith("ready app="):
                fields = dict(f.split("=", 1) for f in line.split()[1:])
                return int(fields["sidecar_port"])
        time.sleep(0.2)
    raise SmokeError("scorer never bound its ports")


def _stats(port: int) -> dict:
    status, body = _http(
        "GET", port, "/v1.0/invoke/priority-scorer/method/ml/stats")
    check(status == 200, f"/ml/stats answered {status}: {body}")
    return body


def _wait_ready(proc: subprocess.Popen, port: int,
                log: pathlib.Path) -> dict:
    deadline = time.monotonic() + READY_TIMEOUT_S
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"scorer exited rc={proc.returncode} during warmup:\n"
              f"{log.read_text()[-4000:]}")
        stats = _stats(port)
        if stats["ready"]:
            return stats
        time.sleep(0.5)
    raise SmokeError("scorer never reported ready")


def _stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def scorer_phase(chips: int, seed: int, workdir: pathlib.Path,
                 platform: str = "tpu") -> tuple[dict, dict]:
    """Returns (the phase record, {taskId: (task, served result)}).
    ``platform="cpu"`` is the rehearsal off the chip."""
    t0 = time.perf_counter()
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": platform}
    if platform == "cpu":
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    elif chips == 1:
        env.update(ONE_CHIP_ENV)
    log = workdir / "scorer.log"
    with open(log, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "tasksrunner", "host",
             "tasksrunner.ml.service:make_app",
             "--components", str(COMPONENTS)],
            cwd=workdir, env=env, stdout=out, stderr=subprocess.STDOUT)
    try:
        port = _wait_for_ports(proc, log)
        warm = _wait_ready(proc, port, log)
        check(warm["platform"] == platform,
              f"the scorer runs on {warm['platform']!r}, not {platform!r}")
        check(warm["device_count"] == chips,
              f"the scorer sees {warm['device_count']} devices, "
              f"expected {chips}")
        check(warm["jit_cache_size"] == len(warm["buckets"]),
              f"warmup left {warm['jit_cache_size']} jit entries for "
              f"{len(warm['buckets'])} buckets")

        served = {}
        invokes = make_tasks(seed, N_INVOKES, "invoke")

        def score(task):
            status, body = _http(
                "POST", port, "/v1.0/invoke/priority-scorer/method/score",
                task)
            check(status == 200, f"score {task['taskId']}: {status} {body}")
            check(body.get("taskId") == task["taskId"],
                  f"score for {task['taskId']} came back as {body}")
            return task, body

        with concurrent.futures.ThreadPoolExecutor(CONCURRENCY) as pool:
            for task, body in pool.map(score, invokes):
                served[task["taskId"]] = (task, body)

        events = make_tasks(seed + 1, N_EVENTS, "event")
        for task in events:
            status, body = _http(
                "POST", port, "/v1.0/publish/taskspubsub/tasksavedtopic", task)
            check(status in (200, 204), f"publish {task['taskId']}: {status} {body}")
        deadline = time.monotonic() + 120
        for task in events:
            while True:
                status, body = _http(
                    "GET", port, f"/v1.0/state/scores/{task['taskId']}")
                if status == 200 and body:
                    served[task["taskId"]] = (task, body)
                    break
                check(time.monotonic() < deadline,
                      f"no score stored for {task['taskId']} ({status})")
                time.sleep(0.1)

        after = _stats(port)
        recompiles = after["jit_cache_size"] - warm["jit_cache_size"]
        check(recompiles == 0, f"{recompiles} recompiles after warmup")
        check(after["completed"] == N_INVOKES + N_EVENTS,
              f"the batcher completed {after['completed']} of "
              f"{N_INVOKES + N_EVENTS}")
    finally:
        _stop(proc)
    check(proc.returncode is not None, "scorer did not stop")
    return {
        "phase": "scorer",
        "device": {"platform": warm["platform"], "kind": warm["device_kind"],
                   "count": warm["device_count"]},
        "compile_s": warm["warmup_s"],
        "wall_s": time.perf_counter() - t0,
        "invokes": N_INVOKES, "events": N_EVENTS,
        "batches": after["batches"], "recompiles": recompiles,
    }, served


# -- phases 2 and 3: in this process, once the child is gone ---------------

def _device(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _flash(on: bool) -> None:
    # read at trace time by tasksrunner.ml.model.use_flash
    os.environ["TASKSRUNNER_FLASH"] = "1" if on else "0"


def reference_phase(chips: int, served: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tasksrunner.ml.model import ModelConfig, init_params
    from tasksrunner.ml.service import PRIORITY_LABELS, encode_task, serving_fn

    t0 = time.perf_counter()
    device = jax.devices()[0]
    cfg = ModelConfig(n_classes=len(PRIORITY_LABELS))
    params = jax.device_put(init_params(cfg, jax.random.PRNGKey(0)), device)
    ids = sorted(served)
    tokens = jax.device_put(jnp.asarray(
        [encode_task(served[i][0], cfg) for i in ids], jnp.int32), device)
    if chips == 1:
        # the plain einsum core, full-precision matmuls
        _flash(False)
        with jax.default_matmul_precision("highest"):
            fn = serving_fn(cfg, None).lower(params, tokens).compile()
        reference = "einsum core, highest precision"
    else:
        _flash(True)
        fn = serving_fn(cfg, None).lower(params, tokens).compile()
        reference = "one-device forward"
    compile_s = time.perf_counter() - t0
    probs = np.asarray(fn(params, tokens))
    _flash(True)

    worst, near_ties = 0.0, 0
    for row, task_id in zip(probs, ids):
        body = served[task_id][1]
        label = PRIORITY_LABELS.index(body["priority"])
        err = abs(body["confidence"] - float(row[label]))
        worst = max(worst, err)
        check(err <= PROB_ATOL,
              f"{task_id}: served {body} vs reference {row.tolist()}")
        best = int(row.argmax())
        if label != best:
            check(float(row[best] - row[label]) <= 2 * PROB_ATOL,
                  f"{task_id}: served label {body['priority']} but the "
                  f"reference's argmax is {PRIORITY_LABELS[best]} "
                  f"({row.tolist()})")
            near_ties += 1
    return {
        "phase": "reference", "device": _device(jax), "reference": reference,
        "compile_s": compile_s, "wall_s": time.perf_counter() - t0,
        "rows": len(ids), "max_abs_prob_err": worst, "tol": PROB_ATOL,
        "label_near_ties": near_ties,
    }


def train_phase(seed: int, cfg=None, batch: int | None = None) -> dict:
    """The bench train step; ``cfg``/``batch`` shrink it for the CPU
    rehearsal."""
    import jax
    import jax.numpy as jnp

    from tasksrunner.ml.model import (
        BENCH_BATCH, BENCH_CONFIG, init_params, make_train_step,
    )

    t0 = time.perf_counter()
    cfg, batch = cfg or BENCH_CONFIG, batch or BENCH_BATCH
    k_tok, k_lab, k_par = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k_tok, (batch, cfg.seq_len), 0,
                                cfg.vocab, dtype=jnp.int32)
    labels = jax.random.randint(k_lab, (batch,), 0, cfg.n_classes,
                                dtype=jnp.int32)

    def compiled_step(flash: bool):
        _flash(flash)
        params = init_params(cfg, k_par)
        c0 = time.perf_counter()
        step = make_train_step(cfg).lower(params, tokens, labels).compile()
        return step, params, time.perf_counter() - c0

    def run(flash: bool) -> tuple[list[float], float]:
        step, params, compile_s = compiled_step(flash)
        losses = []
        for _ in range(TRAIN_STEPS):
            params, loss = step(params, tokens, labels)
            losses.append(float(loss))
        return losses, compile_s

    losses, compile_s = run(True)
    ref_losses, ref_compile_s = run(False)
    _flash(True)
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    for i, (got, want) in enumerate(zip(losses, ref_losses)):
        check(abs(got - want) <= LOSS_RTOL * abs(want),
              f"step {i} loss {got} vs einsum core {want}")
    return {
        "phase": "train_step", "device": _device(jax),
        "compile_s": compile_s, "einsum_compile_s": ref_compile_s,
        "wall_s": time.perf_counter() - t0,
        "losses": losses, "einsum_losses": ref_losses, "rtol": LOSS_RTOL,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if not (REPO / "tasksrunner").is_dir() or not COMPONENTS.is_dir():
        print(f"chip_smoke: no tasksrunner checkout around {REPO}",
              file=sys.stderr)
        return 2
    # JAX must find the TPU or fail: no CPU fallback, here or in the child
    os.environ["JAX_PLATFORMS"] = "tpu"
    if args.chips == 1:
        os.environ.update(ONE_CHIP_ENV)
    sys.path.insert(0, str(REPO))
    try:
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            record, served = scorer_phase(args.chips, args.seed,
                                          pathlib.Path(tmp))
        emit(record)
        # the child has exited: this process may take the chip now
        import jax

        from tasksrunner.ml.compile_cache import enable_compile_cache
        enable_compile_cache()
        device = _device(jax)
        check(device["platform"] == "tpu",
              f"JAX found no TPU (platform {device['platform']!r})")
        check(device["count"] == args.chips,
              f"{device['count']} devices visible, expected {args.chips}")
        emit(reference_phase(args.chips, served))
        if args.chips == 1:
            emit(train_phase(args.seed))
    except Exception as exc:  # noqa: BLE001 - any failure fails the smoke
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
