"""The train cells: the program's encoder train step
(``tasksrunner.ml.model.make_train_step``) at the configuration's
widths, driven from the seed in this one process.

Set-up builds the weights on the device in one jitted call, compiles
the step once, drives that compiled step through its first three steps
with the window's own feed, and makes the window's batches with that
feed, as many as the third step's time says the window will take. The
window keeps driving the same object until ``seconds`` have passed,
with up to ``IN_FLIGHT`` steps queued on the device and a device sync
at each edge. Then the program's state is freed and the reference
follows the same three steps from the same weights.
"""

from __future__ import annotations

import collections
import functools
import math
import time
from typing import Callable, NamedTuple

import harness
import reference

#: steps the host may run ahead of the device: several seconds of
#: queued work, so that a host stall of a second or two on the chip's
#: machine does not leave the device idle
IN_FLIGHT = 32


def model_dims(config: dict, traffic: dict) -> dict:
    m = config["model"]
    return {
        "vocab": m["vocab_size"], "d_model": m["hidden_size"],
        "n_heads": m["num_attention_heads"], "d_ff": m["intermediate_size"],
        "n_layers": m["num_hidden_layers"], "init_std": m["initializer_range"],
        "n_classes": config["head"]["n_classes"], "seq_len": traffic["seq_len"],
    }


class Parts(NamedTuple):
    m: dict
    batch: int
    lr: float
    #: key -> the weights, on the device, in one jitted call
    init: Callable
    #: (key, step) -> that step's token ids and labels
    feed: Callable
    #: () -> the program's jitted train step
    make_step: Callable


def make_parts(cell: harness.Cell) -> Parts:
    import jax
    from tasksrunner.ml.model import ModelConfig, make_train_step
    m = model_dims(cell.config, cell.traffic)
    batch, lr = cell.traffic["batch"], cell.config["optimizer"]["learning_rate"]
    cfg = ModelConfig(vocab=m["vocab"], seq_len=m["seq_len"], d_model=m["d_model"],
                      n_heads=m["n_heads"], d_ff=m["d_ff"], n_layers=m["n_layers"],
                      n_classes=m["n_classes"])
    return Parts(
        m=m, batch=batch, lr=lr,
        init=jax.jit(functools.partial(reference.train_weights, m)),
        feed=jax.jit(functools.partial(
            reference.train_batch, batch=batch, seq_len=m["seq_len"],
            vocab=m["vocab"], n_classes=m["n_classes"])),
        make_step=functools.partial(make_train_step, cfg, learning_rate=lr))


def planted(fault: str | None, step, parts: Parts):
    """The timed step, or with a fault planted underneath it: half of
    the batch left out (the mean taken over the rest), or the state
    returned unchanged."""
    import jax
    import jax.numpy as jnp
    if fault is None:
        return step
    if fault == "half_batch":
        half, rows = parts.make_step(), parts.batch // 2
        return lambda p, t, y: half(p, t[:rows], y[:rows])
    if fault == "unchanged":
        def unchanged(p, t, y):
            kept = jax.tree.map(jnp.copy, p)
            _, loss = step(p, t, y)
            return kept, loss
        return unchanged
    raise ValueError(f"no fault {fault!r}")


@functools.cache
def _leaf_norms():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x, y, scale: [
        jnp.sqrt(jnp.sum(((u - v) * scale) ** 2))
        for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y))])


def _norms_of_change(a, b, scale: float = 1.0) -> list[float]:
    """Per-leaf norms of (a - b) * scale, in one jitted call."""
    return [float(v) for v in _leaf_norms()(a, b, scale)]


def three_steps(step, parts: Parts, key):
    """Losses of steps 1-3, the first gradient's per-leaf norms as the
    optimizer got it ((w0 - w1) / lr) and the per-leaf norms of the
    change after three steps (w3 - w0), with w0 made again from the
    seed each time. Returns the state after step 3 too, and the third
    step's seconds, synced on the device at both ends."""
    import jax
    init, feed, lr = parts.init, parts.feed, parts.lr
    params = init(key)
    params, loss1 = step(params, *feed(key, 0))
    grad = _norms_of_change(init(key), params, 1.0 / lr)
    params, loss2 = step(params, *feed(key, 1))
    third = jax.block_until_ready((params, feed(key, 2)))[1]
    t0 = time.perf_counter()
    params, loss3 = jax.block_until_ready(step(params, *third))
    step_s = time.perf_counter() - t0
    change = _norms_of_change(params, init(key))
    losses = [float(x) for x in jax.device_get([loss1, loss2, loss3])]
    return params, losses, grad, change, step_s


def run(cell: harness.Cell) -> dict:
    setup = harness.Setup(cell.t_start)
    harness.use_program_env_here()
    with setup.part("import_and_device_init"):
        import jax
        devices = jax.devices()
        harness.check_device(devices[0].platform, len(devices), cell)
        parts = make_parts(cell)

    key = reference.key_from_seed(cell.seed)
    with setup.part("weights"):
        params = jax.block_until_ready(parts.init(key))
    with setup.part("compile"):
        tokens, labels = parts.feed(key, 0)
        compiled = parts.make_step().lower(params, tokens, labels).compile()
        mem = compiled.memory_analysis()
        del params, tokens, labels
    step = planted(cell.fault, compiled, parts)
    with setup.part("first_three_steps"):
        params, losses, grad, change, step_s = three_steps(step, parts, key)
    with setup.part("window_batches"):
        # one batch for each step the window will queue; reused in turn
        # only if the steps run faster than the third did
        n_batches = math.ceil(cell.seconds / step_s) + IN_FLIGHT
        batches = jax.block_until_ready([parts.feed(key, 3 + i) for i in range(n_batches)])
    setup.close()
    harness.emit({"setup": setup.line(), "window_batches": n_batches,
                  "third_step_s": step_s, "compiled_bytes": {
                      "argument": mem.argument_size_in_bytes,
                      "output": mem.output_size_in_bytes,
                      "temp": mem.temp_size_in_bytes, "alias": mem.alias_size_in_bytes}})

    trace_dir = harness.OUT / "work" / cell.workload / "trace"
    if cell.trace:
        import devtrace
        devtrace.start(str(trace_dir))
    jax.block_until_ready(params)
    steps, queued = 0, collections.deque()
    t0 = time.perf_counter()
    while True:
        params, loss = step(params, *batches[steps % n_batches])
        steps += 1
        queued.append(loss)
        if len(queued) > IN_FLIGHT:
            queued.popleft().block_until_ready()
        if time.perf_counter() - t0 >= cell.seconds:
            break
    jax.block_until_ready((params, loss))
    window = time.perf_counter() - t0
    reduced = None
    if cell.trace:
        devtrace.stop()
    stats = [d.memory_stats() or {} for d in devices]
    peak = harness.memory_peak(stats)
    final_loss = float(loss)
    del params, loss, queued, batches, compiled, step

    if cell.trace:
        reduced = devtrace.reduce(devtrace.load(devtrace.find(str(trace_dir))))

    t_ref = time.perf_counter()
    ref_step = reference.make_reference_step(parts.m, batch=parts.batch, lr=parts.lr)
    _, ref_losses, ref_grad, ref_change, _ = three_steps(ref_step, parts, key)
    values = compare(losses, grad, change, ref_losses, ref_grad, ref_change)
    harness.emit({"memory_stats": stats[0]})
    harness.emit({"reference_s": time.perf_counter() - t_ref,
                  "leaves_left_out": values["leaves_left_out"],
                  "losses": losses, "reference_losses": ref_losses,
                  "window_final_loss": final_loss})

    dev, m = devices[0], parts.m
    return {
        "setup": setup,
        "e2e": {"train_step_ms": window / steps * 1000.0},
        "layer": {
            "train": {"steps": steps, "window_s": window,
                      "flops_per_step": reference_flops(m, parts.batch),
                      "device_kind": dev.device_kind, "platform": dev.platform,
                      "batch": parts.batch,
                      "seq_len": m["seq_len"], "n_heads": m["n_heads"],
                      "d_head": m["d_model"] // m["n_heads"], "n_layers": m["n_layers"]},
            "trace": reduced,
        },
        "attempted": steps, "failed": 0,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
        "trace": reduced,
        "values": values,
    }


def reference_flops(m: dict, batch: int) -> float:
    import yardstick
    return yardstick.encoder_train_flops(batch=batch, seq=m["seq_len"],
                                         d_model=m["d_model"], d_ff=m["d_ff"],
                                         n_layers=m["n_layers"])


def compare(losses, grad, change, ref_losses, ref_grad, ref_change) -> dict:
    """The compared numbers. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are
    left out of both norms' comparison; their count is reported
    beside the numbers."""
    names = [str(i) for i in range(len(ref_grad))]
    g_prog, g_ref = dict(zip(names, grad)), dict(zip(names, ref_grad))
    c_prog, c_ref = dict(zip(names, change)), dict(zip(names, ref_change))
    median = sorted(ref_grad)[len(ref_grad) // 2]
    keep = [k for k in names if g_ref[k] >= 1e-3 * median]
    return {
        "leaves_left_out": len(names) - len(keep),
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)),
        "grad_gap": harness.worst_gap(g_prog, g_ref, keep),
        "update_gap": harness.worst_gap(c_prog, c_ref, keep),
    }
