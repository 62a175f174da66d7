"""From a profiler trace to device busy time, the top device operations
and the longest idle gaps, each named by what the host was doing.

``start``/``stop`` wrap ``jax.profiler`` with the Python tracer off
(its per-call hooks would slow the served host path it is meant to
observe). ``load`` turns the ``.xplane.pb`` file into plain lists, and
``reduce`` works on those lists alone, so the self-test can check it on
a small recorded trace without a chip.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """The trace as ``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, dur_ns], ...]}]}]}``: device planes' op lines and
    the host plane, events of zero length dropped."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events if e.duration_ns > 0]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(name: str) -> str:
    """An operation's name: on a TPU the event carries the whole HLO
    instruction (``%fusion.12 = f32[...] fusion(...)``); keep its name
    without the ``%`` and XLA's numeric suffix."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _label(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)[:64] or "unnamed"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes),
    the device operations that took most time, every op's count and
    seconds by name, and the ``top`` longest idle gaps of the first
    device, each named by the host event that overlaps it most."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    host_events = [(s, s + d, n) for p in host for line in p["lines"]
                   for n, s, d in line["events"]]

    every = [(s, s + d) for p in trace["planes"] for line in p["lines"]
             for _, s, d in line["events"]]
    t0 = min(s for s, _ in every)
    t1 = max(e for _, e in every)

    ops: dict[str, list[float]] = {}
    busy_ns = []
    first_busy = None
    for plane in devices:
        intervals = []
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                slot = ops.setdefault(op_name(name), [0, 0.0])
                slot[0] += 1
                slot[1] += d / 1e9
                intervals.append((s, s + d))
        merged = _union(intervals)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged

    gaps = []
    edges = [t0] + [x for iv in first_busy for x in iv] + [t1]
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e > s:
            gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    named_gaps = []
    for length, s, e in gaps[:top]:
        best, best_key = "host_idle", (0.0, 0.0)
        for hs, he, name in host_events:
            overlap = min(he, e) - max(hs, s)
            if overlap <= 0:
                continue
            key = (overlap, -(he - hs))
            if key > best_key:
                best, best_key = _label(name), key
        named_gaps.append([best, length / 1e9])

    ranked = sorted(ops.items(), key=lambda kv: -kv[1][1])
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "devices": len(devices),
        "ops": {name: {"count": c, "seconds": sec} for name, (c, sec) in ops.items()},
        "device_ops": [[name, sec] for name, (_, sec) in ranked[:top]],
        "idle_gaps": named_gaps,
    }


def idle_percent(reduced: dict | None) -> float | None:
    """The share of the traced window in which no operation ran on the
    chip, in %: 1 - (union of the device-op intervals) / window; None
    without a trace."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
