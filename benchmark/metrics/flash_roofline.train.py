"""Kernels (ml/flash.py): the flash attention calls' share of their
roofline, in %: the least time the chip could take for the attention
work the traced window ran (yardstick.flash_pair_counts: forward
4·b·h·s²·d, backward 8·b·h·s²·d FLOP, q/k/v/o/do/dq/dk/dv and the
logsumexp moved once; the larger of the FLOP time at the bf16 peak and
the byte time at the HBM bandwidth), over the device time of the
kernels' events. Moves train_step_ms.

The kernels carry no name of their own yet: the trace names each
Mosaic call after the HLO instruction JAX gives the ``pallas_call``
under ``value_and_grad``: ``jvp__`` for the forward and
``transpose_jvp___`` for the backward (XLA's numeric suffix dropped).
"""

import yardstick

FORWARD = "jvp__"
BACKWARD = "transpose_jvp___"


def read(r):
    t, tr = r.get("train"), r.get("trace")
    if not t or not tr:
        return None
    fwd, bwd = tr["ops"].get(FORWARD), tr["ops"].get(BACKWARD)
    if not fwd or not bwd:
        return None
    counts = yardstick.flash_pair_counts(batch=t["batch"], heads=t["n_heads"],
                                         seq=t["seq_len"], d_head=t["d_head"])
    flops = counts["fwd_flops"] + counts["bwd_flops"]
    pair, _ = yardstick.least_seconds(flops, counts["bytes"], t["device_kind"])
    # the pair's least time, split between its two calls by their FLOP
    least = (fwd["count"] * pair * counts["fwd_flops"] / flops
             + bwd["count"] * pair * counts["bwd_flops"] / flops)
    return 100.0 * least / (fwd["seconds"] + bwd["seconds"])
