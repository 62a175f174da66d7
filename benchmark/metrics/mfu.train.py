"""Model step, train (ml/model.py): the whole step's share of the
chip's bf16 peak, in %: the step's matmul operations
(yardstick.encoder_train_flops) times the steps of the window, over
the window's seconds and the peak of the device kind. Moves
train_step_ms."""

import yardstick


def read(r):
    t = r.get("train")
    if not t or not t["steps"] or t["platform"] != "tpu":
        return None  # a CPU rehearsal has no chip peak
    peak = yardstick.peak(t["device_kind"])["bf16_flops"]
    return 100.0 * t["flops_per_step"] * t["steps"] / t["window_s"] / peak
