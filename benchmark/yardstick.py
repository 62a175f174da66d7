"""The benchmark's fixed arithmetic: chip peaks, and operation and
byte counts.

Nothing here reads the program. A change that claims a gain is
measured against these numbers and may not edit them.
"""

from __future__ import annotations

#: Published peaks per chip, keyed by JAX's exact ``device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (bf16 dense matmul
#: peak and HBM bandwidth of one chip). A kind that is not here is an
#: error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"yardstick.PEAKS with its source") from None


def encoder_train_flops(*, batch: int, seq: int, d_model: int, d_ff: int,
                        n_layers: int) -> float:
    """Matmul operations of one encoder train step (forward and
    backward), recomputation not counted. Per layer the forward is
    8·b·s·d² (q, k, v, o projections) + 4·b·s²·d (QKᵀ and PV over the
    full, bidirectional sequence) + 4·b·s·d·ff (the two feed-forward
    matmuls); a train step is three forwards' worth. Embedding gathers,
    the 5-way head, norms and softmax are left out (under 0.1%)."""
    b, s, d, ff = batch, seq, d_model, d_ff
    forward = n_layers * (8 * b * s * d * d + 4 * b * s * s * d
                          + 4 * b * s * d * ff)
    return 3.0 * forward


def flash_pair_counts(*, batch: int, heads: int, seq: int, d_head: int,
                      io_bytes: int = 2) -> dict:
    """What attention needs for one forward and its backward, whatever
    implements it: forward 4·b·h·s²·d FLOP (QKᵀ, PV), backward
    8·b·h·s²·d (dV, dP, dQ, dK; recomputing P is not counted). Bytes:
    q, k, v, o, do, dq, dk, dv at the activation dtype and the f32 row
    logsumexp, each moved once."""
    b, h, s, d = batch, heads, seq, d_head
    tensor = b * h * s * d * io_bytes
    return {
        "fwd_flops": 4.0 * b * h * s * s * d,
        "bwd_flops": 8.0 * b * h * s * s * d,
        "bytes": 8.0 * tensor + 4.0 * b * h * s,
    }


def least_seconds(flops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    p = peak(device_kind)
    t_flops = flops / p["bf16_flops"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
