"""Pallas flash-attention kernels for the ML extension.

EXTENSION ONLY (see tasksrunner/ml/model.py) — the reference has no
numerical workload (SURVEY.md §0); these kernels back the harness
contract's compute path.

Design (per /opt/skills/guides/pallas_guide.md):

* One grid program per (batch, head-block): at the scorer's shapes
  (seq ≤ 1k, d_head 64) a head's whole attention fits VMEM easily
  (q/k/v/o ≈ 0.5 MB + one [S,S] f32 score tile ≈ 1 MB at s=512), so
  each head is a single fused QKᵀ → softmax → PV with no K-streaming
  loop — and because one-head programs are overhead-dominated (the
  round-4 attribution), `_head_block` folds as many heads per program
  as the ~16 MB/core VMEM budget allows (4 at the bench shapes; the
  budget math lives in its docstring). The flash recipe's K-streaming
  only pays once S² no longer fits, and the blockwise ring layer
  (ring.py) already bounds S per device before that point.
* Internally arrays are laid out [batch, heads, seq, d_head] so each
  block's minor-most two dims are the full (seq, d_head) tile —
  Pallas TPU requires the last two block dims be tile-aligned or
  whole; the (b, h) grid dims lead. The public interface stays the
  model's [batch, seq, heads, d_head]; XLA fuses the transposes into
  the surrounding reshapes.
* Matmuls run bf16 × bf16 → f32 (`preferred_element_type`) on the
  MXU; softmax stays f32 on the VPU; nothing round-trips to HBM
  between the three stages (the win over dispatching three XLA ops).
* Training needs gradients: `flash_attention` carries a custom VJP
  whose backward pass is a second Pallas kernel implementing the
  standard flash backward (recompute P from the saved row-logsumexp,
  then dV = PᵀdO, dS = P∘(dO Vᵀ − Δ), dQ = dS·K, dK = dSᵀ·Q) — same
  VMEM-residency argument, one grid program per (batch, head-block)
  with a tighter budget (more streams and live score tiles).
* On the cpu backend the kernels run in interpreter mode, so the
  correctness suite (tests/test_ml_extension.py) exercises the exact
  kernel code against the einsum reference; tests/test_tpu_compile.py
  compiles them for a described v5e. Any other backend is an error.
* Backward default: Δ = Σ(dO∘O) is PREcomputed outside the kernel
  (``_bwd_kernel_delta``, the flash-v2 arrangement): one fewer
  double-buffered [h_blk, S, D] input stream, a structural saving
  rather than a shape-dependent one. It was chosen from an
  interpret-mode sweep (``scripts/sweep_flash_bwd.py --cpu``), which
  times the interpreter, not the chip; its on-chip time is not
  measured. The same sweep ranked ``bwd_hblk=8`` fastest, an
  interpret-mode artifact (fewer program invocations) that the
  on-chip round-4 note in ``_head_block`` contradicts, so the block
  heuristic stays. ``TASKSRUNNER_FLASH_BWD_DELTA=fused`` restores the
  in-kernel Δ for A/B runs; both variants stay numerically pinned by
  ``test_flash_backward_variants_match_einsum``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    """Interpret mode is for the CPU test suite only: on the chip the
    kernels compile for Mosaic, and any other backend is an error
    rather than a silent interpreter run."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run on a TPU, or interpreted on the cpu "
        f"backend for tests; the default backend here is {backend!r}")


def _dot(a, b, *, trans_b: bool = False):
    """bf16×bf16→f32 MXU contraction of 2-D operands."""
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
        preferred_element_type=jnp.float32)


def _env_hblk(var: str, h: int) -> int | None:
    """Trace-time head-block override for on-chip sweeps
    (scripts/sweep_flash_bwd.py): TASKSRUNNER_FLASH_HBLK_FWD /
    _BWD / _RING = an integer dividing n_heads. Unset → the
    VMEM-budget heuristic below decides."""
    import os
    raw = os.environ.get(var)
    if not raw:
        return None
    blk = int(raw)
    if blk < 1 or h % blk:
        raise ValueError(f"{var}={raw} must divide n_heads={h}")
    return blk


def _head_block(h: int, s: int, d: int, *, n_qkv: int = 4,
                n_tiles: int = 2) -> int:
    """Heads folded into one grid program. One-head programs are tiny
    (67 MFLOP at the bench shapes) and the per-program pipeline
    overhead dominated the kernel — measured on the v5e, 4 heads per
    program runs the forward 1.7× faster than 1 (2.17 → 1.27 ms at
    b=32 h=16 s=512 d=64), while 8 regresses (VMEM pressure defeats
    the in/out copy pipelining). The loop is a static unroll; results
    are bit-identical across block sizes.

    The block size is VMEM-budgeted, not fixed: per program, Pallas
    double-buffers ``n_qkv``-ish [h_blk, s, d] bf16 streams and the
    unrolled body keeps ~``n_tiles`` [s, s] f32 score tiles live per
    head iteration — at larger seq the tiles quadruple, so a blind
    h_blk=4 would blow the ~16 MB/core budget exactly the way the
    measured 8-head variant did at s=512."""
    budget = 12 * 1024 * 1024  # leave headroom under ~16 MB/core
    for blk in (4, 2):
        if h % blk:
            continue
        streams = 2 * n_qkv * blk * s * d * 2          # double-buffered bf16
        tiles = n_tiles * s * s * 4                    # f32, per iteration
        if streams + tiles <= budget:
            return blk
    return 1


def _specs(b, s, h, d, h_blk: int = 1):
    """BlockSpecs over the internal [b, h, s, d] / [b, h, 1, s]
    layouts: one (batch, head-block) per grid program, minor dims
    whole."""
    qkv = pl.BlockSpec((1, h_blk, s, d), lambda i, j: (i, j, 0, 0),
                       memory_space=pltpu.VMEM)
    lse = pl.BlockSpec((1, h_blk, 1, s), lambda i, j: (i, j, 0, 0),
                       memory_space=pltpu.VMEM)
    return qkv, lse


# -- forward --------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, scale, h_blk):
    for i in range(h_blk):                     # static unroll
        q = q_ref[0, i]                        # [S, D]
        k = k_ref[0, i]
        v = v_ref[0, i]
        s = _dot(q, k, trans_b=True) * scale   # [S, S] f32
        m = jnp.max(s, axis=-1)                # [S]
        p = jnp.exp(s - m[:, None])            # f32, unnormalised
        den = jnp.sum(p, axis=-1)              # [S]
        ctx = _dot(p, v) / den[:, None]        # [S, D] f32 in-register
        o_ref[0, i] = ctx.astype(o_ref.dtype)  # HBM bytes in IO dtype
        l_ref[0, i, 0, :] = m + jnp.log(den)   # row logsumexp, for bwd


def _flash_fwd(q, k, v, scale):
    """q/k/v in internal [b, h, s, d] layout; the context comes back in
    the inputs' dtype (bf16 activations halve the HBM bytes — softmax
    statistics and accumulation stay f32 inside the kernel)."""
    b, h, s, d = q.shape
    h_blk = (_env_hblk("TASKSRUNNER_FLASH_HBLK_FWD", h)
             or _head_block(h, s, d, n_qkv=5, n_tiles=2))
    qkv_spec, lse_spec = _specs(b, s, h, d, h_blk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, h_blk=h_blk),
        grid=(b, h // h_blk),
        in_specs=[qkv_spec, qkv_spec, qkv_spec],
        out_specs=[qkv_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# -- backward -------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, l_ref,
                dq_ref, dk_ref, dv_ref, *, scale, h_blk):
    for i in range(h_blk):                      # static unroll
        q = q_ref[0, i]
        k = k_ref[0, i]
        v = v_ref[0, i]
        o = o_ref[0, i]
        do = do_ref[0, i]
        lse = l_ref[0, i, 0, :]                 # [S]
        s = _dot(q, k, trans_b=True) * scale    # [S, S]
        p = jnp.exp(s - lse[:, None])           # normalised probs, f32
        dv = _dot(p.T, do)                      # [S, D]
        dp = _dot(do, v, trans_b=True)          # [S, S]
        delta = jnp.sum(do.astype(jnp.float32)  # f32 on the VPU even
                        * o.astype(jnp.float32), axis=-1)  # with bf16 IO
        ds = p * (dp - delta[:, None]) * scale  # [S, S]
        dq_ref[0, i] = _dot(ds, k).astype(dq_ref.dtype)
        dk_ref[0, i] = _dot(ds.T, q).astype(dk_ref.dtype)
        dv_ref[0, i] = dv.astype(dv_ref.dtype)


def _bwd_kernel_delta(q_ref, k_ref, v_ref, do_ref, l_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, h_blk):
    """Backward variant with Δ = Σ(dO ∘ O) PREcomputed outside the
    kernel (the standard flash-v2 arrangement): the ``o`` stream
    disappears from the program (one fewer [h_blk, S, D] double-
    buffered input), trading a cheap XLA-fused elementwise pass for
    VMEM headroom. Numerically identical to _bwd_kernel; which one
    wins on the clock is a sweep question (scripts/sweep_flash_bwd.py)."""
    for i in range(h_blk):                      # static unroll
        q = q_ref[0, i]
        k = k_ref[0, i]
        v = v_ref[0, i]
        do = do_ref[0, i]
        lse = l_ref[0, i, 0, :]                 # [S]
        delta = delta_ref[0, i, 0, :]           # [S], f32
        s = _dot(q, k, trans_b=True) * scale    # [S, S]
        p = jnp.exp(s - lse[:, None])           # normalised probs, f32
        dv = _dot(p.T, do)                      # [S, D]
        dp = _dot(do, v, trans_b=True)          # [S, S]
        ds = p * (dp - delta[:, None]) * scale  # [S, S]
        dq_ref[0, i] = _dot(ds, k).astype(dq_ref.dtype)
        dk_ref[0, i] = _dot(ds.T, q).astype(dk_ref.dtype)
        dv_ref[0, i] = dv.astype(dv_ref.dtype)


def _bwd_delta_precompute() -> bool:
    """Δ placement for the backward, resolved at trace time. Default
    is PREcompute (_bwd_kernel_delta) — promoted by the sweep result
    in the module docstring; TASKSRUNNER_FLASH_BWD_DELTA=fused
    restores the in-kernel Δ of the round-4 configuration."""
    import os
    return os.environ.get("TASKSRUNNER_FLASH_BWD_DELTA", "precompute") != "fused"


def _flash_bwd_call(q, k, v, out, lse, dout, scale):
    b, h, s, d = q.shape
    dout = dout.astype(q.dtype)
    if _bwd_delta_precompute():
        # Δ in one XLA-fused elementwise+reduce pass; the kernel then
        # streams 4 big inputs instead of 5
        delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, :, None, :]            # [b, h, 1, s]
        h_blk = (_env_hblk("TASKSRUNNER_FLASH_HBLK_BWD", h)
                 or _head_block(h, s, d, n_qkv=7, n_tiles=3))
        qkv_spec, lse_spec = _specs(b, s, h, d, h_blk)
        return pl.pallas_call(
            functools.partial(_bwd_kernel_delta, scale=scale, h_blk=h_blk),
            grid=(b, h // h_blk),
            in_specs=[qkv_spec] * 4 + [lse_spec, lse_spec],
            out_specs=[qkv_spec] * 3,
            out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype)] * 3,
            interpret=_interpret(),
        )(q, k, v, dout, lse, delta)
    # bwd streams more (q/k/v/o/do in, dq/dk/dv out) and keeps more
    # score-sized temporaries live (s, p, dp, ds)
    h_blk = (_env_hblk("TASKSRUNNER_FLASH_HBLK_BWD", h)
             or _head_block(h, s, d, n_qkv=8, n_tiles=3))
    qkv_spec, lse_spec = _specs(b, s, h, d, h_blk)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, h_blk=h_blk),
        grid=(b, h // h_blk),
        in_specs=[qkv_spec] * 5 + [lse_spec],
        out_specs=[qkv_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype)] * 3,
        interpret=_interpret(),
    )(q, k, v, out, dout, lse)


# -- public op ------------------------------------------------------------

def _to_internal(x):
    return jnp.transpose(x, (0, 2, 1, 3))      # [b,s,h,d] -> [b,h,s,d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, scale=None):
    """Fused attention: q/k/v [batch, seq, heads, d_head] → context
    [batch, seq, heads, d_head] **in the inputs' dtype** (bf16
    activations halve HBM bytes; softmax statistics and MXU
    accumulation stay f32 inside the kernel). Differentiable; the VJP
    is the flash backward kernel, gradients in the inputs' dtype."""
    out, _ = _flash_fwd(_to_internal(q), _to_internal(k), _to_internal(v),
                        _resolve_scale(q, scale))
    return _to_internal(out)


def _resolve_scale(q, scale):
    return float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)


def _fwd_rule(q, k, v, scale):
    qi, ki, vi = _to_internal(q), _to_internal(k), _to_internal(v)
    out, lse = _flash_fwd(qi, ki, vi, _resolve_scale(q, scale))
    return _to_internal(out), (qi, ki, vi, out, lse)


def _bwd_rule(scale, res, dout):
    qi, ki, vi, out, lse = res
    dq, dk, dv = _flash_bwd_call(qi, ki, vi, out, lse, _to_internal(dout),
                                 _resolve_scale(qi, scale))
    return _to_internal(dq), _to_internal(dk), _to_internal(dv)


flash_attention.defvjp(_fwd_rule, _bwd_rule)


# -- ring block update ----------------------------------------------------

def _ring_block_kernel(q_ref, k_ref, v_ref, m_ref, num_ref, den_ref,
                       m_out, num_out, den_out, *, scale, h_blk):
    """One visiting K/V block folded into the running flash state —
    the ring step's inner update (ring.py `_block_update`) as one
    fused kernel: logits, running max, correction, and both
    accumulators without leaving VMEM. Head-blocked like the main
    kernels: the per-device ring blocks are the SMALLEST programs in
    the module (Sq = seq/sp), so per-program overhead bites hardest
    here."""
    for i in range(h_blk):                      # static unroll
        q = q_ref[0, i]                         # [Sq, D]
        k = k_ref[0, i]                         # [Sk, D]
        v = v_ref[0, i]
        m = m_ref[0, i, 0, :]                   # [Sq]
        num = num_ref[0, i]                     # [Sq, D]
        den = den_ref[0, i, 0, :]
        s = _dot(q, k, trans_b=True) * scale    # [Sq, Sk]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_out[0, i, 0, :] = m_new
        num_out[0, i] = num * corr[:, None] + _dot(p, v)
        den_out[0, i, 0, :] = den * corr + jnp.sum(p, axis=-1)


def ring_block_update(q, k_blk, v_blk, m, num, den, *, scale):
    """Pallas twin of ring.py's `_block_update`.

    Layouts match the ring's per-device state: q/k/v [b, sq|sk, h, dh],
    m/den [b, h, sq], num [b, h, sq, dh]. Forward-only — the ring's
    VJP differentiates the einsum block update instead.
    """
    b, sq, h, d = q.shape
    sk = k_blk.shape[1]
    # budget with the larger of the two seq dims: the score tile is
    # [Sq, Sk] and the streams carry both block sizes
    h_blk = (_env_hblk("TASKSRUNNER_FLASH_HBLK_RING", h)
             or _head_block(h, max(sq, sk), d, n_qkv=7, n_tiles=2))
    qkv_spec, vec_spec = _specs(b, sq, h, d, h_blk)
    kv_spec = pl.BlockSpec((1, h_blk, sk, d), lambda i, j: (i, j, 0, 0),
                           memory_space=pltpu.VMEM)
    m_new, num_new, den_new = pl.pallas_call(
        functools.partial(_ring_block_kernel, scale=scale, h_blk=h_blk),
        grid=(b, h // h_blk),
        in_specs=[qkv_spec, kv_spec, kv_spec, vec_spec, qkv_spec, vec_spec],
        out_specs=[vec_spec, qkv_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        interpret=_interpret(),
    )(_to_internal(q), _to_internal(k_blk), _to_internal(v_blk),
      m[:, :, None, :], num, den[:, :, None, :])
    return m_new[:, :, 0, :], num_new, den_new[:, :, 0, :]
