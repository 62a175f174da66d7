"""Task-priority scorer: a small TPU-first transformer encoder.

EXTENSION ONLY — the reference has no model to port (SURVEY.md §7.1);
this exists to back the harness contract and demonstrate hosting
compute services on the runtime.

TPU-first design notes:

* all matmuls run in bfloat16 with float32 accumulation
  (``preferred_element_type``) so they land on the MXU at full tile
  throughput; params are kept in float32 and cast per-step;
* static shapes everywhere; the whole train step is one ``jax.jit``
  region — no Python control flow inside;
* parallelism is expressed as shardings over a ``Mesh(("dp","tp"))``
  or ``Mesh(("dp","sp","tp"))``: batch on ``dp``, feature/head
  dimensions on ``tp``, sequence on ``sp``; XLA inserts the
  collectives (psum for tp-reduced matmuls, gradient all-reduce over
  dp) — nothing is hand-scheduled;
* attention runs the Pallas flash kernel (tasksrunner/ml/flash.py),
  under ``shard_map`` on a mesh because GSPMD cannot split a Mosaic
  call, or the plain ``jnp.einsum`` pair with ``TASKSRUNNER_FLASH=0``
  (the tests' reference) — except under sequence parallelism (an
  ``sp`` axis of size > 1), where the attention core switches to ring
  attention (tasksrunner/ml/ring.py): K/V blocks rotate by
  ``ppermute`` over the ICI ring and no device ever holds the full
  sequence.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 8192       # hashed token ids
    seq_len: int = 32
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 512
    n_layers: int = 2
    n_classes: int = 5      # priority buckets

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads


#: the train-step shape measured on the chip (bench.py --tpu-bench) and
#: smoke-tested there (chip_smoke.py); the served shape is ModelConfig()
BENCH_CONFIG = ModelConfig(vocab=32768, seq_len=512, d_model=1024,
                           n_heads=16, d_ff=4096, n_layers=8)
BENCH_BATCH = 32


def init_params(cfg: ModelConfig, key: jax.Array) -> dict[str, Any]:
    keys = jax.random.split(key, 3 + cfg.n_layers)

    def dense(k, shape):
        fan_in = shape[0]
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in)))

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[3 + i], 6)
        layers.append({
            "wq": dense(lk[0], (cfg.d_model, cfg.d_model)),
            "wk": dense(lk[1], (cfg.d_model, cfg.d_model)),
            "wv": dense(lk[2], (cfg.d_model, cfg.d_model)),
            "wo": dense(lk[3], (cfg.d_model, cfg.d_model)),
            "w1": dense(lk[4], (cfg.d_model, cfg.d_ff)),
            "w2": dense(lk[5], (cfg.d_ff, cfg.d_model)),
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        })
    return {
        "embed": 0.02 * jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), jnp.float32),
        "pos": 0.02 * jax.random.normal(keys[1], (cfg.seq_len, cfg.d_model), jnp.float32),
        "head": dense(keys[2], (cfg.d_model, cfg.n_classes)),
        "layers": layers,
    }


def act_dtype() -> jnp.dtype:
    """Residual-stream / activation dtype, resolved at trace time.

    bf16 by default: params stay f32 masters and every contraction
    still accumulates f32 on the MXU, but activations written to HBM
    (residual stream, FF intermediate, attention q/k/v/ctx and their
    saved-for-backward residuals) are half the bytes — on a v5e the
    step is HBM-bound in several phases, so this is the single largest
    MFU lever (BASELINE.md roofline). ``TASKSRUNNER_ACT_F32=1``
    restores full-f32 activations for A/B runs."""
    from tasksrunner.envflag import env_flag
    return (jnp.float32 if env_flag("TASKSRUNNER_ACT_F32", default=False)
            else jnp.bfloat16)


def _matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """bf16 × bf16 → f32 accumulate on the MXU, result stored in the
    activation dtype (the f32 accumulation happens in-register; only
    the downcast result pays HBM bytes)."""
    out = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(act_dtype())


def _layernorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)  # moments in f32 on the VPU, always
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale
    return out.astype(act_dtype())


def _use_ring(mesh: Mesh | None) -> bool:
    return (mesh is not None and "sp" in mesh.axis_names
            and mesh.shape["sp"] > 1)


def use_flash() -> bool:
    """Single-chip attention core toggle: the Pallas flash kernel
    (tasksrunner/ml/flash.py, default) vs the plain einsum pair.
    Resolved at trace time — set TASKSRUNNER_FLASH=0 before jitting
    to compare (bench.py reports both)."""
    from tasksrunner.envflag import env_flag

    return env_flag("TASKSRUNNER_FLASH")


def _flash_core(q, k, v, mesh: Mesh | None) -> jax.Array:
    """The Pallas flash kernel, placed per device under a mesh.

    GSPMD cannot split a Mosaic call, so on a mesh the kernel runs
    inside ``shard_map``: batch on ``dp`` and heads on ``tp`` where the
    mesh has the axis and it divides the dimension, replicated
    otherwise (a serving bucket smaller than the mesh)."""
    from tasksrunner.ml.flash import flash_attention
    if mesh is None:
        return flash_attention(q, k, v)

    def axis(name: str, dim: int) -> str | None:
        ok = name in mesh.axis_names and dim % mesh.shape[name] == 0
        return name if ok else None

    spec = P(axis("dp", q.shape[0]), None, axis("tp", q.shape[2]), None)
    return jax.shard_map(flash_attention, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def _attention(x: jax.Array, layer: dict, cfg: ModelConfig,
               mesh: Mesh | None = None) -> jax.Array:
    b, s, _ = x.shape
    h, dh = cfg.n_heads, cfg.d_head

    def heads(w):
        return _matmul(x, w).reshape(b, s, h, dh)

    q, k, v = heads(layer["wq"]), heads(layer["wk"]), heads(layer["wv"])
    if _use_ring(mesh):
        from tasksrunner.ml.ring import ring_attention
        ctx = ring_attention(q, k, v, mesh=mesh)          # [b, s, h, dh]
    elif use_flash():
        ctx = _flash_core(q, k, v, mesh)                  # Pallas kernel
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.bfloat16),
                            k.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits / jnp.sqrt(jnp.float32(dh)), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(jnp.bfloat16),
                         v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    return _matmul(ctx.reshape(b, s, h * dh), layer["wo"])


def forward(params: dict, tokens: jax.Array, *, cfg: ModelConfig,
            mesh: Mesh | None = None) -> jax.Array:
    """tokens [batch, seq] int32 → class logits [batch, n_classes].

    ``mesh`` only changes how the attention core is placed (ring under
    an ``sp`` axis, the flash kernel per device otherwise); everything
    else is plain GSPMD — the same code jits single-chip and
    multi-chip."""
    x = (params["embed"][tokens] + params["pos"][None, :, :]).astype(act_dtype())
    for layer in params["layers"]:
        x = x + _attention(_layernorm(x, layer["ln1"]), layer, cfg, mesh)
        y = _layernorm(x, layer["ln2"])
        y = _matmul(jax.nn.gelu(_matmul(y, layer["w1"])), layer["w2"])
        x = x + y
    pooled = jnp.mean(x.astype(jnp.float32), axis=1)  # f32 reduction
    # final logits stay full f32 (no act_dtype downcast): bf16 here
    # saves no HBM — this IS the output — and would quantize the
    # log_softmax inputs
    return jnp.matmul(pooled.astype(jnp.bfloat16),
                      params["head"].astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def loss_fn(params: dict, tokens: jax.Array, labels: jax.Array, *,
            cfg: ModelConfig, mesh: Mesh | None = None) -> jax.Array:
    logits = forward(params, tokens, cfg=cfg, mesh=mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# -- sharding ------------------------------------------------------------

def param_specs(cfg: ModelConfig) -> dict[str, Any]:
    """PartitionSpecs over Mesh(("dp","tp")): feature dims on tp,
    replicated over dp (gradients psum over dp automatically)."""
    layer = {
        "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
        "wo": P("tp", None),
        "w1": P(None, "tp"), "w2": P("tp", None),
        "ln1": P(None), "ln2": P(None),
    }
    return {
        "embed": P(None, "tp"),
        "pos": P(None, None),
        "head": P(None, None),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params, specs,
        is_leaf=lambda x: isinstance(x, jax.Array) or not isinstance(x, (dict, list)),
    )


def make_train_step(cfg: ModelConfig, mesh: Mesh | None = None, *,
                    learning_rate: float = 1e-3):
    """One SGD step as a single jit region. With a mesh, inputs are
    batch-sharded over dp (and sequence-sharded over sp when the mesh
    has that axis), params tp-sharded; XLA inserts the collectives —
    except the ring attention core, which hand-places its ppermutes."""

    def step(params, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, labels, cfg=cfg, mesh=mesh))(params)
        new_params = jax.tree.map(
            lambda p, g: (p - learning_rate * g).astype(p.dtype), params, grads)
        return new_params, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,))

    specs = param_specs(cfg)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    seq_axis = "sp" if "sp" in mesh.axis_names else None
    data_sh = NamedSharding(mesh, P("dp", seq_axis))
    label_sh = NamedSharding(mesh, P("dp"))
    return jax.jit(
        step,
        in_shardings=(param_sh, data_sh, label_sh),
        out_shardings=(param_sh, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )


def hash_token_ids(text: str, cfg: ModelConfig) -> list[int]:
    """Deterministic hashed tokenizer for one text (no external
    vocab): words → buckets in [1, vocab); 0 is padding. Pure Python —
    the serving encode path runs it per request on the event loop
    without touching a device."""
    import zlib
    ids = [1 + (zlib.crc32(w.lower().encode()) % (cfg.vocab - 1))
           for w in text.split()][: cfg.seq_len]
    return ids + [0] * (cfg.seq_len - len(ids))


def hash_tokens(texts: list[str], cfg: ModelConfig) -> jnp.ndarray:
    """Batched :func:`hash_token_ids`, committed as a device array."""
    return jnp.asarray([hash_token_ids(t, cfg) for t in texts], jnp.int32)


# -- serving placement ---------------------------------------------------

def serving_mesh(devices: list | None = None) -> Mesh | None:
    """A 1-D data mesh over every visible device for the serving path,
    or None single-chip. Inference has no tp-worthy weights at this
    model size: the win is batch-dimension data parallelism, so the
    mesh is just ``("dp",)``."""
    import numpy as np
    devices = jax.devices() if devices is None else devices
    if len(devices) <= 1:
        return None
    return Mesh(np.asarray(devices), ("dp",))


def replicate_params(params: dict, mesh: Mesh) -> dict:
    """Device-put every leaf once, fully replicated over the mesh —
    after this no serving call ever re-feeds weights."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), params)
