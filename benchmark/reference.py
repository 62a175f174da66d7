"""The plain reference: the encoder's equations in float32 jax.numpy at
"highest" matmul precision, with no kernels, batching or cache.

It imports nothing of the program and takes nothing that the program
made. The train cells' weights are made here from the run's seed, and
the program is handed a copy.

``precision="fp8"`` is the control: the same equations with every
matmul operand rounded to float8 (e4m3, one scale per tensor, float32
accumulation) in the forward and the backward, the step below the
bfloat16 operands that the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, also one over 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# -- weights ----------------------------------------------------------------

def train_weights(m: dict, key: jax.Array) -> dict:
    """BERT's initializer: every matrix and table drawn from a normal
    truncated at two standard deviations, scaled by
    ``initializer_range``; norm scales 1. Float32 masters, as the
    train step keeps them."""
    d, ff, std = m["d_model"], m["d_ff"], m["init_std"]
    keys = jax.random.split(key, 3 + m["n_layers"])

    def normal(k, shape):
        return std * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)

    layers = []
    for i in range(m["n_layers"]):
        lk = jax.random.split(keys[3 + i], 6)
        layers.append({
            "wq": normal(lk[0], (d, d)), "wk": normal(lk[1], (d, d)),
            "wv": normal(lk[2], (d, d)), "wo": normal(lk[3], (d, d)),
            "w1": normal(lk[4], (d, ff)), "w2": normal(lk[5], (ff, d)),
            "ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
        })
    return {
        "embed": normal(keys[0], (m["vocab"], d)),
        "pos": normal(keys[1], (m["seq_len"], d)),
        "head": normal(keys[2], (d, m["n_classes"])),
        "layers": layers,
    }


def train_batch(key: jax.Array, step: int, *, batch: int, seq_len: int,
                vocab: int, n_classes: int) -> tuple[jax.Array, jax.Array]:
    """Step ``step``'s token ids and labels: fresh on every step, all
    rows different, drawn on the device from the run's key."""
    k_tok, k_lab = jax.random.split(jax.random.fold_in(key, step))
    tokens = jax.random.randint(k_tok, (batch, seq_len), 0, vocab, jnp.int32)
    labels = jax.random.randint(k_lab, (batch,), 0, n_classes, jnp.int32)
    return tokens, labels


# -- the equations ----------------------------------------------------------

def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor (amax to 448)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b))


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    g8 = _fp8(g)
    da = jnp.matmul(g8, jnp.swapaxes(_fp8(b), -1, -2))
    db = jnp.matmul(jnp.swapaxes(_fp8(a), -1, -2), g8)
    # broadcast batch dims of b (weights) were summed by matmul's VJP
    while db.ndim > b.ndim:
        db = db.sum(axis=0)
    return da, db


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(a, b, precision: str):
    return _mm_fp8(a, b) if precision == "fp8" else jnp.matmul(a, b)


def _norm(x, scale):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * scale


def _gelu(x):
    """GELU, tanh form (as in BERT's own code)."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits(params: dict, tokens: jax.Array, *, n_heads: int,
           precision: str = "f32") -> jax.Array:
    """tokens [b, s] → class logits [b, classes]: embedding plus
    positions, pre-norm layers (attention over the whole sequence,
    then a GELU feed-forward), mean pooling, a linear head."""
    x = params["embed"][tokens] + params["pos"][None]
    b, s, d = x.shape
    dh = d // n_heads
    for layer in params["layers"]:
        h = _norm(x, layer["ln1"])

        def heads(w):
            return jnp.swapaxes(_mm(h, w, precision).reshape(b, s, n_heads, dh), 1, 2)

        q, k, v = heads(layer["wq"]), heads(layer["wk"]), heads(layer["wv"])
        scores = _mm(q, jnp.swapaxes(k, -1, -2), precision) / math.sqrt(dh)
        ctx = _mm(jax.nn.softmax(scores, axis=-1), v, precision)
        x = x + _mm(jnp.swapaxes(ctx, 1, 2).reshape(b, s, d), layer["wo"], precision)
        y = _norm(x, layer["ln2"])
        x = x + _mm(_gelu(_mm(y, layer["w1"], precision)), layer["w2"], precision)
    return _mm(jnp.mean(x, axis=1), params["head"], precision)


def _with_precision(fn):
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# -- train step -------------------------------------------------------------

def _block_loss_sum(params, tokens, labels, n_heads, precision):
    lp = jax.nn.log_softmax(logits(params, tokens, n_heads=n_heads,
                                   precision=precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[:, None], axis=-1))


def make_reference_step(m: dict, *, batch: int, lr: float, precision: str = "f32",
                        rows_per_block: int | None = None):
    """One SGD step on the mean cross-entropy, the gradient summed over
    blocks of rows so that it fits beside the weights. Returns
    ``step(params, tokens, labels) -> (new_params, loss)``."""
    seq = m["seq_len"]
    rows = rows_per_block or max(1, min(batch, 4096 // seq))
    while batch % rows:
        rows -= 1
    grad_fn = jax.jit(_with_precision(jax.value_and_grad(
        lambda p, t, y: _block_loss_sum(p, t, y, m["n_heads"], precision))))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    update = jax.jit(lambda p, g: jax.tree.map(lambda w, d: w - lr * (d / batch), p, g))

    def step(params, tokens, labels):
        total, grads = 0.0, None
        for i in range(0, batch, rows):
            loss, g = grad_fn(params, tokens[i:i + rows], labels[i:i + rows])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        return update(params, grads), total / batch

    return step
