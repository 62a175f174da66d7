"""The main path's kernels and programs, compiled for a described TPU
v5e (2x2) with no chip attached.

Interpret mode (the rest of the suite) lowers the Pallas kernels to
ordinary ops, which hides what only the chip's compiler refuses:
unaligned tiles, too much VMEM, and a Mosaic call that GSPMD would
have to split. These compiles catch all three at no chip time.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tasksrunner.ml import flash, service
from tasksrunner.ml.model import (
    BENCH_CONFIG, ModelConfig, init_params, make_train_step, param_specs,
)

SERVED = ModelConfig(n_classes=len(service.PRIORITY_LABELS))
# (batch, seq, heads, d_head): the served bucket 32, then the bench step
SHAPES = {
    "served": (32, SERVED.seq_len, SERVED.n_heads, SERVED.d_head),
    "bench": (32, BENCH_CONFIG.seq_len, BENCH_CONFIG.n_heads,
              BENCH_CONFIG.d_head),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture
def chip_compile(topo, monkeypatch):
    """Compile for Mosaic, not the interpreter, with the persistent
    cache off: a TPU executable written to it cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(cfg, sharding_of):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, sharding_of(s)),
                        shapes, param_specs(cfg),
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_flash_forward_compiles(chip_compile, shape):
    x = _sds(SHAPES[shape], jnp.bfloat16,
             SingleDeviceSharding(chip_compile.devices[0]))
    _assert_kernel(jax.jit(flash.flash_attention).lower(x, x, x).compile())


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_flash_backward_compiles(chip_compile, shape):
    x = _sds(SHAPES[shape], jnp.bfloat16,
             SingleDeviceSharding(chip_compile.devices[0]))

    def grads(q, k, v):
        return jax.grad(
            lambda *a: flash.flash_attention(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _assert_kernel(jax.jit(grads).lower(x, x, x).compile())


@pytest.mark.parametrize("chips", [1, 4])
def test_served_forward_compiles(chip_compile, chips):
    """softmax(forward) at bucket 32, as the scorer serves it: on one
    chip, and on the ("dp",) mesh the scorer builds over four — where
    the kernel must run under shard_map, since GSPMD cannot split a
    Mosaic call."""
    devices = chip_compile.devices
    if chips == 1:
        mesh = None
        params = _params(SERVED, lambda _: SingleDeviceSharding(devices[0]))
        tokens = _sds((32, SERVED.seq_len), jnp.int32,
                      SingleDeviceSharding(devices[0]))
    else:
        mesh = Mesh(np.asarray(devices[:chips]), ("dp",))
        params = _params(SERVED, lambda _: NamedSharding(mesh, P()))
        tokens = _sds((32, SERVED.seq_len), jnp.int32,
                      NamedSharding(mesh, P("dp", None)))
    compiled = service.serving_fn(SERVED, mesh).lower(params, tokens).compile()
    _assert_kernel(compiled)


def test_train_step_compiles_on_dp_tp_mesh(chip_compile):
    """The ("dp","tp") train step on the 2x2 mesh: forward and
    backward kernels per device, params tp-sharded."""
    mesh = Mesh(np.asarray(chip_compile.devices).reshape(2, 2), ("dp", "tp"))
    params = _params(SERVED, lambda spec: NamedSharding(mesh, spec))
    tokens = _sds((8, SERVED.seq_len), jnp.int32,
                  NamedSharding(mesh, P("dp", None)))
    labels = _sds((8,), jnp.int32, NamedSharding(mesh, P("dp")))
    compiled = make_train_step(SERVED, mesh).lower(params, tokens,
                                                   labels).compile()
    _assert_kernel(compiled)
