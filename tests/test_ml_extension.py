"""Tests for the OPTIONAL ML workload extension and the harness
contract (__graft_entry__.py). See tasksrunner/ml/__init__.py for why
this is an extension, not ported capability."""

import asyncio
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tasksrunner.ml.model import (
    ModelConfig,
    forward,
    hash_tokens,
    init_params,
    loss_fn,
    make_train_step,
    shard_params,
)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

TINY = ModelConfig(vocab=256, seq_len=8, d_model=32, n_heads=2, d_ff=64,
                   n_layers=2, n_classes=5)


def test_forward_shapes_and_determinism():
    params = init_params(TINY, jax.random.PRNGKey(0))
    tokens = hash_tokens(["fix the deploy", "write docs now"], TINY)
    assert tokens.shape == (2, TINY.seq_len)
    logits = forward(params, tokens, cfg=TINY)
    assert logits.shape == (2, TINY.n_classes)
    assert jnp.allclose(logits, forward(params, tokens, cfg=TINY))


def test_train_step_reduces_loss_single_device():
    params = init_params(TINY, jax.random.PRNGKey(0))
    step = make_train_step(TINY, learning_rate=0.1)
    tokens = hash_tokens([f"task number {i}" for i in range(8)], TINY)
    labels = jnp.asarray([i % TINY.n_classes for i in range(8)], jnp.int32)
    _, first_loss = make_train_step(TINY)(
        jax.tree.map(jnp.copy, params), tokens, labels)
    for _ in range(10):
        params, loss = step(params, tokens, labels)
    assert float(loss) < float(first_loss)


def test_sharded_train_step_matches_single_device():
    """dp×tp sharded step must be numerically equivalent (up to bf16
    noise) to the single-device step — the correctness check for the
    sharding layout."""
    from jax.sharding import Mesh

    devices = jax.devices()
    assert len(devices) >= 8, "conftest must provide the virtual 8-cpu mesh"
    mesh = Mesh(np.array(devices[:8]).reshape(4, 2), ("dp", "tp"))

    params = init_params(TINY, jax.random.PRNGKey(1))
    tokens = hash_tokens([f"alpha beta {i}" for i in range(16)], TINY)
    labels = jnp.asarray([i % TINY.n_classes for i in range(16)], jnp.int32)

    single_params, single_loss = make_train_step(TINY)(
        jax.tree.map(jnp.copy, params), tokens, labels)

    with mesh:
        sharded = shard_params(jax.tree.map(jnp.copy, params), mesh, TINY)
        step = make_train_step(TINY, mesh)
        new_params, loss = step(sharded, tokens, labels)
        jax.block_until_ready(loss)

    assert abs(float(loss) - float(single_loss)) < 1e-2
    # spot-check one updated weight agrees across layouts
    a = np.asarray(single_params["head"])
    b = np.asarray(new_params["head"])
    np.testing.assert_allclose(a, b, atol=2e-2)


def test_graft_entry_contract():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]

    g.dryrun_multichip(8)
    g.dryrun_multichip(4)

    with pytest.raises(RuntimeError, match="need"):
        g.dryrun_multichip(1024)


def test_dryrun_multichip_catches_broken_collective(monkeypatch):
    """The dryrun must be SELF-verifying: sabotage the sequence-parallel
    collective (ring attention sees only its local K/V block — one ring
    hop missing) and the dryrun has to fail, not print a plausible
    loss."""
    import __graft_entry__ as g
    import tasksrunner.ml.ring as ring_mod

    real = ring_mod.ring_attention

    def broken_ring_attention(q, k, v, *, mesh):
        # zero the second half of K/V: the blocks a working ring would
        # deliver from the other sp shard arrive corrupted — the
        # forward loss visibly shifts and the dryrun must notice
        half = k.shape[1] // 2
        return real(q, k.at[:, half:].set(0), v.at[:, half:].set(0),
                    mesh=mesh)

    monkeypatch.setattr(ring_mod, "ring_attention", broken_ring_attention)
    with pytest.raises(AssertionError, match="diverge"):
        g.dryrun_multichip(8)


def test_ring_attention_matches_dense():
    """Ring attention over an sp axis must equal full attention (up to
    bf16 noise): the per-block flash accumulation and ppermute rotation
    see every K/V block exactly once."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tasksrunner.ml.ring import ring_attention

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:8]).reshape(1, 4, 2), ("dp", "sp", "tp"))
    b, s, h, dh = 2, 16, 4, 8
    q, k, v = (jax.random.normal(key, (b, s, h, dh), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(7), 3))

    scale = 1.0 / dh ** 0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.bfloat16),
                        k.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(logits, -1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)

    with mesh:
        sh = NamedSharding(mesh, P("dp", "sp", "tp", None))
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh))(
            jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_sequence_parallel_train_step_matches_single_device():
    """Full train step on a dp×sp×tp mesh (ring attention path,
    sequence-sharded tokens) must match the single-device step."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))

    params = init_params(TINY, jax.random.PRNGKey(2))
    tokens = hash_tokens([f"gamma delta {i}" for i in range(8)], TINY)
    labels = jnp.asarray([i % TINY.n_classes for i in range(8)], jnp.int32)

    single_params, single_loss = make_train_step(TINY)(
        jax.tree.map(jnp.copy, params), tokens, labels)

    with mesh:
        sharded = shard_params(jax.tree.map(jnp.copy, params), mesh, TINY)
        step = make_train_step(TINY, mesh)
        tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp", "sp")))
        lab_sh = jax.device_put(labels, NamedSharding(mesh, P("dp")))
        new_params, loss = step(sharded, tok_sh, lab_sh)
        jax.block_until_ready(loss)

    assert abs(float(loss) - float(single_loss)) < 2e-2
    np.testing.assert_allclose(np.asarray(single_params["head"]),
                               np.asarray(new_params["head"]), atol=2e-2)


@pytest.mark.asyncio
async def test_scorer_service_on_the_runtime():
    """The workload service slots into the building blocks like any
    other app: invoke /score synchronously, and saved-task events get
    scored via the subscription and written to the scores state."""
    from tasksrunner import App, InProcCluster
    from tasksrunner.component.spec import parse_component
    from tasksrunner.ml.service import PRIORITY_LABELS, make_app

    specs = [
        parse_component({"componentType": "state.in-memory"},
                        default_name="scores"),
        parse_component({"componentType": "pubsub.in-memory"},
                        default_name="taskspubsub"),
    ]
    scorer = make_app()
    publisher = App("some-api")

    cluster = InProcCluster(specs)
    cluster.add_app(scorer)
    cluster.add_app(publisher)
    await cluster.start()
    try:
        client = cluster.client("some-api")
        # synchronous inference over service invocation
        resp = await client.invoke_method(
            "priority-scorer", "score", data={"taskName": "fix prod outage"})
        assert resp.status == 200
        doc = resp.json()
        assert doc["priority"] in PRIORITY_LABELS
        assert 0.0 < doc["confidence"] <= 1.0

        # async scoring through the pub/sub block
        await client.publish_event(
            "taskspubsub", "tasksavedtopic",
            {"taskId": "t-42", "taskName": "water the plants"})
        deadline = asyncio.get_running_loop().time() + 10
        score = None
        while score is None:
            assert asyncio.get_running_loop().time() < deadline
            r = await client.invoke_method("priority-scorer", "scores/t-42",
                                           http_method="GET")
            if r.status == 200:
                score = r.json()
            else:
                await asyncio.sleep(0.05)
        assert score["priority"] in PRIORITY_LABELS
    finally:
        await cluster.stop()


# -- Pallas flash kernels (tasksrunner/ml/flash.py) ----------------------
# Off-TPU these run in interpreter mode, so the EXACT kernel bodies are
# exercised on CPU against the einsum reference.

def _einsum_attention(q, k, v):
    dh = q.shape[-1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits / jnp.sqrt(jnp.float32(dh)), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


def test_flash_attention_matches_einsum_forward_and_grad():
    from tasksrunner.ml.flash import flash_attention

    key = jax.random.key(7)
    b, s, h, d = 2, 64, 4, 32
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))

    out = flash_attention(q, k, v)
    ref = _einsum_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)

    # gradients: the custom VJP (flash backward kernel) against
    # autodiff through the einsum pair
    def loss_of(attn):
        return lambda *qkv: jnp.sum(jnp.sin(attn(*qkv)))

    g_flash = jax.grad(loss_of(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_of(_einsum_attention), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2, rtol=1e-2)


def test_ring_block_update_pallas_matches_einsum():
    from tasksrunner.ml.flash import ring_block_update
    from tasksrunner.ml.ring import _block_update

    key = jax.random.key(9)
    b, sq, sk, h, d = 2, 16, 24, 2, 32
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k_blk = jax.random.normal(ks[1], (b, sk, h, d))
    v_blk = jax.random.normal(ks[2], (b, sk, h, d))
    m = jax.random.normal(ks[3], (b, h, sq))
    num = jax.random.normal(ks[4], (b, h, sq, d))
    den = jax.nn.softplus(jax.random.normal(ks[5], (b, h, sq)))
    scale = 1.0 / d ** 0.5

    got = ring_block_update(q, k_blk, v_blk, m, num, den, scale=scale)
    want = _block_update(q, k_blk, v_blk, m, num, den, scale=scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)


def test_flash_toggle_changes_attention_core(monkeypatch):
    """TASKSRUNNER_FLASH=0 falls back to the einsum pair; both cores
    produce the same logits for the same params."""
    from tasksrunner.ml import model as model_mod

    key = jax.random.key(3)
    params = init_params(TINY, key)
    tokens = jax.random.randint(key, (4, TINY.seq_len), 0, TINY.vocab,
                                dtype=jnp.int32)
    monkeypatch.setenv("TASKSRUNNER_FLASH", "0")
    ref = forward(params, tokens, cfg=TINY)
    monkeypatch.setenv("TASKSRUNNER_FLASH", "1")
    got = forward(params, tokens, cfg=TINY)
    # bf16 rounding differs slightly between the two cores and
    # accumulates over layers — this asserts same-computation, not
    # bit-identity
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("n,dp,sp,tp", [
    (2, 1, 1, 2),   # tp-only: both heads-halves on separate devices
    (2, 2, 1, 1),   # dp-only: pure data parallelism, no collectives
                    # inside the model at all
    (4, 2, 1, 2),   # dp x tp: the classic 2D layout, no ring
    (8, 4, 1, 2),   # asymmetric: wide dp, tp pair, sp off
    (8, 1, 4, 2),   # sp-heavy: 4-stage ring attention + tp pair
])
def test_dryrun_mesh_factorization_matrix(n, dp, sp, tp):
    """Round-5 verdict item 6: the multichip path must hold under MORE
    than the one 2x2x2 happy path. Each factorization runs the same
    self-verifying dryrun (sharded loss AND updated params must match
    the single-device step) — a PartitionSpec that only works when
    every axis is 2 fails here."""
    import __graft_entry__ as g

    g._dryrun_factored(n, dp=dp, sp=sp, tp=tp)


def test_dryrun_factored_rejects_bad_factorization():
    import __graft_entry__ as g

    with pytest.raises(ValueError, match="devices"):
        g._dryrun_factored(8, dp=2, sp=1, tp=2)   # 4 != 8
    with pytest.raises(ValueError, match="divide"):
        g._dryrun_factored(8, dp=1, sp=1, tp=8)   # 8 ∤ n_heads=4


@pytest.mark.parametrize("env", [
    {"TASKSRUNNER_FLASH_BWD_DELTA": "precompute"},
    {"TASKSRUNNER_FLASH_HBLK_BWD": "1"},
    {"TASKSRUNNER_FLASH_HBLK_BWD": "2",
     "TASKSRUNNER_FLASH_HBLK_FWD": "2"},
    {"TASKSRUNNER_FLASH_BWD_DELTA": "precompute",
     "TASKSRUNNER_FLASH_HBLK_BWD": "4",
     "TASKSRUNNER_FLASH_HBLK_FWD": "4"},
])
def test_flash_backward_variants_match_einsum(monkeypatch, env):
    """Every sweepable kernel configuration (scripts/sweep_flash_bwd.py
    explores these on-chip) must be numerically interchangeable: the
    sweep may only ever trade SPEED. Exercised in interpret mode so
    the exact kernel bodies run on CPU."""
    from tasksrunner.ml.flash import flash_attention

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    key = jax.random.key(11)
    b, s, h, d = 2, 64, 4, 32
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))

    def loss_of(attn):
        return lambda *qkv: jnp.sum(jnp.sin(attn(*qkv)))

    out = flash_attention(q, k, v)
    ref = _einsum_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)
    g_flash = jax.grad(loss_of(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_of(_einsum_attention), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2, rtol=1e-2)


def test_flash_hblk_override_rejects_nondivisor(monkeypatch):
    from tasksrunner.ml.flash import flash_attention

    monkeypatch.setenv("TASKSRUNNER_FLASH_HBLK_FWD", "3")
    q = jnp.zeros((1, 8, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="divide n_heads"):
        flash_attention(q, q, q)
