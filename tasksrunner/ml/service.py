"""The demo workload service: the scorer hosted as a runtime app.

EXTENSION ONLY (see package docstring) — this is the pattern for
hosting compute on tasksrunner: a model served by an ordinary ``App``
that participates in the same building blocks as every other service.

* ``POST /score`` — synchronous inference: task JSON in, priority
  class + confidence out (service-invocation callable:
  ``client.invoke_method("priority-scorer", "score", ...)`` — and over
  processes that lane rides the binary mesh codec like every other
  invoke). Responses echo the request's ``taskId`` so callers can
  match concurrent scores to their tasks.
* subscribes to ``tasksavedtopic`` — every saved task is scored
  asynchronously and the score written to the ``scores`` state
  component, exactly how the Tasks Tracker processor consumes the
  same topic.
* ``GET /scores/{task_id}`` — read a stored score back.
* ``GET /ml/stats`` — serving-plane introspection: queue depth,
  per-bucket batch counts, the jit cache size (flat after warmup
  == zero recompiles; the bench and tests assert on it), the warmup's
  seconds, and where the model runs (platform, device kind, device
  count).

Serving runs on the continuous-batching engine
(:mod:`tasksrunner.ml.batching`): requests queue, micro-batches
assemble under the ``TASKSRUNNER_ML_MAX_DELAY_MS`` budget, batch
shapes pad up a fixed bucket ladder, and each bucket jit-compiles
exactly once at startup warmup, with JAX's persistent compile cache
placed by :mod:`tasksrunner.ml.compile_cache`. Params are device-put
once — fully replicated over a 1-D data mesh when >1 device is
visible, with the batch dimension sharded over the mesh for bucket
sizes the device count divides. The batcher's tokens-in-flight ratio
registers with the admission controller, so floods shed
429+Retry-After at the front door, and its ``ml_*`` histograms feed
the target-p99 autoscale rule.

During warmup both lanes answer a retryable not-ready — 503 with a
``Retry-After`` header. The runtime turns that header into a
redelivery backoff (pubsub ``Nack``), so the broker stops hot-looping
deliveries while XLA compiles and no attempt budget is burned before
``compiled`` is populated.
"""

from __future__ import annotations

import asyncio
import logging
import time

from tasksrunner.app import App, Response
from tasksrunner.errors import SaturatedError

logger = logging.getLogger(__name__)

PRIORITY_LABELS = ["backlog", "low", "normal", "high", "urgent"]

#: seconds the not-ready paths ask clients/brokers to stay away; one
#: beat is enough — warmup is seconds, and redeliveries only need to
#: stop arriving *every retry_delay tick*
WARMUP_RETRY_AFTER = 1


def encode_task(task: dict, cfg) -> list[int]:
    """The token row a task is scored on: its text fields, hashed."""
    from tasksrunner.ml.model import hash_token_ids

    text = " ".join(
        str(task.get(k, "")) for k in
        ("taskName", "taskCreatedBy", "taskAssignedTo") if task.get(k))
    return hash_token_ids(text or "empty", cfg)


def serving_fn(cfg, mesh):
    """The served computation, one jit region: tokens → class
    probabilities. Softmax inside the region means one device→host
    transfer per batch, of exactly the probabilities."""
    import jax

    from tasksrunner.ml.model import forward

    return jax.jit(lambda p, t: jax.nn.softmax(
        forward(p, t, cfg=cfg, mesh=mesh), axis=-1))


def make_app(*, pubsub: str = "taskspubsub", topic: str = "tasksavedtopic",
             state_store: str = "scores") -> App:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tasksrunner.envflag import env_flag
    from tasksrunner.ml.batching import BatcherConfig, MicroBatcher
    from tasksrunner.ml.compile_cache import enable_compile_cache
    from tasksrunner.ml.model import (
        ModelConfig, init_params, replicate_params, serving_mesh,
    )
    from tasksrunner.observability import admission

    cfg = ModelConfig(n_classes=len(PRIORITY_LABELS))
    app = App("priority-scorer")
    compiled = {}

    bcfg = BatcherConfig.from_env()
    if not env_flag("TASKSRUNNER_ML_BATCHING"):
        bcfg = bcfg.serial()

    def _place_tokens(tokens: np.ndarray):
        """Shard the batch dimension over the data mesh when it
        divides evenly, else replicate. Either way each bucket keeps
        exactly one (shape, sharding) pair, so the jit cache stays one
        entry per bucket."""
        mesh = compiled.get("mesh")
        if mesh is None:
            return tokens
        spec = P("dp", None) if tokens.shape[0] % mesh.size == 0 else P(None, None)
        return jax.device_put(tokens, NamedSharding(mesh, spec))

    def _run_batch(rows: list[np.ndarray], bucket: int) -> list[dict]:
        tokens = np.zeros((bucket, cfg.seq_len), np.int32)
        for i, row in enumerate(rows):
            tokens[i] = row
        probs = np.asarray(compiled["fn"](compiled["params"],
                                          _place_tokens(tokens)))
        out = []
        for i in range(len(rows)):
            idx = int(probs[i].argmax())
            out.append({
                "priority": PRIORITY_LABELS[idx],
                "confidence": round(float(probs[i, idx]), 4),
            })
        return out

    batcher = MicroBatcher(
        _run_batch, config=bcfg,
        tokens_of=lambda row: int((row != 0).sum()) or 1)

    @app.on_startup
    async def load_model():
        def build():
            t0 = time.perf_counter()
            enable_compile_cache()
            params = init_params(cfg, jax.random.PRNGKey(0))
            mesh = serving_mesh()
            # device-resident once: replicated over the data mesh (or
            # committed to the single device) — serving calls never
            # re-feed weights
            params = (replicate_params(params, mesh) if mesh is not None
                      else jax.device_put(params))
            fn = serving_fn(cfg, mesh)
            compiled["mesh"] = mesh
            # warm every bucket so no request ever pays an XLA compile
            # — after this loop the jit cache must stay flat
            for bucket in bcfg.buckets:
                fn(params, _place_tokens(
                    np.zeros((bucket, cfg.seq_len), np.int32))
                   ).block_until_ready()
            devices = jax.devices()
            compiled["where"] = {
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "warmup_s": time.perf_counter() - t0,
            }
            return params, fn

        # compile off the event loop: the server/sidecar are already up,
        # and probes + the 503 not-ready paths must answer during the
        # (potentially tens of seconds) XLA compile
        params, fn = await asyncio.to_thread(build)
        batcher.start()
        admission.register_signal("ml_tokens_in_flight", batcher.saturation)
        compiled["params"], compiled["fn"] = params, fn

    @app.on_shutdown
    async def unload_model():
        admission.unregister_signal("ml_tokens_in_flight")
        await batcher.stop()

    def _not_ready() -> Response:
        # registered and serving, but the jit warmup hasn't finished: a
        # retryable not-ready with a backoff hint, never an opaque 500
        # — the Retry-After is what keeps broker redeliveries from
        # hot-looping against a loading model
        return Response(503, {"error": "model loading, retry shortly"},
                        headers={"Retry-After": str(WARMUP_RETRY_AFTER)})

    def _shed(exc: SaturatedError) -> Response:
        return Response(429, {"error": str(exc)},
                        headers={"Retry-After": str(int(exc.retry_after or 1))})

    async def _score(task: dict) -> dict:
        return await batcher.submit(np.asarray(encode_task(task, cfg),
                                               np.int32))

    @app.post("/score")
    async def score(req):
        if "fn" not in compiled:
            return _not_ready()
        try:
            task = req.json()
        except ValueError:
            return 400, {"error": "body must be JSON"}
        if not isinstance(task, dict):
            return 400, {"error": "body must be a task object"}
        try:
            result = await _score(task)
        except SaturatedError as exc:
            return _shed(exc)
        if task.get("taskId") is not None:
            result = {**result, "taskId": str(task["taskId"])}
        return result

    @app.subscribe(pubsub=pubsub, topic=topic, route="/on-task-saved")
    async def on_task_saved(req):
        if "fn" not in compiled:
            return _not_ready()  # Retry-After → broker backs off
        task = req.data  # CloudEvents envelope unwrapped
        if not isinstance(task, dict) or not task.get("taskId"):
            return 200  # not a task event; ack and move on
        try:
            result = await _score(task)
        except SaturatedError as exc:
            return _shed(exc)  # Retry-After → broker backs off
        await app.client.save_state(state_store, str(task["taskId"]), result)
        logger.info("scored task %s: %s (%.2f)", task["taskId"],
                    result["priority"], result["confidence"])
        return 200

    @app.get("/scores/{task_id}")
    async def get_score(req):
        value = await app.client.get_state(state_store, req.path_params["task_id"])
        if value is None:
            return 404, {"error": f"no score for {req.path_params['task_id']}"}
        return value

    @app.get("/ml/stats")
    async def ml_stats(req):
        stats = batcher.stats()
        stats["ready"] = "fn" in compiled
        fn = compiled.get("fn")
        stats["jit_cache_size"] = int(fn._cache_size()) if fn is not None else 0
        stats.update(compiled.get("where", {}))
        return stats

    return app
