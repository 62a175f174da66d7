"""bench.py harness logic that must not depend on host speed or a
live chip: the chip section refuses to run without a chip, the peaks
table refuses a device it does not know, the compile cache lands where
it is placed, and the mTLS topology variant runs.

These are correctness tests for the measurement harness itself — the
wall-clock perf gates live in test_bench.py behind
TASKSRUNNER_PERF_TESTS.
"""

import asyncio
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import bench


def test_tpu_step_bench_raises_without_a_chip():
    """No chip, no number: the tests run on the cpu backend, where the
    bench must fail rather than time the interpreter."""
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.run_tpu_step_bench()


def test_peak_flops_known_kind_and_unknown_kind():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        bench._peak_flops("TPU v99")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, monkeypatch, from_env):
    """The cache follows JAX_COMPILATION_CACHE_DIR when it is set, and
    otherwise sits at the fixed path in the checkout, whatever the
    working directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tasksrunner.ml import compile_cache

    monkeypatch.chdir(tmp_path)
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
        want = str(tmp_path / "cc")
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(REPO / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        if from_env:
            # a compile lands in the placed directory
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            compilation_cache.reset_cache()
            jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
            assert any((tmp_path / "cc").iterdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        compilation_cache.reset_cache()


def test_xproc_mesh_tls_variant_runs_and_restores_env():
    """The mTLS bench topology: per-app certs provisioned, the run
    completes through the authenticated lane, and the driver's cert
    env vars do not leak into the calling process."""
    import os
    from tasksrunner.invoke.pki import CA_ENV, CERT_ENV, KEY_ENV

    before = {k: os.environ.get(k) for k in (CA_ENV, CERT_ENV, KEY_ENV)}
    out = asyncio.run(bench.run_xproc(
        n_tasks=40, warmup=5, rounds=1, concurrency=16, mesh_tls=True))
    assert out["throughput"] > 0
    after = {k: os.environ.get(k) for k in (CA_ENV, CERT_ENV, KEY_ENV)}
    assert before == after
