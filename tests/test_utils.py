"""Profiling + multihost utilities on the simulated device set."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from burst_attn_tpu import obs
from burst_attn_tpu.utils import multihost, profiling


def test_step_timer():
    t = obs.StepTimer()
    x = jnp.ones((256, 256))
    f = jax.jit(lambda x: x @ x)
    for _ in range(3):
        with t:
            t.watch(f(x))
    s = t.summary()
    assert s["steps"] == 2  # first dropped as compile
    assert s["min_s"] <= s["mean_s"] <= s["max_s"]


def test_step_timer_requires_watch():
    t = obs.StepTimer()
    with pytest.raises(RuntimeError, match="watch"):
        with t:
            pass


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "prof")
    with profiling.trace(d):
        with obs.annotate("matmul"):
            jnp.ones((64, 64)) @ jnp.ones((64, 64))
    found = [f for _, _, fs in os.walk(d) for f in fs]
    assert found, "no profile artifacts written"


def test_make_hybrid_mesh_single_host():
    mesh = multihost.make_hybrid_mesh(ici={"intra": 4}, dcn={"inter": 2})
    assert mesh.axis_names == ("inter", "intra")
    assert mesh.shape == {"inter": 2, "intra": 4}
    with pytest.raises(ValueError, match="devices"):
        multihost.make_hybrid_mesh(ici={"intra": 16}, dcn={"inter": 2})


def test_initialize_single_process_noop():
    multihost.initialize()  # must not raise in a single-process run
    assert jax.process_count() == 1


def test_place_compile_cache(monkeypatch):
    """Placed from outside (the environment variable JAX reads itself), the
    repo sets no directory; otherwise it is <checkout>/.jax_cache, a fixed
    path derived from the package's own location."""
    from pathlib import Path

    import burst_attn_tpu
    from burst_attn_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = Path(burst_attn_tpu.__file__).resolve().parents[1]
        assert place_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
