"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's `local[2]` in-process Spark trick
(`utils/.../test/TestSparkContext.scala:36-80`): "distributed" behavior is
tested on local virtual devices — here via XLA's host-platform device count,
so every sharding/collective path is exercised without a TPU pod.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Learned cost model (perf/): OFF by default in the suite — tier-1
# sweeps/schedules must make bit-deterministic cold-start decisions and
# must not read or append to the checkout's profile corpus.
# Tests that exercise the model opt back in with monkeypatch.setenv
# (perf.params.enabled() reads the env per call).
os.environ["TRANSMOGRIFAI_PERF_MODEL"] = "0"

# Crash flight recorder: serving tests trip breakers/watchdogs on
# purpose, and each incident dumps a post-mortem artifact — point the
# dump dir at a per-run temp location instead of the checkout's store
# directory (same hygiene rule as the perf corpus above).
import tempfile as _tempfile  # noqa: E402

os.environ.setdefault(
    "TRANSMOGRIFAI_FLIGHT_DIR",
    os.path.join(_tempfile.gettempdir(),
                 f"transmogrifai-flight-tests-{os.getpid()}"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The fast suite is compile-dominated (tree-fit programs at many shapes):
# the persistent XLA cache makes every run after the first start warm.
# Executables are keyed by HLO + backend version, so this stays
# hermetic, and CPU entries never collide with a chip's in the one
# cache directory (utils/compile_cache.py).
from transmogrifai_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_uids():
    """Deterministic uids per test (reference resets UID in test fixtures)."""
    from transmogrifai_tpu.utils import uid
    uid.reset()
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)
