"""Test harness: virtual 8-device CPU mesh.

The reference tests distributed logic without a cluster by spawning local
processes over a file-store rendezvous (``tests/unit/common.py:129
DistributedExec``).  The JAX analogue is simpler and faster: force the CPU
platform with 8 virtual devices (``--xla_force_host_platform_device_count``)
so every mesh shape up to 8 is testable in-process — same coverage philosophy
(multi-node is never tested directly in CI; a local many-device world is the
proxy).
"""
import contextlib
import functools
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ds.initialize / InferenceEngineV2 place a persistent compile cache inside
# the checkout (utils/compile_cache.py).  The test lane — this process and
# every worker it spawns — compiles fresh instead: a result here must not
# depend on what an earlier run left on disk.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# children the tests spawn (serving workers, elastic ranks, launcher
# bootstraps) run on the CPU like this process does
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


def make_grid(**axes):
    from deepspeed_tpu.parallel.topology import initialize_mesh

    return initialize_mesh(**axes)


@contextlib.contextmanager
def dense_serving_context():
    """An ``InferenceEngineV2`` built inside gets ``ServingContext(fused=
    False)``: the jnp bodies everywhere.  How a test builds the dense
    reference ENGINE; no user option selects it."""
    from deepspeed_tpu.ops import quantizer

    with pytest.MonkeyPatch.context() as m:
        m.setattr(quantizer, "ServingContext",
                  functools.partial(quantizer.ServingContext, fused=False))
        yield


@pytest.fixture
def grid8():
    return make_grid(fsdp=8)


@pytest.fixture(autouse=True)
def _clear_ambient_mesh():
    """initialize() installs the mesh as ambient state (by design, for user
    flows); tests must not leak it into each other — an AOT-topology test
    running after an engine test would otherwise constrain against the
    previous test's CPU mesh."""
    yield
    from deepspeed_tpu.parallel.sharding import set_current_mesh

    set_current_mesh(None)
