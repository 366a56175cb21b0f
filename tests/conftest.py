"""Test harness: run everything on a CPU-simulated 8-device mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on virtual CPU devices (`--xla_force_host_platform_device_count`),
the standard JAX technique for SPMD tests. Must run before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# never stall on hub retries in tests; local files / fallbacks only
os.environ.setdefault("HF_HUB_OFFLINE", "1")
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax  # noqa: E402

# No persistent compilation cache is set here: a run compiles what it
# tests. A warm cache is safe on jaxlib 0.9.0 (cold and warm runs of
# test_ppo_e2e + test_checkpoint, donated train steps included, both
# pass), so serve children the tests start through the CLI keep the
# CLI's cache like any user's.

# JAX's DEFAULT matmul precision on CPU downcasts to bf16-like accuracy;
# correctness tests need true f32 matmuls (on TPU the library passes
# bf16 compute_dtype explicitly, so this only affects tests).
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def serve_mesh_devices(devices):
    """The mesh-serving rig: asserts the forced-host device pool covers
    a tp=2 x fsdp=2 serve slice. In-process pytest runs always have 8
    (the env block above forces them before jax initializes); standalone
    runs go through `make serve-mesh`, which sets XLA_FLAGS explicitly.
    Tests needing the rig take this fixture and carry @pytest.mark.mesh
    so the target can select exactly them."""
    if len(devices) < 4:
        pytest.skip(
            "mesh-serving tests need >= 4 devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    return devices
