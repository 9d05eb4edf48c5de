"""Test fixtures: a virtual 8-device CPU mesh.

The reference tested distributed behavior only against a real GKE cluster
(SURVEY.md §4.3); the simulated multi-host fixture it lacked is this file.
Env vars must be set before jax is first imported, hence the assignments at
module import time (pytest imports conftest before test modules).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# jax may already be imported (a pytest plugin, a `-p` module), in which
# case the env var above came too late — force the platform through the
# config API as well.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    """2x2x2 mesh: dp=2, fsdp=2, tp=2 — exercises every collective family."""
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices)


@pytest.fixture(scope="session")
def tls_paths(tmp_path_factory):
    """One platform CA + server cert for the whole test session: every
    secure-facade test serves HTTPS with these and pins the CA — bearer
    tokens never ride plaintext, mirroring the launcher's boot path."""
    from kubeflow_tpu.web import tls

    return tls.ensure_tls_dir(str(tmp_path_factory.mktemp("tls")))
