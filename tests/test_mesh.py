"""MeshSpec/build_mesh axis inference and validation."""
import jax
import numpy as np
import pytest

from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.parallel.mesh import (
    AXES,
    local_mesh_spec,
    step_compiler_options,
)


def test_resolve_wildcard():
    spec = MeshSpec(dp=-1, tp=2).resolve(8)
    assert spec.dp == 4 and spec.tp == 2
    assert spec.data_parallelism == 4


def test_resolve_exact():
    spec = MeshSpec(dp=2, fsdp=2, tp=2).resolve(8)
    assert spec.sizes() == (1, 2, 2, 1, 1, 2)


def test_resolve_rejects_bad_product():
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(dp=-1, tp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(dp=-1, fsdp=-1).resolve(8)


def test_build_mesh_axes(devices):
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices)
    assert mesh.axis_names == AXES
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
    assert mesh.devices.size == 8


def test_build_mesh_default_is_all_dp(devices):
    mesh = build_mesh(devices=devices)
    assert mesh.shape["dp"] == 8


def test_local_mesh_spec():
    assert local_mesh_spec(8, tp=2).fsdp == 4
    with pytest.raises(ValueError):
        local_mesh_spec(8, tp=3)


class _StubTpu:
    """All `step_compiler_options` may look at: a device's platform."""

    platform = "tpu"


@pytest.mark.parametrize(
    "n,spec",
    [
        (1, MeshSpec()),
        (4, MeshSpec(dp=-1)),
        (8, MeshSpec(dp=-1)),
        (8, MeshSpec(dp=2, fsdp=2, tp=2)),
    ],
    ids=["1", "4", "8", "8-dp2-fsdp2-tp2"],
)
def test_step_compiler_options_none_on_cpu_meshes(devices, n, spec):
    assert step_compiler_options(build_mesh(spec, devices[:n])) is None


@pytest.mark.parametrize(
    "spec,engaged",
    [
        (MeshSpec(), False),  # one device: no peer
        (MeshSpec(dp=4), False),  # peers, but only gradient tuples to reduce
        (MeshSpec(dp=2, fsdp=2), False),
        (MeshSpec(dp=2, tp=2), True),
        (MeshSpec(sp=4), True),
    ],
    ids=["one-device", "dp4", "dp2-fsdp2", "dp2-tp2", "sp4"],
)
def test_step_compiler_options_follow_the_tpu_mesh(spec, engaged):
    n = int(np.prod(spec.sizes()))
    stubs = np.array([_StubTpu() for _ in range(n)], dtype=object)
    options = step_compiler_options(jax.sharding.Mesh(stubs.reshape(spec.sizes()), AXES))
    assert (options is not None) == engaged and options != {}
    assert all(
        type(k) is str and type(v) in (bool, int)
        for k, v in (options or {}).items()
    )


def test_mesh_runs_sharded_compute(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    xs = jax.device_put(x, NamedSharding(mesh8, P(("dp", "fsdp"), None)))
    y = jax.jit(lambda a: (a * 2).sum())(xs)
    assert float(y) == float(x.sum() * 2)


def test_hybrid_mesh_two_slices(devices):
    """2 slices x 4 chips: dp spans DCN, fsdp/tp ride ICI in-slice."""
    from kubeflow_tpu.parallel.mesh import build_hybrid_mesh

    mesh = build_hybrid_mesh(
        MeshSpec(fsdp=2, tp=2), MeshSpec(dp=2), devices
    )
    assert mesh.axis_names == AXES
    assert mesh.shape["dp"] == 2
    assert mesh.shape["fsdp"] == 2 and mesh.shape["tp"] == 2
    assert mesh.devices.size == 8
    # The dp axis is the slice boundary: within one dp index, all devices
    # come from the same consecutive-device "slice".
    arr = mesh.devices.reshape(2, 4)  # dp, (fsdp*tp)
    ids0 = {d.id for d in arr[0].flat}
    ids1 = {d.id for d in arr[1].flat}
    assert ids0 == {0, 1, 2, 3} and ids1 == {4, 5, 6, 7}


def test_hybrid_mesh_runs_collectives(devices):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubeflow_tpu.parallel.mesh import build_hybrid_mesh

    mesh = build_hybrid_mesh(MeshSpec(fsdp=4), MeshSpec(dp=2), devices)
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    y = jax.jit(lambda a: a.sum())(xs)
    assert float(y) == float(x.sum())


def test_hybrid_mesh_rejects_wildcard_dcn(devices):
    from kubeflow_tpu.parallel.mesh import build_hybrid_mesh

    with pytest.raises(ValueError, match="explicit"):
        build_hybrid_mesh(MeshSpec(fsdp=4), MeshSpec(dp=-1), devices)


def test_hybrid_mesh_bad_slice_division(devices):
    from kubeflow_tpu.parallel.mesh import build_hybrid_mesh

    with pytest.raises(ValueError, match="divisible"):
        build_hybrid_mesh(MeshSpec(fsdp=2), MeshSpec(dp=3), devices)
