"""The gated-norm kernel pair (`ops/gatenorm.py`, interpreted here) against
the op's plain form, `gated_norm_plain`, which runs wherever the pair does
not (Mamba-2's skip, `silu` gate and a group's RMS norm; KDA's head norm
and sigmoid gate); and which of the two `gated_norm` takes where."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kubeflow_tpu.ops import gatenorm
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5


def plain(o, gate, scale, x=None, d=None, *, group, gate_first, act=None):
    return gatenorm.gated_norm_plain(
        o, gate, scale, group=group, eps=EPS, gate_first=gate_first,
        skip=None if x is None else (x, d), act=act,
    )


def kernels(o, gate, scale, x=None, d=None, *, group, gate_first, act=None):
    return gatenorm.gated_norm(
        o, gate, scale, group=group, eps=EPS, gate_first=gate_first,
        skip=None if x is None else (x, d), act=act, interpret=True,
    )


def _drawn(shape, skip, seed=0):
    """(o, gate, scale, x, d), then `out`'s cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = lambda key: jax.random.normal(key, shape, F32).astype(BF16)
    width = shape[-1]
    return (
        wide(keys[0]), wide(keys[1]),
        1.0 + 0.3 * jax.random.normal(keys[2], (width,), F32),
        wide(keys[3]) if skip else None,
        1.0 + 0.3 * jax.random.normal(keys[4], (width,), F32) if skip else None,
    ), wide(keys[5])


FORMS = {
    "silu then a group's norm, with a skip": dict(
        group=1024, gate_first=True, skip=True, width=2048,
    ),
    "a head's norm then sigmoid": dict(
        group=128, gate_first=False, skip=False, width=256,
    ),
    # the activation apart from the order (Gated DeltaNet's: norm, then silu)
    "a head's norm then silu": dict(
        group=128, gate_first=False, skip=False, width=256, act="silu",
    ),
    "sigmoid then a group's norm": dict(
        group=256, gate_first=True, skip=False, width=512, act="sigmoid",
    ),
}
NAMES = ("o", "gate", "scale", "x", "d")


def _held_to_plain(form, shape, seed=0):
    spec = FORMS[form]
    how = dict(
        group=spec["group"], gate_first=spec["gate_first"], act=spec.get("act")
    )
    args, dout = _drawn((*shape, spec["width"]), spec["skip"], seed)
    got, got_vjp = jax.vjp(functools.partial(kernels, **how), *args)
    want, want_vjp = jax.vjp(functools.partial(plain, **how), *args)
    assert got.dtype == BF16
    # one unit of bfloat16 at the largest value
    np.testing.assert_allclose(
        got.astype(F32), want.astype(F32),
        atol=2.0 ** -8 * float(jnp.max(jnp.abs(want.astype(F32)))),
    )
    for name, a, b in zip(NAMES, got_vjp(dout), want_vjp(dout)):
        if b is None:
            assert a is None, name
            continue
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(F32), b.astype(F32)
        # every gradient is rounded once from float32 on both sides: what
        # differs is a unit of bfloat16 here and there (the sigmoid by
        # `tanh`, the order of the sums)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-3, name


@pytest.mark.parametrize("seq", [128, 384, 2048])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_forward_and_every_gradient_match_the_plain_expression(
    form, batch, seq
):
    """One block, three blocks of 128 rows and (a group of 1,024 lanes)
    eight of 256 rows, the scale's and d's gradients summed over them and
    over the batch."""
    _held_to_plain(form, (batch, seq))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_lane_slices_of_wider_arrays_are_operands_like_any(form):
    """The state-space mixer's z and x are the leading columns of arrays
    72.5 and 40 lane tiles wide: sliced out, the same values and
    gradients, each gradient of the slice's own shape."""
    spec = FORMS[form]
    how = dict(
        group=spec["group"], gate_first=spec["gate_first"], act=spec.get("act")
    )
    (o, gate, scale, x, d), dout = _drawn(
        (2, 256, spec["width"]), spec["skip"], seed=1
    )
    pad = lambda u: None if u is None else jnp.concatenate(
        [u, jnp.full((*u.shape[:-1], 192), 7.0, u.dtype)], axis=-1
    )
    cut = lambda u: None if u is None else u[..., :spec["width"]]
    run = lambda fn: jax.vjp(
        lambda gate, x: fn(o, cut(gate), scale, cut(x), d, **how),
        pad(gate), pad(x),
    )
    (got, got_vjp), (want, want_vjp) = run(kernels), run(plain)
    np.testing.assert_allclose(
        got.astype(F32), want.astype(F32),
        atol=2.0 ** -8 * float(jnp.max(jnp.abs(want.astype(F32)))),
    )
    for a, b in zip(got_vjp(dout), want_vjp(dout)):
        if b is None:
            assert a is None
            continue
        assert a.shape == b.shape and not a[..., spec["width"]:].any()
        a, b = a.astype(F32), b.astype(F32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-3


def test_each_sequence_and_each_group_is_normed_alone():
    """A row of another sequence or the lanes of another group move
    nothing: each [1, S, group] piece alone gives what it gives inside."""
    (o, gate, scale, _, _), dout = _drawn((2, 256, 256), skip=False)
    how = dict(group=128, gate_first=False)
    both, vjp = jax.vjp(lambda o, gate: kernels(o, gate, scale, **how), o, gate)
    do, dgate = vjp(dout)
    for b in range(2):
        for lanes in (slice(0, 128), slice(128, 256)):
            cut = lambda u: u[b:b + 1, :, lanes]
            alone, vjp = jax.vjp(
                lambda o, gate: kernels(o, gate, scale[lanes], **how),
                cut(o), cut(gate),
            )
            np.testing.assert_array_equal(alone, cut(both))
            for a, whole in zip(vjp(cut(dout)), (do, dgate)):
                np.testing.assert_array_equal(a, cut(whole))


FALLBACKS = {
    "float32": dict(dtype=F32),
    "a float32 gate": dict(gate_dtype=F32),
    "a group that is no whole lane tile": dict(group=64),
    "a width that is no whole group": dict(width=384, group=256),
    "a group wider than a block": dict(width=4096, group=4096),
    "a sequence of 100 rows": dict(seq=100),
    "a mesh of two devices": dict(devices=2),
    "a mesh of four": dict(devices=4),
    "a backend that interprets": dict(compiled=None),
}


@pytest.mark.parametrize("case", [None, *sorted(FALLBACKS)])
def test_where_the_kernels_apply(case, monkeypatch):
    how = dict(
        dtype=BF16, seq=256, width=256, group=128, devices=1, compiled=True,
    ) | (FALLBACKS[case] if case else {})
    mesh = Mesh(np.array(jax.devices()[: how["devices"]]), ("x",))
    shape = (2, how["seq"], how["width"])
    o = jax.ShapeDtypeStruct(shape, how["dtype"])
    gate = jax.ShapeDtypeStruct(shape, how.get("gate_dtype", how["dtype"]))
    assert gatenorm.kernels_apply(
        o, gate, how["group"], mesh, compiled=how["compiled"]
    ) is (case is None)
    assert gatenorm.kernels_apply(o, gate, how["group"], None) is False
    # ... and `gated_norm` takes the form it says: the pair, or the plain
    # form's very equations and no kernel.
    monkeypatch.setattr(gatenorm, "kernels_apply", functools.partial(
        gatenorm.kernels_apply, compiled=how["compiled"]
    ))
    scale = jax.ShapeDtypeStruct(shape[-1:], F32)
    args = dict(group=how["group"], eps=EPS, gate_first=False)
    traced = lambda fn, **more: jax.make_jaxpr(
        functools.partial(fn, **args, **more)
    )(o, gate, scale)
    entry = traced(gatenorm.gated_norm, mesh=mesh)
    if case is None:
        assert jaxpr_kernel_names(entry.jaxpr) == ["gatenorm_fwd"]
    else:
        assert jaxpr_kernel_names(entry.jaxpr) == []
        assert str(entry) == str(traced(gatenorm.gated_norm_plain))
    if not set(FALLBACKS.get(case, {})) - {"devices", "compiled"}:
        # shapes the pair takes: told to interpret, it runs whatever the
        # backend and the mesh
        told = traced(gatenorm.gated_norm, mesh=mesh, interpret=True)
        assert jaxpr_kernel_names(told.jaxpr) == ["gatenorm_fwd"]


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_pair_is_one_call_each_way_under_its_names(form):
    spec = FORMS[form]
    how = dict(
        group=spec["group"], gate_first=spec["gate_first"], act=spec.get("act")
    )
    args, dout = _drawn((1, 256, spec["width"]), spec["skip"])
    run = functools.partial(kernels, **how)
    forward = jax.make_jaxpr(run)(*args)
    assert jaxpr_kernel_names(forward.jaxpr) == ["gatenorm_fwd"]
    backward = jax.make_jaxpr(lambda *a: jax.vjp(run, *a)[1](dout))(*args)
    assert sorted(jaxpr_kernel_names(backward.jaxpr)) == [
        "gatenorm_bwd", "gatenorm_fwd",
    ]
    wide = [
        v.aval for eqn in backward.jaxpr.eqns for v in eqn.outvars
        if v.aval.dtype == F32 and v.aval.shape[-2:] == args[0].shape[-2:]
    ]
    assert not wide  # no float32 [tokens, W] array outside the kernels
