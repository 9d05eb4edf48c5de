"""Ring attention (sequence-parallel shard_map) vs dense reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops import dense_attention, ring_attention
from kubeflow_tpu.parallel import MeshSpec, build_mesh


def _qkv(key, b=2, s=16, h=4, d=8):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, jnp.float32),
        jax.random.normal(kk, shape, jnp.float32),
        jax.random.normal(kv, shape, jnp.float32),
    )


def test_dense_attention_matches_naive():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = dense_attention(q, k, v, causal=False)
    # Naive per-query softmax.
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", w, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_dense_causal_ignores_future():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    out = dense_attention(q, k, v, causal=True)
    # Changing future keys/values must not change earlier outputs.
    k2 = k.at[:, -1].set(100.0)
    v2 = v.at[:, -1].set(-3.0)
    out2 = dense_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-6
    )
    assert not np.allclose(np.asarray(out[:, -1]), np.asarray(out2[:, -1]))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_matches_dense(devices, causal, sp):
    mesh = build_mesh(MeshSpec(dp=2, sp=sp, tp=8 // (2 * sp) or 1), devices)
    q, k, v = _qkv(jax.random.PRNGKey(2), b=4, s=32)
    ref = dense_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda a, b_, c: ring_attention(a, b_, c, mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_ring_trivial_sp_falls_back(mesh8):
    q, k, v = _qkv(jax.random.PRNGKey(3))
    out = ring_attention(q, k, v, mesh8, causal=True)  # mesh8 has sp=1
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_ring_with_sharded_inputs(devices):
    # End-to-end under jit with inputs actually laid out over the mesh.
    mesh = build_mesh(MeshSpec(dp=2, sp=4), devices)
    q, k, v = _qkv(jax.random.PRNGKey(4), b=4, s=64)
    sh = NamedSharding(mesh, P(("dp", "fsdp"), "sp", None, None))
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    out = jax.jit(
        lambda a, b_, c: ring_attention(a, b_, c, mesh, causal=True)
    )(qs, ks, vs)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


# -- attend: the model's one entry point ------------------------------------
#
# (impl, kernels compile?, mesh, (B, S, H, Hkv)) -> the kernels of the traced
# program, or what is raised. Nothing runs: the dispatch is read from the
# `pallas_call`s of a jaxpr.
ATTEND_CASES = {
    "auto_on_the_cpu": ("auto", False, None, (2, 256, 4, 4), []),
    "auto_compiled": ("auto", True, None, (2, 256, 4, 4), ["flash_fwd_compact"]),
    "dense_forced": ("dense", True, None, (2, 256, 4, 4), []),
    "auto_dp_tp_grouped": (
        "auto", True, {"dp": 2, "tp": 2}, (4, 256, 8, 4), ["flash_fwd_compact"]
    ),
    "sp_ring_flash": (
        # a hop is a switch over a chunk from before (the whole rectangle),
        # the device's own (the triangle) and one from after (skipped)
        "auto", True, {"sp": 2}, (2, 256, 4, 2),
        ["flash_fwd_rect", "flash_fwd_compact"] * 2,
    ),
    "sp_ring_dense_hops": ("auto", False, {"sp": 2}, (2, 256, 4, 2), []),
    "auto_batch_not_dividing_dp": (
        "auto", True, {"dp": 2}, (3, 256, 4, 4), RuntimeWarning
    ),
    "flash_batch_not_dividing_dp": (
        "flash", True, {"dp": 2}, (3, 256, 4, 4), ValueError
    ),
    "unknown_impl": ("pallas", True, None, (2, 256, 4, 4), ValueError),
}


@pytest.mark.parametrize("case", ATTEND_CASES)
def test_attend_dispatch(devices, monkeypatch, case):
    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.testing.hlo import _walk_eqns

    impl, compiled, spec, (b, s, h, hkv), want = ATTEND_CASES[case]
    monkeypatch.setattr(attention, "kernels_compiled", lambda: compiled)
    mesh = spec and build_mesh(
        MeshSpec(**spec), devices[: int(np.prod(list(spec.values())))]
    )
    q = jax.ShapeDtypeStruct((b, s, h, 8), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, s, hkv, 8), jnp.float32)
    trace = lambda: jax.make_jaxpr(
        lambda q, k, v: attention.attend(q, k, v, mesh=mesh, impl=impl)
    )(q, kv, kv)

    if want is ValueError:
        with pytest.raises(ValueError, match="attention_impl"):
            trace()
        return
    if want is RuntimeWarning:
        with pytest.warns(RuntimeWarning, match="DENSE"):
            jaxpr = trace()
        want = []
    else:
        jaxpr = trace()
    assert jaxpr.out_avals[0].shape == q.shape
    outer = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    calls = [
        e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    ]
    assert [e.params["name"] for e in calls] == want
    if spec:
        # Whatever runs on a mesh runs inside the seam's own shard_map...
        assert ("shard_map" in outer) == (bool(want) or "sp" in spec)
    if case == "auto_dp_tp_grouped":
        # ...and the kernels pick a query head's K/V head themselves: K and
        # V go in as they came, a device's 2 rows of 2 heads, not its 4.
        (call,) = calls
        shapes = [v.aval.shape for v in call.invars if len(v.aval.shape) == 3]
        assert shapes == [(2 * 4, s, 8), (2 * 2, s, 8), (2 * 2, s, 8)]
    if "sp" in (spec or {}):
        permutes = [
            e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "ppermute"
        ]
        assert permutes


# (mesh, window) -> the kernels traced with a rope part, or the refusal.
ATTEND_ROPE_CASES = {
    "no_mesh": (None, None, ["flash_fwd_mla"]),
    "dp": ({"dp": 2}, None, ["flash_fwd_mla"]),
    "tp": ({"tp": 2}, None, "on a mesh .*'tp': 2"),
    "sp": ({"sp": 2}, None, "on a mesh .*'sp': 2"),
    "window": (None, 16, "a rope part of 8 dims with window=16"),
}


@pytest.mark.parametrize("case", ATTEND_ROPE_CASES)
def test_attend_takes_a_rope_part_on_shards_of_the_batch_only(
    devices, monkeypatch, case
):
    """Two-part scores (latent attention) through the seam: the kernels'
    `mla` calls alone or inside the batch's `shard_map`, the one rope key
    going in as it came; a window, the ring and a `tp` axis are refused
    with their numbers."""
    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.testing.hlo import _walk_eqns

    spec, window, want = ATTEND_ROPE_CASES[case]
    monkeypatch.setattr(attention, "kernels_compiled", lambda: True)
    mesh = spec and build_mesh(
        MeshSpec(**spec), devices[: int(np.prod(list(spec.values())))]
    )
    b, s, h = 2, 64, 4
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)
    args = (shape(b, s, h, 16),) * 3 + (shape(b, s, h, 8), shape(b, s, 8))
    trace = lambda: jax.make_jaxpr(
        lambda q, k, v, qr, kr: attention.attend(
            q, k, v, mesh=mesh, impl="auto", window=window, q_rope=qr,
            k_rope=kr, scale=0.25,
        )
    )(*args)
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            trace()
        return
    jaxpr = trace()
    assert jaxpr.out_avals[0].shape == (b, s, h, 16)
    calls = [
        e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    ]
    assert [e.params["name"] for e in calls] == want
    rows = b // (spec or {}).get("dp", 1)
    shapes = [v.aval.shape for v in calls[0].invars if len(v.aval.shape) == 3]
    # q, k, v and q_rope a head a row; the rope key a batch row, once
    assert shapes == [(rows * h, s, 16)] * 3 + [(rows * h, s, 8), (rows, s, 8)]


def test_dense_attention_with_a_rope_part_is_the_joint_heads_attention():
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    b, s, h = 2, 16, 3
    q, k = (jax.random.normal(kx, (b, s, h, 8)) for kx in keys[:2])
    v = jax.random.normal(keys[2], (b, s, h, 12))  # of another width
    q_rope = jax.random.normal(keys[3], (b, s, h, 4))
    k_rope = jax.random.normal(keys[4], (b, s, 4))
    joint_k = jnp.concatenate(
        [k, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, 4))], axis=-1
    )
    want = dense_attention(jnp.concatenate([q, q_rope], axis=-1), joint_k, v)
    got = dense_attention(q, k, v, q_rope=q_rope, k_rope=k_rope)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        dense_attention(q, k, v, q_rope=q_rope, k_rope=k_rope, scale=0.5),
        dense_attention(
            jnp.concatenate([q, q_rope], axis=-1) * 0.5 * 12 ** 0.5, joint_k, v
        ),
        atol=1e-5, rtol=1e-5,
    )
