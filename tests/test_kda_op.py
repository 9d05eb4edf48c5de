"""`ops/kda.py`: the chunked gated delta rule against the recurrence token
by token, the kernels (interpreted) against the plain chunked form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import kda
from kubeflow_tpu.testing.hlo import pallas_kernel_names

NAMES = ("q", "k", "v", "g", "b")


def recurrence(q, k, v, g, b):
    """o by the definition, a position at a time: q, k, v, g [B, S, H, d],
    b [B, S, H], float32."""
    bsz, _, h, d = q.shape

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", kt, bt[..., None] * (vt - seen)
        )
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    hi = jax.default_matmul_precision("highest")
    with hi:
        _, o = jax.lax.scan(
            step, jnp.zeros((bsz, h, d, d), jnp.float32),
            tuple(jnp.moveaxis(u, 1, 0) for u in (q, k, v, g, b)),
        )
    return jnp.moveaxis(o, 0, 1)


def operands(seq, heads, d, *, decay=0.1, seed=0, batch=2, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, seq, heads * d)
    unit = lambda u: (
        u.reshape(batch, seq, heads, d)
        / jnp.linalg.norm(u.reshape(batch, seq, heads, d), axis=-1, keepdims=True)
    ).reshape(shape)
    q = unit(jax.random.normal(keys[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -decay * jax.random.uniform(keys[3], shape, minval=0.1, maxval=2.0)
    b = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return tuple(u.astype(dtype) for u in (q, k, v)) + (g, b)


def by_heads(ops, heads):
    q, k, v, g, b = ops
    split = lambda u: u.reshape(*u.shape[:2], heads, -1).astype(jnp.float32)
    return split(q), split(k), split(v), split(g), b


def close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert np.isfinite(got).all() and gap < tol, (what, gap)


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_matches_the_recurrence_values_and_gradients(chunk):
    heads, d, seq = 2, 8, 32
    ops = operands(seq, heads, d)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, heads * d))

    def plain(*ops):
        o = recurrence(*by_heads(ops, heads))
        return jnp.sum(o.reshape(weight.shape) * weight), o

    def chunked(*ops):
        with jax.default_matmul_precision("highest"):
            o = kda.kda_chunked(*ops, chunk=chunk)
        return jnp.sum(o * weight), o

    every = tuple(range(5))
    (_, want), want_grads = jax.value_and_grad(plain, every, has_aux=True)(*ops)
    (_, got), got_grads = jax.value_and_grad(chunked, every, has_aux=True)(*ops)
    close(got, want.reshape(got.shape), 1e-5, "o")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        close(a, b, 1e-4, f"d{name}")


def test_a_decay_past_e_minus_100_inside_a_chunk_stays_finite_and_right():
    heads, d, seq, chunk = 1, 8, 32, 32
    ops = operands(seq, heads, d, decay=4.0, batch=1)
    g = ops[3]
    assert float(jnp.min(jnp.sum(g, axis=1))) < -100.0
    want = recurrence(*by_heads(ops, heads))
    with jax.default_matmul_precision("highest"):
        got = kda.kda_chunked(*ops, chunk=chunk)
        grads = jax.grad(
            lambda *ops: jnp.sum(kda.kda_chunked(*ops, chunk=chunk)),
            tuple(range(5)),
        )(*ops)
    close(got, want.reshape(got.shape), 1e-5, "o")
    assert all(bool(jnp.isfinite(u).all()) for u in grads)


def test_the_in_chunk_inverse():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (3, 32, 32)), -1)
    want = np.linalg.inv(np.eye(32) + np.asarray(a, np.float64))
    close(kda._unit_lower_inverse(a), want, 1e-5)
    with pytest.raises(ValueError, match="halves"):
        kda._unit_lower_inverse(jnp.zeros((24, 24)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_kernels_match_the_chunked_form(dtype, tol):
    heads, d, seq, chunk = 16, 16, 40, 16  # a padded tail, two grid steps of heads
    ops = operands(seq, heads, d, batch=1, seed=3, dtype=dtype)
    weight = jax.random.normal(jax.random.PRNGKey(5), (1, seq, heads * d))

    def run(interpret):
        def loss(*ops):
            o = kda.kda_scan(*ops, chunk=chunk, interpret=interpret)
            return jnp.sum(o.astype(jnp.float32) * weight), o
        return jax.value_and_grad(loss, tuple(range(5)), has_aux=True)(*ops)

    (_, want), want_grads = run(None)
    (_, got), got_grads = run(True)
    close(got, want, tol, "o")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        close(a, b, tol, f"d{name}")
    names = pallas_kernel_names(
        jax.grad(lambda *ops: jnp.sum(
            kda.kda_scan(*ops, chunk=chunk, interpret=True).astype(jnp.float32)
        )), *ops
    )
    assert set(names) == {"kda_fwd", "kda_bwd"}


def test_flash_policy_keeps_the_kernels_results_and_drops_the_forward():
    heads, d, seq, chunk = 4, 16, 32, 16
    ops = operands(seq, heads, d, batch=1)
    policy = jax.checkpoint_policies.save_only_these_names(
        kda.CHECKPOINT_OUT_NAME, kda.CHECKPOINT_STATES_NAME
    )
    scan = jax.checkpoint(
        lambda *ops: kda.kda_scan(*ops, chunk=chunk, interpret=True),
        policy=policy,
    )
    names = pallas_kernel_names(
        jax.grad(lambda *ops: jnp.sum(scan(*ops))), *ops
    )
    assert names.count("kda_fwd") == 1 and names.count("kda_bwd") == 1


def test_schedule():
    sched = kda.kda_schedule(8192, heads=32, head_dim=128, chunk=64)
    assert sched["chunks"] == 128 and sched["grid"] == (1, 4, 128)
    assert sched["heads_a_step"] == 8 and sched["sub_block"] == 16
    assert sched["sub_block_pairs"] == 10
    # o and the states entering each chunk, bfloat16
    assert sched["saved_bytes_a_call"] == (8192 * 4096 + 128 * 128 * 4096) * 2
    assert sched["state_scratch_bytes"] == 128 * 1024 * 4
    assert kda.kda_schedule(100, heads=2, head_dim=128, chunk=64)[
        "padded_seq_len"
    ] == 128


def test_shapes_are_refused_with_their_numbers():
    ops = operands(16, 2, 8)
    with pytest.raises(ValueError, match="whole heads"):
        kda.kda_scan(*ops[:4], jnp.zeros((2, 16, 3)), chunk=16)


def test_on_a_mesh_the_kernels_run_a_shard_and_give_the_same():
    """Batch over `dp`, whole heads over `tp`: what `shard_map` hands a
    device is a sequence of its own heads."""
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    heads, d, seq, chunk = 4, 16, 32, 16
    ops = operands(seq, heads, d, batch=2, seed=5)
    mesh = build_mesh(MeshSpec(dp=2, tp=2), jax.devices()[:4])
    run = lambda mesh: jax.jit(jax.value_and_grad(
        lambda *ops: jnp.sum(kda.kda_scan(
            *ops, chunk=chunk, mesh=mesh, interpret=True
        ) ** 2),
        tuple(range(5)),
    ))(*ops)
    want, want_grads = run(None)
    got, got_grads = run(mesh)
    close(got, want, 1e-5, "loss")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        close(a, b, 1e-4, f"d{name}")
    with pytest.raises(ValueError, match="heads over tp"):
        kda.kda_scan(
            *operands(seq, 3, d, batch=2), chunk=chunk, mesh=mesh, interpret=True
        )
