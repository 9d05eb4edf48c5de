"""`ops/kda.py`'s second form, the delta rule with a decay a HEAD over grouped
key heads (Gated DeltaNet): the plain chunked form and the kernels `gdn_fwd`
/ `gdn_bwd` (interpreted) against the recurrence a position at a time, and
against the channel form over the same inputs with g widened and q, k
repeated: the two forms are one rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import kda
from kubeflow_tpu.testing.hlo import pallas_kernel_names

NAMES = ("q", "k", "v", "g", "b")
EVERY = tuple(range(5))


def recurrence(q, k, v, g, b):
    """o by the definition, a position at a time: q, k [B, S, H_k, d], v
    [B, S, H, d], g, b [B, S, H], float32; value head h reads key head
    h // (H / H_k)."""
    bsz, _, h, d = v.shape
    group = h // q.shape[2]
    q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[..., None, None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", kt, bt[..., None] * (vt - seen)
        )
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(
            step, jnp.zeros((bsz, h, d, d), jnp.float32),
            tuple(jnp.moveaxis(u, 1, 0) for u in (q, k, v, g, b)),
        )
    return jnp.moveaxis(o, 0, 1)


def operands(
    seq, heads, key_heads, d, *, decay=0.3, seed=0, batch=2, dtype=jnp.float32
):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key, n):
        u = jax.random.normal(key, (batch, seq, n, d))
        return (u / jnp.linalg.norm(u, axis=-1, keepdims=True)).reshape(
            batch, seq, n * d
        )

    q, k = unit(keys[0], key_heads) * d ** -0.5, unit(keys[1], key_heads)
    v = jax.random.normal(keys[2], (batch, seq, heads * d))
    g = -decay * jax.random.uniform(
        keys[3], (batch, seq, heads), minval=0.1, maxval=2.0
    )
    b = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return tuple(u.astype(dtype) for u in (q, k, v)) + (g, b)


def by_definition(ops, d):
    q, k, v, g, b = ops
    split = lambda u: u.reshape(*u.shape[:2], -1, d).astype(jnp.float32)
    o = recurrence(split(q), split(k), split(v), g, b)
    return o.reshape(v.shape)


def close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert np.isfinite(got).all() and gap < tol, (what, gap)


def _value_and_grads(f, ops, weight):
    def loss(*ops):
        o = f(*ops)
        return jnp.sum(o.astype(jnp.float32) * weight), o

    (_, o), grads = jax.value_and_grad(loss, EVERY, has_aux=True)(*ops)
    return o, grads


@pytest.mark.parametrize("key_heads", [2, 4], ids=["grouped", "equal heads"])
@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_both_forms_match_the_recurrence_values_and_every_gradient(
    form, key_heads
):
    """Three chunks of 16, four value heads over two key heads or four."""
    heads, d, seq, chunk = 4, 8, 48, 16
    ops = operands(seq, heads, key_heads, d)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, heads * d))
    want, want_grads = _value_and_grads(
        lambda *ops: by_definition(ops, d), ops, weight
    )

    def run(*ops):
        if form == "kernels":
            return kda.kda_scan(*ops, chunk=chunk, interpret=True)
        with jax.default_matmul_precision("highest"):
            return kda.gdn_chunked(*ops, chunk=chunk)

    got, got_grads = _value_and_grads(run, ops, weight)
    close(got, want, 1e-5, "o")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        assert a.shape == b.shape, name
        close(a, b, 1e-4, f"d{name}")


def test_each_row_of_the_batch_alone_equals_its_place_in_the_batch():
    heads, key_heads, d, seq, chunk = 4, 2, 8, 32, 16
    ops = operands(seq, heads, key_heads, d, batch=3, seed=2)
    weight = jax.random.normal(jax.random.PRNGKey(1), (3, seq, heads * d))
    run = lambda *ops: kda.kda_scan(*ops, chunk=chunk, interpret=True)
    whole, whole_grads = _value_and_grads(run, ops, weight)
    for row in range(3):
        one = tuple(u[row:row + 1] for u in ops)
        got, grads = _value_and_grads(run, one, weight[row:row + 1])
        np.testing.assert_array_equal(got, whole[row:row + 1])
        for name, a, b in zip(NAMES, grads, whole_grads):
            np.testing.assert_array_equal(a, b[row:row + 1], err_msg=name)


@pytest.mark.parametrize("interpret", [None, True], ids=["plain", "kernels"])
def test_the_channel_form_over_the_same_inputs_widened_agrees(interpret):
    """g a head's scalar on every key channel, q and k once a value head:
    `diag(a) = a I`, one rule in two forms, values and gradients (g's and
    the key heads' summed back from the widened operands')."""
    heads, key_heads, d, seq, chunk = 4, 2, 16, 40, 16  # a padded tail
    group = heads // key_heads
    ops = operands(seq, heads, key_heads, d, seed=4)
    weight = jax.random.normal(jax.random.PRNGKey(3), (2, seq, heads * d))
    scan = lambda *ops: kda.kda_scan(*ops, chunk=chunk, interpret=interpret)

    def widened(q, k, v, g, b):
        rep = lambda u: jnp.repeat(
            u.reshape(*u.shape[:2], key_heads, d), group, axis=2
        ).reshape(*u.shape[:2], heads * d)
        return scan(rep(q), rep(k), v, jnp.repeat(g, d, axis=-1), b)

    with jax.default_matmul_precision("highest"):
        got, got_grads = _value_and_grads(scan, ops, weight)
        want, want_grads = _value_and_grads(widened, ops, weight)
    close(got, want, 1e-5, "o")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        close(a, b, 2e-4, f"d{name}")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_kernels_match_the_chunked_form(dtype, tol):
    """Sixteen value heads over eight key heads: two grid steps of eight
    heads over four; a padded tail."""
    heads, key_heads, d, seq, chunk = 16, 8, 16, 40, 16
    ops = operands(seq, heads, key_heads, d, batch=1, seed=3, dtype=dtype)
    weight = jax.random.normal(jax.random.PRNGKey(5), (1, seq, heads * d))
    # the CPU has no bfloat16 x bfloat16 = float32 product of the plain
    # form's shapes: it reads the same rounded values in float32
    wide = tuple(u.astype(jnp.float32) for u in ops)
    want, want_grads = _value_and_grads(
        lambda *ops: kda.kda_scan(*ops, chunk=chunk), wide, weight
    )
    got, got_grads = _value_and_grads(
        lambda *ops: kda.kda_scan(*ops, chunk=chunk, interpret=True), ops, weight
    )
    assert got.dtype == dtype and want.shape == got.shape
    close(got, want, tol, "o")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        assert a.dtype == ops[NAMES.index(name)].dtype, name
        close(a, b, tol, f"d{name}")
    names = pallas_kernel_names(
        jax.grad(lambda *ops: jnp.sum(
            kda.kda_scan(*ops, chunk=chunk, interpret=True).astype(jnp.float32)
        )), *ops
    )
    assert set(names) == {"gdn_fwd", "gdn_bwd"}


def test_a_decay_past_e_minus_100_inside_a_chunk_stays_finite_and_right():
    """Only differences of the running decay that are never positive go
    through `exp`: no clip, no overflow."""
    heads, key_heads, d, seq, chunk = 2, 1, 8, 32, 32
    ops = operands(seq, heads, key_heads, d, decay=4.0, batch=1)
    assert float(jnp.min(jnp.sum(ops[3], axis=1))) < -100.0
    want = by_definition(ops, d)
    for interpret in (None, True):
        with jax.default_matmul_precision("highest"):
            got = kda.kda_scan(*ops, chunk=chunk, interpret=interpret)
            grads = jax.grad(
                lambda *ops: jnp.sum(
                    kda.kda_scan(*ops, chunk=chunk, interpret=interpret)
                ), EVERY,
            )(*ops)
        close(got, want, 1e-5, "o")
        assert all(bool(jnp.isfinite(u).all()) for u in grads)


def test_flash_policy_keeps_the_kernels_results_and_drops_the_forward():
    heads, key_heads, d, seq, chunk = 4, 2, 16, 32, 16
    ops = operands(seq, heads, key_heads, d, batch=1)
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
    assert names.count("gdn_fwd") == 1 and names.count("gdn_bwd") == 1


def test_the_form_is_read_from_the_decays_shape_and_needs_no_kernel_off_the_chip():
    heads, key_heads, d, seq, chunk = 4, 2, 16, 32, 16
    ops = operands(seq, heads, key_heads, d, batch=1)
    scan = lambda *ops: jnp.sum(kda.kda_scan(*ops, chunk=chunk))
    assert pallas_kernel_names(jax.grad(scan), *ops) == []
    # the same operands widened by hand take the channel form's kernels
    rep = lambda u: jnp.repeat(u.reshape(1, seq, key_heads, d), 2, axis=2).reshape(
        1, seq, heads * d
    )
    q, k, v, g, b = ops
    names = pallas_kernel_names(
        lambda *ops: kda.kda_scan(*ops, chunk=chunk, interpret=True),
        rep(q), rep(k), v, jnp.repeat(g, d, axis=-1), b,
    )
    assert names == ["kda_fwd"]


def test_schedule():
    sched = kda.kda_schedule(
        8192, heads=32, key_heads=16, head_dim=128, chunk=64, batch=2
    )
    assert sched["form"] == "head" and sched["chunks"] == 128
    assert sched["grid"] == (2, 4, 128)
    assert (sched["heads_a_step"], sched["key_heads_a_step"]) == (8, 4)
    # o and the states entering each chunk, bfloat16, a value head each
    assert sched["saved_bytes_a_call"] == 2 * (8192 * 4096 + 128 * 128 * 4096) * 2
    assert sched["state_scratch_bytes"] == 128 * 1024 * 4
    assert "sub_block" not in sched
    # equal heads: eight a step; three value heads a key head: whole groups
    assert kda._group_heads(32, 32) == (8, 8)
    assert kda._group_heads(12, 4) == (2, 6)


def test_shapes_are_refused_with_their_numbers():
    heads, d = 4, 8
    q, k, v, g, b = operands(16, heads, 2, d)
    with pytest.raises(ValueError, match="key heads that divide the value heads"):
        kda.kda_scan(  # three key heads under four value heads
            jnp.zeros((2, 16, 3 * d)), jnp.zeros((2, 16, 3 * d)), v, g, b, chunk=16
        )
    with pytest.raises(ValueError, match="key heads that divide"):
        kda.kda_scan(q, k[..., :d], v, g, b, chunk=16)  # q and k differ


def test_on_a_mesh_of_several_devices_the_plain_form_runs_and_gives_the_same():
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    heads, key_heads, d, seq, chunk = 4, 2, 16, 32, 16
    ops = operands(seq, heads, key_heads, d, batch=2, seed=5)
    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    assert not kda._head_form_kernels(ops[2].astype(jnp.bfloat16), mesh)
    run = lambda mesh: jax.jit(jax.value_and_grad(
        lambda *ops: jnp.sum(kda.kda_scan(*ops, chunk=chunk, mesh=mesh) ** 2),
        EVERY,
    ))(*ops)
    want, want_grads = run(None)
    got, got_grads = run(mesh)
    close(got, want, 1e-6, "loss")
    for name, a, b in zip(NAMES, got_grads, want_grads):
        close(a, b, 1e-5, f"d{name}")
