"""The long-context attention schedule (ISSUE 3) and the fused one-pass
backward (ISSUE 7): compacted causal grid, lane-packed lse, shared-delta
backward, fused dq/dkv kernel, and internal padding — interpret-mode
parity against the dense reference (and against the two-kernel backward)
plus static-schedule regression gates (grid-step count, lse HBM bytes,
backward HBM-byte halving, fused VMEM gating)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import flash
from kubeflow_tpu.ops.attention import dense_attention
from kubeflow_tpu.ops.flash import (
    _LANES,
    _bwd_fused,
    _diag_bands,
    _diag_plan,
    _flash_bwd_kernels,
    _flash_delta_impl,
    _flash_fwd_impl,
    _grid_steps,
    flash_attention,
    flash_schedule,
)
from kubeflow_tpu.testing.hlo import pallas_kernel_names


def _qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


def _grads(attn, q, k, v):
    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * jnp.cos(o.astype(jnp.float32)))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# -- compacted causal grid --------------------------------------------------


@pytest.mark.parametrize("s,block", [(512, 128), (384, 128), (256, 64)])
def test_compact_causal_forward_and_grads_match_dense(s, block):
    """Square causal blocks run the compact triangular grid (asserted via
    the schedule) and must match dense numerics fwd + bwd."""
    sched = flash_schedule(s, s, block_q=block, block_k=block, causal=True)
    assert sched["compact"], sched
    assert sched["grid_steps"] < sched["rect_grid_steps"]

    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, 2, 32)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    )
    np.testing.assert_allclose(
        attn(q, k, v), dense_attention(q, k, v, causal=True),
        atol=2e-5, rtol=2e-5,
    )
    got = _grads(attn, q, k, v)
    want = _grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


def test_uneven_blocks_fall_back_to_rectangular():
    """bq != bk cannot compact (block rows aren't triangular); the
    rectangular fallback with clamped DMAs must still match dense."""
    sched = flash_schedule(256, 256, block_q=64, block_k=128, causal=True)
    assert not sched["compact"]
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 256, 2, 16)
    out = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        out, dense_attention(q, k, v, causal=True), atol=2e-5, rtol=2e-5
    )


def test_noncausal_is_rectangular_and_matches():
    sched = flash_schedule(256, 256, block_q=128, block_k=128, causal=False)
    assert not sched["compact"]
    assert sched["grid_steps"] == sched["rect_grid_steps"]
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 256, 2, 16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=False, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        attn(q, k, v), dense_attention(q, k, v, causal=False),
        atol=2e-5, rtol=2e-5,
    )
    got = _grads(attn, q, k, v)
    want = _grads(
        lambda q, k, v: dense_attention(q, k, v, causal=False), q, k, v
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


def test_grid_step_regression_causal_half_the_steps():
    """The acceptance gate: at S=4096 the compacted causal grid must run
    <= 0.6x the rectangular grid's steps (the triangular count
    nq(nq+1)/2 approaches half the rectangle as nq grows; 256-wide
    blocks give nq=16 -> 136/256 = 0.53)."""
    sched = flash_schedule(4096, 4096, block_q=256, block_k=256, causal=True)
    assert sched["compact"]
    ratio = sched["grid_steps"] / sched["rect_grid_steps"]
    assert ratio <= 0.6, sched
    # And with the default (1024) blocks compaction still engages.
    default = flash_schedule(4096, 4096, causal=True)
    assert default["compact"]
    assert default["grid_steps"] < default["rect_grid_steps"]
    # The schedule helper is the SAME accounting the impl builds its
    # grid from — pin the equivalence so the test can't drift from the
    # kernel.
    steps, rect, compact = _grid_steps(True, 4096, 4096, 256, 256)
    assert (steps, rect, compact) == (
        sched["grid_steps"], sched["rect_grid_steps"], True,
    )


# -- lane-packed lse --------------------------------------------------------


def test_lse_packed_layout_cuts_hbm_bytes_128x():
    """The packed [BH, S/128, 128] lse layout must be exactly 128x
    smaller than the lane-replicated [BH, S, 128] buffer, and the fwd
    impl must actually emit it (asserted from the returned shape, which
    is the kernel's out_shape/BlockSpec shape)."""
    sched = flash_schedule(1024, 1024, causal=True)
    assert sched["lse_packed"]
    assert sched["lse_replicated_bytes"] == 128 * sched["lse_bytes"]

    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 1024, 2, 16)
    qf = q.transpose(0, 2, 1, 3).reshape(2, 1024, 16)
    _, lse = _flash_fwd_impl(
        qf, qf, qf, True, 1024, 1024, True, None, True
    )
    assert lse.shape == (2, 1024 // _LANES, _LANES)

    # Un-lane-aligned blocks cannot pack; the replicated fallback stays.
    sched_small = flash_schedule(96, 96, block_q=32, block_k=32)
    assert not sched_small["lse_packed"]


@pytest.mark.parametrize(
    "s,block,packed",
    [
        (4096, 1024, True),  # (8, 128) packed tile
        (4096, 2048, True),
        (512, 512, True),  # block spans the array: any 128-multiple
        (4096, 512, False),  # (4, 128): second-minor dim not 8-aligned
        (4096, 128, False),
        (384, 128, False),
    ],
)
def test_lse_packs_only_where_the_tpu_lowering_accepts_the_block(
    s, block, packed
):
    """The packed (1, bq/128, 128) lse block is a legal TPU tile only
    when bq/128 is a sublane multiple or the block is the whole
    sequence. Interpret mode accepts every 128-multiple, so this is
    pinned here and compiled for the chip in test_chip_compile.py."""
    sched = flash_schedule(s, s, block_q=block, block_k=block)
    assert sched["lse_packed"] == packed, sched


def test_packed_lse_values_match_dense_logsumexp():
    """The packed tiles must hold the true per-row softmax statistics:
    unpacked lse == dense log-sum-exp of the scaled causal scores."""
    b, s, h, d = 1, 256, 1, 32
    q, k, v = _qkv(jax.random.PRNGKey(4), b, s, h, d)
    _, lse = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True,
        return_lse=True,
    )
    assert lse.shape == (b, h, s)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    want = jax.scipy.special.logsumexp(scores.astype(jnp.float32), axis=-1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want), atol=2e-5, rtol=2e-5
    )


# -- shared-delta backward --------------------------------------------------


def test_shared_delta_precompute_matches_rowsum():
    """The delta precompute kernel must emit rowsum(dO * O) in the lse
    layout — the single value both backward kernels consume."""
    bh, s, d = 2, 256, 16
    o = jax.random.normal(jax.random.PRNGKey(5), (bh, s, d))
    do = jax.random.normal(jax.random.PRNGKey(6), (bh, s, d))
    want = jnp.sum(do * o, axis=-1)

    packed = _flash_delta_impl(o, do, 128, True, True)
    assert packed.shape == (bh, s // _LANES, _LANES)
    np.testing.assert_allclose(
        packed.reshape(bh, s), want, atol=1e-5, rtol=1e-5
    )

    replicated = _flash_delta_impl(o, do, 64, True, False)
    assert replicated.shape == (bh, s, _LANES)
    np.testing.assert_allclose(
        replicated[:, :, 0], want, atol=1e-5, rtol=1e-5
    )


# -- fused one-pass dq/dkv backward (ISSUE 7) -------------------------------


def _count(names, prefix):
    return sum(n.startswith(prefix) for n in names)


def _bwd_kernel_counts(attn, q, k, v):
    """(fused, two_pass_dq, two_pass_dkv) kernel-call counts in the
    grad jaxpr — the same mechanical engagement check the attention
    bench gates on, read from the pallas_call equations' names."""
    names = pallas_kernel_names(
        jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2),
        ),
        q, k, v,
    )
    return (
        _count(names, "flash_bwd_fused"),
        _count(names, "flash_dq_"),
        _count(names, "flash_dkv_"),
    )


@pytest.mark.parametrize(
    "s,block,packed",
    [(512, 128, False), (256, 64, False), (256, 256, True)],
)
def test_fused_bwd_engages_and_matches_dense(s, block, packed):
    """The compact causal grid now runs ONE backward kernel: the
    schedule reports it, the grad jaxpr contains exactly the fused
    kernel (neither two-pass kernel), and grads match dense — in both
    the lane-packed and the replicated lse layout."""
    sched = flash_schedule(
        s, s, block_q=block, block_k=block, causal=True,
        head_dim=32, dtype_bytes=4,
    )
    assert sched["bwd_fused"], sched
    assert sched["lse_packed"] == packed
    assert sched["bwd_total_grid_steps"] == sched["grid_steps"]

    q, k, v = _qkv(jax.random.PRNGKey(10), 2, s, 2, 32)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    )
    fused, dq2, dkv2 = _bwd_kernel_counts(attn, q, k, v)
    assert fused == 1 and dq2 == 0 and dkv2 == 0, (fused, dq2, dkv2)

    got = _grads(attn, q, k, v)
    want = _grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


@pytest.mark.parametrize("packed", [True, False])
def test_fused_matches_two_kernel_path(packed):
    """Pin fused == two-pass on identical (lse, delta) inputs: the
    fusion must be a pure schedule change, not a numerics change. Both
    lse layouts (packed 128-blocks, replicated 64-blocks)."""
    bh, s, d = 2, 256, 32
    block = 128 if packed else 64
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, do = (
        jax.random.normal(kx, (bh, s, d)) for kx in keys
    )
    o, lse = _flash_fwd_impl(q, k, v, True, block, block, True, None, packed)
    delta = _flash_delta_impl(o, do, block, True, packed)
    fused = _flash_bwd_kernels(
        q, k, v, do, lse, delta, True, block, block, True, None, packed,
        True,
    )
    two = _flash_bwd_kernels(
        q, k, v, do, lse, delta, True, block, block, True, None, packed,
        False,
    )
    for f, t, name in zip(fused, two, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            f, t, atol=1e-5, rtol=1e-5, err_msg=f"{name} fused!=two-pass"
        )


def test_noncausal_and_uneven_blocks_stay_two_pass():
    """The rectangular fallback is preserved unchanged: non-causal and
    uneven-block configurations must not fuse (schedule AND traced
    program), and forcing fused there is a loud error."""
    sched = flash_schedule(256, 256, causal=False, head_dim=16,
                           dtype_bytes=4)
    assert not sched["bwd_fused"]
    assert sched["bwd_total_grid_steps"] == 2 * sched["grid_steps"]
    assert not flash_schedule(
        256, 256, block_q=64, block_k=128, causal=True
    )["bwd_fused"]

    q, k, v = _qkv(jax.random.PRNGKey(12), 1, 256, 2, 16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=False, block_q=128, block_k=128, interpret=True
    )
    fused, dq2, dkv2 = _bwd_kernel_counts(attn, q, k, v)
    assert fused == 0 and dq2 == 1 and dkv2 == 1, (fused, dq2, dkv2)

    qf = q.transpose(0, 2, 1, 3).reshape(2, 256, 16)
    o, lse = _flash_fwd_impl(qf, qf, qf, False, 128, 128, True, None, True)
    delta = _flash_delta_impl(o, jnp.ones_like(o), 128, True, True)
    with pytest.raises(ValueError, match="compact causal grid"):
        _flash_bwd_kernels(
            qf, qf, qf, jnp.ones_like(o), lse, delta, False, 128, 128,
            True, None, True, True,
        )


def test_fused_vmem_budget_gates_engagement(monkeypatch):
    """The dq ring costs S·d·4 bytes of VMEM, so fusion must fall back
    past the budget (32k × d=128 is a 16 MiB ring on a ~16 MiB core);
    the budget is all that decides on the compact grid."""
    assert flash_schedule(16384, 16384)["bwd_fused"]
    big = flash_schedule(32768, 32768)
    assert big["compact"] and not big["bwd_fused"]
    assert big["bwd_fused_vmem_bytes"] > 12 * 2**20
    # The impl-side predicate is the same function the schedule reports.
    assert _bwd_fused(True, 16384, 16384, 1024, 1024, 128, 2, True)
    assert not _bwd_fused(True, 32768, 32768, 1024, 1024, 128, 2, True)

    # Forcing fused=True past the budget is a LOUD error (the dq ring
    # would exhaust core VMEM with an opaque Mosaic failure otherwise).
    z = lambda shape: jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match="over-budget"):
        _flash_bwd_kernels(
            z((1, 32768, 128)), z((1, 32768, 128)), z((1, 32768, 128)),
            z((1, 32768, 128)), z((1, 256, 128)), z((1, 256, 128)),
            True, 1024, 1024, True, None, True, True,
        )

    monkeypatch.setattr(flash, "_FUSED_VMEM_BUDGET", 0)
    assert not flash_schedule(16384, 16384)["bwd_fused"]
    assert not _bwd_fused(True, 16384, 16384, 1024, 1024, 128, 2, True)


def test_bwd_hbm_byte_model_fused_halves_two_pass():
    """The acceptance gate (ISSUE 7): at the 16k flagship shape the
    fused backward must model ~half the two-pass HBM bytes (the
    per-step K/V re-streaming is gone; residents and output writes keep
    the ratio a little above 0.5), monotonically approaching 1/2 as the
    triangle deepens."""
    ratios = {}
    for s in (2048, 4096, 8192, 16384):
        sc = flash_schedule(s, s)
        assert sc["bwd_hbm_bytes_fused"] < sc["bwd_hbm_bytes_two_pass"]
        ratios[s] = sc["bwd_hbm_bytes_fused"] / sc["bwd_hbm_bytes_two_pass"]
    assert ratios[16384] <= 0.6, ratios
    assert ratios[8192] <= 0.6, ratios
    assert all(
        ratios[a] >= ratios[b]
        for a, b in ((2048, 4096), (4096, 8192), (8192, 16384))
    ), ratios
    # The chosen-path figure follows the fused flag.
    sc = flash_schedule(16384, 16384)
    assert sc["bwd_fused"] and sc["bwd_hbm_bytes"] == sc["bwd_hbm_bytes_fused"]


def test_fused_under_remat_flash_policy_never_reruns_fwd():
    """remat_policy="flash" × fused backward: a block checkpoint that
    pins the kernel's named (out, lse) residuals must still dead-code
    the forward kernel out of the backward — the fused kernel must not
    have changed the residual set. Asserted from the grad jaxpr: the
    checkpointed grad traces the forward kernel exactly as often as the
    un-checkpointed grad, and runs the fused backward."""
    s, block = 256, 128
    q, k, v = _qkv(jax.random.PRNGKey(13), 1, s, 2, 32)

    def attn(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=True,
        )

    def loss_plain(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    loss_ckpt = jax.checkpoint(
        loss_plain,
        policy=jax.checkpoint_policies.save_only_these_names(
            flash.CHECKPOINT_OUT_NAME, flash.CHECKPOINT_LSE_NAME
        ),
    )
    grads = lambda f: jax.grad(f, argnums=(0, 1, 2))
    plain = pallas_kernel_names(grads(loss_plain), q, k, v)
    ckpt = pallas_kernel_names(grads(loss_ckpt), q, k, v)
    assert _count(plain, "flash_fwd_") >= 1
    assert (
        _count(ckpt, "flash_fwd_") == _count(plain, "flash_fwd_")
    ), "remat_policy='flash' re-runs the flash forward in the backward"
    assert _count(ckpt, "flash_bwd_fused") == 1
    assert _count(ckpt, "flash_dq_") == 0
    # And the checkpointed grads equal the plain ones.
    for a, b, name in zip(
        grads(loss_ckpt)(q, k, v), grads(loss_plain)(q, k, v), "qkv"
    ):
        np.testing.assert_allclose(
            a, b, atol=1e-5, rtol=1e-5, err_msg=f"d{name} mismatch"
        )


def test_fused_handles_ragged_padded_tail():
    """Ragged S rides the fused kernel too: 321 pads to 384 (compact,
    square blocks, kv_len tail mask) and grads must match dense."""
    s = 321
    sched = flash_schedule(s, s, block_q=128, block_k=128, head_dim=16,
                           dtype_bytes=4)
    assert sched["padded_seq_q"] == 384 and sched["bwd_fused"], sched

    q, k, v = _qkv(jax.random.PRNGKey(14), 1, s, 2, 16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    )
    fused, dq2, dkv2 = _bwd_kernel_counts(attn, q, k, v)
    assert fused == 1 and dq2 == 0 and dkv2 == 0
    got = _grads(attn, q, k, v)
    want = _grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


# -- internal padding (ragged sequence lengths) -----------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [100, 321, 1025])
def test_ragged_sequences_pad_and_match_dense(causal, s):
    """Lengths with no 8-aligned divisor (previously a hard error →
    silent dense fallback at the model layer) pad to the next lane
    multiple, mask the tail, and match dense numerics fwd + bwd. The
    non-causal case is the one the tail mask exists for: without it the
    zero-padded keys would soak up softmax mass."""
    sched = flash_schedule(s, s, causal=causal)
    assert sched["padded_seq_q"] % _LANES == 0
    assert sched["padded_seq_q"] >= s

    q, k, v = _qkv(jax.random.PRNGKey(7), 1, s, 2, 16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True
    )
    out = attn(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(
        out, dense_attention(q, k, v, causal=causal), atol=2e-4, rtol=2e-4
    )
    got = _grads(attn, q, k, v)
    want = _grads(
        lambda q, k, v: dense_attention(q, k, v, causal=causal), q, k, v
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch (s={s})"
        )


def test_odd_head_counts():
    """Heads are flattened into the grid's bh dimension — odd counts must
    work (they exercise bh rows that share nothing 2-power-aligned)."""
    for h in (3, 5):
        q, k, v = _qkv(jax.random.PRNGKey(8), 2, 128, h, 16)
        out = flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        )
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bf16_compact_packed_path():
    q, k, v = _qkv(jax.random.PRNGKey(9), 1, 256, 2, 32, jnp.bfloat16)
    out = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    )
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


# -- the diagonal inside a block (ISSUE 29) ----------------------------------


@pytest.fixture
def mask_calls(monkeypatch):
    """Which masks a kernel body builds while it is traced: `_band_mask`
    is the banded body on the diagonal, `_causal_mask` / `_kv_tail_mask`
    the body that masks by position. Counted at trace time, so a case
    has to bring a shape no other test of this process has traced."""
    calls = []

    def counted(name):
        real = getattr(flash, name)

        def mask(*args):
            calls.append(name)
            return real(*args)

        return mask

    for name in ("_band_mask", "_causal_mask", "_kv_tail_mask"):
        monkeypatch.setattr(flash, name, counted(name))
    return calls


@pytest.mark.parametrize(
    "bq,bands",
    [(2048, 2), (1024, 2), (512, 2), (768, 2), (256, 2), (384, 1),
     (128, 1), (64, 1), (1000, 1)],
)
def test_bands_come_from_the_block_size_alone(bq, bands):
    """Two bands where a band's rows are whole lane tiles, else one; band
    r's keys end with its own rows, so n(n+1)/2 of the n² sub-tiles are
    computed and the static mask keeps column c of row a where
    c <= a + r·t."""
    assert _diag_bands(bq) == bands
    for n in (bands, 4):  # the plan itself takes any number of bands
        t = bq // n
        plan = _diag_plan(bq, n)
        assert plan == [
            (slice(r * t, (r + 1) * t), slice(0, (r + 1) * t))
            for r in range(n)
        ]
        pairs = sum(
            (rows.stop - rows.start) * (cols.stop - cols.start)
            for rows, cols in plan
        )
        assert pairs == t * t * n * (n + 1) // 2
    last = np.asarray(flash._band_mask(jnp.zeros((8, 24))))
    want = np.where(np.arange(8)[:, None] + 16 >= np.arange(24), 0.0, -np.inf)
    np.testing.assert_array_equal(last, want)


@pytest.mark.parametrize("two_pass", [False, True])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize(
    "s,block,bands", [(256, 128, 1), (512, 256, 2), (1024, 512, 4)]
)
def test_diagonal_and_interior_bodies_match_dense(
    s, block, bands, group, two_pass, monkeypatch, mask_calls
):
    """Two diagonal steps and one below the diagonal a grid row, with
    the diagonal blocks cut into 1, 2 and 4 bands, equal and grouped
    heads, through the fused backward and (with no VMEM budget for it)
    the two-pass kernels: forward and all three gradients against dense
    attention, and only the static mask is ever built. The rule gives
    1 and 2 bands; 4, which the chip timed no faster, is put in its
    place here so the plan stays held for any number."""
    if two_pass:
        monkeypatch.setattr(flash, "_FUSED_VMEM_BUDGET", 0)
    if bands == 4:
        assert _diag_bands(block) == 2
        monkeypatch.setattr(flash, "_DIAG_BANDS", (4, 2, 1))
    d = 24 + 8 * two_pass  # a shape of this case alone (see `mask_calls`)
    sched = flash_schedule(
        s, s, block_q=block, block_k=block, head_dim=d, dtype_bytes=4
    )
    assert (sched["diag_steps"], sched["interior_steps"]) == (2, 1)
    assert sched["diag_tile"] == block // bands
    assert sched["bwd_fused"] == (not two_pass)

    ks = jax.random.split(jax.random.PRNGKey(s + group), 3)
    q = jax.random.normal(ks[0], (1, s, 4, d))
    k = jax.random.normal(ks[1], (1, s, 4 // group, d))
    v = jax.random.normal(ks[2], (1, s, 4 // group, d))
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    )
    dense = lambda q, k, v: dense_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        causal=True,
    )
    fused, dq2, dkv2 = _bwd_kernel_counts(attn, q, k, v)
    assert (fused, dq2, dkv2) == ((0, 1, 1) if two_pass else (1, 0, 0))
    np.testing.assert_allclose(
        attn(q, k, v), dense(q, k, v), atol=2e-5, rtol=2e-5
    )
    for g, w, name in zip(
        _grads(attn, q, k, v), _grads(dense, q, k, v), "qkv"
    ):
        assert g.shape == w.shape
        np.testing.assert_allclose(
            g, w, atol=1e-4, rtol=1e-4, err_msg=f"d{name} mismatch"
        )
    assert set(mask_calls) == {"_band_mask"}, mask_calls


@pytest.mark.parametrize(
    "s,causal,masks",
    [
        # 502 = 2 x 251 has no 8-aligned divisor: pads to 512, two
        # 256-blocks, the tail masked through kv_len.
        (502, True, {"_causal_mask", "_kv_tail_mask"}),
        (512, False, set()),  # rectangular grid, nothing masked
    ],
)
def test_ragged_and_noncausal_keep_the_body_that_masks_by_position(
    s, causal, masks, mask_calls
):
    """A padded tail can mask keys the triangle does not, and the
    rectangular grid has no diagonal of square blocks: at a block size
    whose diagonal would be cut in two, both run the whole block under
    the position mask, and still match dense."""
    sched = flash_schedule(s, s, block_q=256, block_k=256, causal=causal)
    assert sched["block_q"] == 256 and sched["padded_seq_q"] == 512
    assert sched["diag_steps"] == sched["interior_steps"] == 0
    assert sched["diag_tile"] == 0

    q, k, v = _qkv(jax.random.PRNGKey(s), 1, s, 2, 40)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=256, block_k=256, interpret=True
    )
    dense = lambda q, k, v: dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        attn(q, k, v), dense(q, k, v), atol=2e-5, rtol=2e-5
    )
    for g, w, name in zip(
        _grads(attn, q, k, v), _grads(dense, q, k, v), "qkv"
    ):
        np.testing.assert_allclose(
            g, w, atol=1e-4, rtol=1e-4, err_msg=f"d{name} mismatch"
        )
    assert set(mask_calls) == masks, mask_calls


@pytest.mark.parametrize(
    "s,diag,interior,pairs,pairs_whole_blocks",
    [(2048, 2, 1, 1.249, 1.50), (8192, 8, 28, 1.062, 1.125),
     (16384, 16, 120, 1.031, 1.0625)],
)
def test_schedule_counts_the_steps_on_and_below_the_diagonal(
    s, diag, interior, pairs, pairs_whole_blocks, monkeypatch
):
    """The engagement counter is static, like the schedule: at the
    benchmark cells' shapes (1024-blocks, two bands of 512) a grid row
    has `diag` banded steps and `interior` mask-free ones, together the
    whole grid, and computes `pairs` times the pairs attention needs
    where whole blocks computed `pairs_whole_blocks` times."""
    sched = flash_schedule(s, s)
    assert sched["diag_steps"] == diag
    assert sched["interior_steps"] == interior
    assert sched["diag_tile"] == 512
    assert diag + interior == sched["grid_steps"]
    assert sched["computed_pairs_over_needed"] == pytest.approx(
        pairs, abs=1e-3
    )
    exact = (interior * 1024**2 + diag * 3 * 512**2) / (s * (s + 1) // 2)
    assert sched["computed_pairs_over_needed"] == exact
    monkeypatch.setattr(flash, "_bands", lambda compact, bq, kv_len: 0)
    whole = flash_schedule(s, s)
    assert whole["diag_steps"] == whole["interior_steps"] == 0
    assert whole["computed_pairs_over_needed"] == pytest.approx(
        pairs_whole_blocks, abs=1e-3
    )


def test_schedule_pairs_where_the_bodies_do_not_engage():
    """Non-causal: every pair is needed and computed. Uneven blocks: the
    causal rectangle computes the blocks it does not predicate off. A
    padded length: the compact grid over the padded blocks, against the
    pairs of the true length."""
    assert flash_schedule(512, 512, causal=False)[
        "computed_pairs_over_needed"
    ] == 1.0
    uneven = flash_schedule(256, 256, block_q=64, block_k=128)
    assert not uneven["compact"] and uneven["diag_steps"] == 0
    # rows 0-63 and 64-127 see one 128-block of keys, the rest two.
    assert uneven["computed_pairs_over_needed"] == (
        (2 * 1 + 2 * 2) * 64 * 128 / (256 * 257 // 2)
    )
    ragged = flash_schedule(2001, 2001)
    assert ragged["padded_seq_q"] == 2048 and ragged["diag_steps"] == 0
    assert ragged["computed_pairs_over_needed"] == (
        3 * 1024**2 / (2001 * 2002 // 2)
    )


# -- where the kernels read a head (ISSUE 31) ---------------------------------


def _through(layout, monkeypatch, q, k, v, **kw):
    """(o, lse, dq, dk, dv) of `flash_attention` with the heads handed to
    the kernels in `layout`, whatever the head size would select."""
    monkeypatch.setattr(flash, "_head_layout", lambda d: layout)

    def loss(q, k, v):
        o, lse = flash_attention(
            q, k, v, interpret=True, return_lse=True, **kw
        )
        o32 = o.astype(jnp.float32)
        return jnp.sum(o32 * jnp.cos(o32)), (o, lse)

    grads, (o, lse) = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (o, lse, *grads)


@pytest.mark.parametrize(
    "name,s,h,hkv,d,block,causal,budget,kernels",
    [
        # equal heads, fused backward, replicated lse (64-row blocks)
        ("equal", 256, 4, 4, 16, 64, True, None, ["flash_bwd_fused"]),
        # zaya's grouping, 8 query heads over 2, packed lse (one block)
        ("grouped", 256, 8, 2, 16, 256, True, None, ["flash_bwd_fused"]),
        # a ragged length: 321 pads to 384, the tail masked by position
        ("ragged", 321, 4, 2, 16, 128, True, None, ["flash_bwd_fused"]),
        # no VMEM for the dq ring: the compact two-pass kernels
        ("two_pass", 384, 4, 2, 24, 128, True, 0,
         ["flash_dq_compact", "flash_dkv_compact"]),
        # not causal: the rectangular grid, forward and backward
        ("rect", 256, 6, 3, 16, 128, False, None,
         ["flash_dq_rect", "flash_dkv_rect"]),
        # whole lanes, the size both layouts are legal at on the chip
        ("lanes", 128, 4, 2, 128, 128, True, None, ["flash_bwd_fused"]),
    ],
)
def test_seq_major_is_bit_identical_to_head_major_and_matches_dense(
    name, s, h, hkv, d, block, causal, budget, kernels, monkeypatch
):
    """The same blocks in the same order into the same bodies: output,
    lse and all three gradients of the kernels reading [B, S, H·d] equal
    those of the kernels reading the transposed [B·H, S, d] bit for bit,
    and both match dense attention."""
    if budget is not None:
        monkeypatch.setattr(flash, "_FUSED_VMEM_BUDGET", budget)
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(ks[0], (2, s, h, d))
    k = jax.random.normal(ks[1], (2, s, hkv, d))
    v = jax.random.normal(ks[2], (2, s, hkv, d))
    kw = dict(causal=causal, block_q=block, block_k=block)
    seq = _through("seq_major", monkeypatch, q, k, v, **kw)
    head = _through("head_major", monkeypatch, q, k, v, **kw)
    for a, b, what in zip(seq, head, ("o", "lse", "dq", "dk", "dv")):
        assert a.shape == b.shape and a.dtype == b.dtype, what
        np.testing.assert_array_equal(a, b, err_msg=f"{name}: {what}")

    names = pallas_kernel_names(
        jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=True, **kw
            ).sum(),
            argnums=(0, 1, 2),
        ),
        q, k, v,
    )
    assert names[2:] == kernels, names

    group = h // hkv
    rep = lambda x: jnp.repeat(x, group, axis=2)
    dense = lambda q, k, v: dense_attention(q, rep(k), rep(v), causal=causal)
    np.testing.assert_allclose(seq[0], dense(q, k, v), atol=2e-4, rtol=2e-4)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) / np.sqrt(d)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    np.testing.assert_allclose(
        seq[1], jax.scipy.special.logsumexp(scores, axis=-1),
        atol=2e-4, rtol=2e-4,
    )
    want = _grads(dense, q, k, v)
    got = _grads(
        lambda q, k, v: flash_attention(q, k, v, interpret=True, **kw),
        q, k, v,
    )
    for g, w, what in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g, w, atol=5e-4, rtol=5e-4, err_msg=f"{name}: d{what}"
        )


def _transposes_outside_kernels(jaxpr) -> int:
    """`transpose` equations of a program, its kernels' bodies apart (a
    packed lse is packed by (128, 128) transposes in registers)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        n += eqn.primitive.name == "transpose"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _transposes_outside_kernels(sub)
    return n


@pytest.mark.parametrize(
    "d,layout,transposes",
    [(64, "head_major", 8), (128, "seq_major", 0), (256, "seq_major", 0)],
)
def test_layout_comes_from_the_head_size_alone(d, layout, transposes):
    """A head is a legal column block of [B, S, H·d] where d is whole
    lanes: there the kernels read the projections' arrays and
    `flash_attention` traces no transpose, forward or backward; any
    other d keeps the four transposes in and the four out. The static
    counter (`flash_schedule`) says which, and the traced program agrees
    with it."""
    sched = flash_schedule(256, 256, block_q=128, block_k=128, head_dim=d)
    assert sched["layout"] == layout
    assert sched["transposes_per_call"] == transposes
    q = jax.ShapeDtypeStruct((2, 256, 4, d), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 256, 2, d), jnp.float32)
    attn = lambda q, k, v: flash_attention(
        q, k, v, block_q=128, block_k=128, interpret=True
    )
    fwd = jax.make_jaxpr(attn)(q, kv, kv)
    both = jax.make_jaxpr(
        jax.grad(lambda q, k, v: attn(q, k, v).sum(), argnums=(0, 1, 2))
    )(q, kv, kv)
    assert _transposes_outside_kernels(fwd.jaxpr) == transposes // 2
    assert _transposes_outside_kernels(both.jaxpr) == transposes


# -- a window's band (ISSUE 34) ---------------------------------------------


def _band_by_hand(s, block, window, tile):
    """(steps, diagonal, edge, interior, computed pairs, needed pairs) of
    the band's compact grid, counted pair of positions by pair."""
    nq = s // block
    steps = diag = edge = interior = computed = 0
    for i in range(nq):
        for j in range(i + 1):
            seen = [
                (a, c) for a in range(i * block, (i + 1) * block)
                for c in range(j * block, (j + 1) * block)
                if 0 <= a - c < window
            ]
            if not seen:
                continue
            steps += 1
            whole = len(seen) == block * block
            diag += i == j
            edge += i != j and not whole
            interior += whole
            if whole:
                computed += block * block
                continue
            # a band of `tile` rows against the lane tiles its rows see
            unit = 128 if block % 128 == 0 else block
            for r0 in range(i * block, (i + 1) * block, tile):
                cols = [c for a, c in seen if r0 <= a < r0 + tile]
                if cols:
                    lo = min(cols) // unit * unit
                    hi = -(-(max(cols) + 1) // unit) * unit
                    computed += tile * (hi - lo)
    needed = sum(min(a + 1, window) for a in range(s))
    return steps, diag, edge, interior, computed, needed


@pytest.mark.parametrize("s, block, window, tile", [
    (64, 16, 16, 16), (64, 16, 5, 16), (64, 16, 24, 16), (64, 16, 33, 16),
    (64, 16, 1, 16),
    (512, 256, 128, 128),   # bands of 128 in blocks of 256
    (1024, 256, 300, 128),  # diagonal, an interior block, an edge
    (1024, 512, 600, 128),  # the far edge crosses two distances
])
def test_the_schedules_band_fields_match_a_count_by_hand(s, block, window, tile):
    sched = flash_schedule(s, s, block_q=block, block_k=block, window=window)
    steps, diag, edge, interior, computed, needed = _band_by_hand(
        s, block, window, tile
    )
    assert sched["compact"] and sched["window"] == window
    assert sched["grid_steps"] == sched["band_steps"] == steps
    assert (sched["diag_steps"], sched["edge_steps"], sched["interior_steps"]) == (
        diag, edge, interior
    )
    assert sched["diag_tile"] == tile
    assert sched["computed_pairs_over_needed"] == pytest.approx(computed / needed)
    assert sched["bwd_total_grid_steps"] == steps and sched["bwd_fused"]


def test_the_band_at_the_laguna_cells_shape():
    """S = 8192 under a window of 512 in blocks of 1024: 15 of the
    triangle's 36 block pairs, bands of 128 rows, 1.25 times the band's
    pairs computed where the triangle under a mask would compute 8.9
    times; the fused backward's ring holds two blocks, not eight."""
    sched = flash_schedule(8192, 8192, window=512)
    assert (sched["block_q"], sched["band_steps"], sched["grid_steps"]) == (1024, 15, 15)
    assert (sched["diag_steps"], sched["edge_steps"], sched["interior_steps"]) == (8, 7, 0)
    assert sched["diag_tile"] == 128
    assert 1.2 < sched["computed_pairs_over_needed"] < 1.3
    causal = flash_schedule(8192, 8192)
    assert causal["grid_steps"] == 36 and causal["band_steps"] == 0
    assert causal["window"] is None and causal["edge_steps"] == 0
    assert causal["bwd_fused_vmem_bytes"] - sched["bwd_fused_vmem_bytes"] == (
        (8 - 2) * 1024 * 128 * 4
    )
    # a window that reaches every key is the causal schedule
    assert flash_schedule(8192, 8192, window=8192) == causal


@pytest.mark.parametrize("window", [5, 24, 33])
@pytest.mark.parametrize("group", [1, 6])
def test_two_pass_band_backward_matches_the_fused_one(window, group):
    """The fused backward's ring of reach + 1 slots against the two-pass
    kernels on the same band."""
    block, s, d = 16, 64, 16
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(12), 4)
    q = jax.random.normal(kq, (group, s, d))
    k = jax.random.normal(kk, (1, s, d))
    v = jax.random.normal(kv, (1, s, d))
    do = jax.random.normal(kd, (group, s, d))
    how = dict(window=window)
    o, lse = _flash_fwd_impl(q, k, v, True, block, block, True, None, False, **how)
    delta = _flash_delta_impl(o, do, block, True, False)
    fused = _flash_bwd_kernels(
        q, k, v, do, lse, delta, True, block, block, True, None, False, True, **how
    )
    two = _flash_bwd_kernels(
        q, k, v, do, lse, delta, True, block, block, True, None, False, False, **how
    )
    for a, b in zip(fused, two):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    names = pallas_kernel_names(
        lambda *a: _flash_bwd_kernels(
            *a, True, block, block, True, None, False, False, **how
        ),
        q, k, v, do, lse, delta,
    )
    assert names == ["flash_dq_window", "flash_dkv_window"]


# -- two-part scores (latent attention) ---------------------------------------


def test_schedule_reports_the_widths_and_the_rope_parts_vmem():
    """At the published widths (128 + 64 over 128) and S = 8192 the fused
    backward still fits its budget with a second dq ring and dk
    accumulator; at 16k it does not, and the two-pass kernels run."""
    from kubeflow_tpu.ops.flash import _FUSED_VMEM_BUDGET, _rope_vmem_bytes

    plain = flash_schedule(8192, 8192, head_dim=128)
    sched = flash_schedule(8192, 8192, head_dim=128, rope_dim=64)
    assert (sched["qk_dim"], sched["rope_dim"], sched["v_dim"]) == (128, 64, 128)
    assert sched["layout"] == "seq_major" and sched["transposes_per_call"] == 0
    assert sched["rope_layout"] == "head_major"
    assert plain["rope_dim"] == 0 and plain["rope_layout"] is None
    # the rope part costs VMEM by whole lane tiles: 64 dims take 128 lanes
    extra = _rope_vmem_bytes(8192, 1024, 1024, 128, 2)
    assert sched["bwd_fused_vmem_bytes"] - plain["bwd_fused_vmem_bytes"] == extra
    assert extra == 8192 * 128 * 4 + 1024 * 128 * 4 + 3 * 2048 * 128 * 4
    assert sched["bwd_fused"] and sched["bwd_fused_vmem_bytes"] <= _FUSED_VMEM_BUDGET
    assert sched["grid_steps"] == plain["grid_steps"] == 36
    long = flash_schedule(16384, 16384, head_dim=128, rope_dim=64)
    assert not long["bwd_fused"] and flash_schedule(16384, 16384)["bwd_fused"]
    # q and dq stream 192 lanes where they streamed 128, v and dO 128
    assert sched["bwd_hbm_bytes"] > plain["bwd_hbm_bytes"]
    assert flash_schedule(64, 64, head_dim=16, rope_dim=8)["rope_layout"] == "head_major"
    with pytest.raises(ValueError, match="under a window of 512"):
        flash_schedule(8192, 8192, rope_dim=64, window=512)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two-pass"])
def test_two_part_backward_kernels_by_name_and_one_gradient(fused):
    """The fused and the two-pass backward over two-part scores: their
    names, and the same five gradients (dk_rope summed over the heads)."""
    bh, s, d, r, block = 2, 256, 16, 8, 64
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    q, k, v, do = (jax.random.normal(kx, (bh, s, d)) for kx in keys[:4])
    rope = (
        jax.random.normal(keys[4], (bh, s, r)),
        jax.random.normal(keys[5], (1, s, r)),
    )
    how = dict(rope=rope, scale=0.2)
    o, lse = _flash_fwd_impl(q, k, v, True, block, block, True, None, False, **how)
    delta = _flash_delta_impl(o, do, block, True, False)
    run = lambda *a: _flash_bwd_kernels(
        *a[:6], True, block, block, True, None, False, fused, rope=a[6:],
        scale=0.2,
    )
    args = (q, k, v, do, lse, delta, *rope)
    names = pallas_kernel_names(run, *args)
    assert names == (
        ["flash_bwd_mla_fused"] if fused else ["flash_dq_mla", "flash_dkv_mla"]
    )
    dq, dk, dv, (dq_rope, dk_rope) = run(*args)
    assert dq_rope.shape == rope[0].shape and dk_rope.shape == rope[1].shape

    def dense(q, k, v, qr, kr):
        s_ = 0.2 * (jnp.einsum("hqd,hkd->hqk", q, k)
                    + jnp.einsum("hqr,kr->hqk", qr, kr[0]))
        s_ = jnp.where(jnp.tril(jnp.ones((s, s), bool)), s_, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s_, -1), v)

    _, vjp = jax.vjp(dense, q, k, v, *rope)
    for got, want in zip((dq, dk, dv, dq_rope, dk_rope), vjp(do)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
