"""The expert layer's passes stop at the row tiles in use (`ops/moe.py`):
the dense loop over experts at every imbalance, k and form of expert; no
result past `n_tiles` is ever consumed (the Pallas interpreter fills
unwritten memory with NaN); the static schedule and the two counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM, forced_experts,
)
from kubeflow_tpu.ops import moe
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer, fit

N, D, F, E = 64, 32, 48, 16
LO, HELD = 2, 4  # the experts held here: 2..5
ROWS = 16  # a row tile


def _experts(routing: str, k: int):
    """[N, k] distinct experts a token ([N] at k = 0, one a token)."""
    key = jax.random.PRNGKey(11)
    held = jnp.arange(LO, LO + HELD)
    away = jnp.concatenate([jnp.arange(LO), jnp.arange(LO + HELD, E)])
    width = max(k, 1)
    if routing == "uneven":
        expert = jax.lax.top_k(jax.random.normal(key, (N, E)), width)[1]
    elif routing == "none held":
        order = jax.vmap(lambda q: jax.random.permutation(q, away))(
            jax.random.split(key, N)
        )
        expert = order[:, :width]
    elif routing == "one held expert":
        order = jax.vmap(lambda q: jax.random.permutation(q, away))(
            jax.random.split(key, N)
        )
        expert = order[:, :width].at[:, width // 2].set(held[1])
    elif routing == "all held":  # as many of a token's experts as there are
        order = jax.vmap(lambda q: jax.random.permutation(q, held))(
            jax.random.split(key, N)
        )
        filler = jax.vmap(lambda q: jax.random.permutation(q, away))(
            jax.random.split(jax.random.fold_in(key, 1), N)
        )
        expert = jnp.concatenate([order, filler], axis=1)[:, :width]
    return (expert[:, 0] if k == 0 else expert).astype(jnp.int32)


def _weights(form: str):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    into = 1 if form == "relu2" else 2
    return (
        *(jax.random.normal(ks[i], (E, D, F)) / 6 for i in range(into)),
        jax.random.normal(ks[2], (E, F, D)) / 7,
    )


def _dense(x, gate, *w, expert, lo):
    """Every held expert over every token, by the weights of the pairs
    routed to it."""
    pairs = expert.reshape(N, -1)
    out = jnp.zeros_like(x)
    for i in range(w[0].shape[0]):
        mine = jnp.sum(jnp.where(pairs == lo + i, gate.reshape(N, -1), 0.0), axis=-1)
        if len(w) == 2:
            y = jnp.square(jax.nn.relu(x @ w[0][i])) @ w[1][i]
        else:
            y = (jax.nn.silu(x @ w[0][i]) * (x @ w[1][i])) @ w[2][i]
        out += mine[:, None] * y
    return out


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("k", [0, 1, 2, 8])  # 0: `expert` [N]; 8 > held
@pytest.mark.parametrize(
    "routing", ["uneven", "none held", "one held expert", "all held"]
)
def test_value_and_every_gradient_match_a_dense_loop(routing, k, form):
    """Dead tiles hold NaN under the interpreter: finite and equal means
    nothing past `n_tiles` was read."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    expert = _experts(routing, k)
    x = jax.random.normal(ks[0], (N, D))
    gate = jax.random.uniform(ks[1], expert.shape, minval=0.1)
    target = jax.random.normal(ks[2], (N, D))
    mine = tuple(m[LO:LO + HELD] for m in _weights(form))
    ours = lambda x, gate, *w: moe.expert_mlp(
        x, expert, gate, w, LO, block_rows=ROWS
    )
    theirs = lambda *a: _dense(*a, expert=expert, lo=LO)
    got, want = ours(x, gate, *mine), theirs(x, gate, *mine)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=3e-5)
    if routing == "none held":
        assert not np.asarray(got).any()
    every = tuple(range(2 + len(mine)))
    got = jax.grad(lambda *a: (ours(*a) * target).sum(), every)(x, gate, *mine)
    want = jax.grad(lambda *a: (theirs(*a) * target).sum(), every)(x, gate, *mine)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=6e-5, rtol=3e-4)


def test_results_past_the_tiles_in_use_are_never_written_nor_read():
    """The poison is there: a grouped matmul's and a row mover's result is
    NaN past `n_tiles` (the interpreter's unwritten memory) unless it was
    asked for zeros, and what reads them stays finite."""
    expert = _experts("uneven", 2)
    plan = moe.plan_dispatch(expert, LO, HELD, ROWS)
    live = int(plan["n_tiles"][0]) * ROWS
    tiles = moe.row_tiles(N, 2, HELD, ROWS)
    assert HELD <= live // ROWS < tiles
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    w = _weights("relu2")[0][LO:LO + HELD]
    rows = moe._rows_take(
        moe._pack(x), plan["row_token"], plan["n_tiles"], width=D,
        block_rows=ROWS, out_dtype=x.dtype, interpret=True,
    )
    assert np.isfinite(np.asarray(rows[:live])).all()
    assert np.isnan(np.asarray(rows[live:])).all()
    token = np.asarray(plan["row_token"])[:live]
    np.testing.assert_array_equal(
        rows[:live], np.where((token < N)[:, None], np.asarray(x)[token % N], 0)
    )
    mm = lambda **how: moe._gmm(
        rows, w, plan["tile_expert"], plan["n_tiles"], block_rows=ROWS,
        interpret=True, **how,
    )
    left, zeroed = np.asarray(mm()), np.asarray(mm(zero_dead=True))
    assert np.isnan(left[live:]).all() and not zeroed[live:].any()
    np.testing.assert_array_equal(left[:live], zeroed[:live])
    hidden, pre = mm(relu2=True)
    np.testing.assert_allclose(
        hidden[:live], np.square(np.maximum(left[:live], 0)), rtol=1e-6
    )
    np.testing.assert_array_equal(pre[:live], left[:live])
    # packed: a row's one piece of F lanes in the first of eight sublanes
    packed = np.asarray(mm(packed=True)).reshape(-1, 8, F)[:live, 0]
    np.testing.assert_array_equal(packed, left[:live])
    # the slope of relu^2 formed on the way in
    slope = moe._gmm(
        hidden, w, plan["tile_expert"], plan["n_tiles"], pre,
        block_rows=ROWS, transpose_rhs=True, interpret=True,
    )
    want = jnp.einsum(
        "rf,rdf->rd", hidden[:live] * 2 * jnp.maximum(pre[:live], 0),
        w[plan["tile_expert"][: live // ROWS]].repeat(ROWS, axis=0),
    )
    np.testing.assert_allclose(slope[:live], want, atol=1e-4, rtol=1e-4)


def test_the_movers_at_a_width_of_several_lane_pieces():
    """d = 256: two pieces of 128 lanes a row, in a tile of eight
    sublanes, as the chip's widths are packed."""
    d, k = 256, 3
    form = moe._Packed.of(d)
    assert form == (128, 2, 8) and moe._Packed.of(1024) == (128, 8, 8)
    assert moe._Packed.of(2048).shape(4) == (64, 128)
    expert = _experts("uneven", k)
    plan = moe.plan_dispatch(expert, LO, HELD, ROWS)
    live = int(plan["n_tiles"][0]) * ROWS
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (N, d))
    token = np.asarray(plan["row_token"])
    real = token < N
    scale = jax.random.uniform(ks[1], token.shape, minval=0.5)
    buffer = jnp.where(
        real[:, None], jax.random.normal(ks[2], (token.shape[0], d)), 0.0
    )
    rows, inner = moe._rows_take(
        moe._pack(x), plan["row_token"], plan["n_tiles"], scale,
        moe._pack(buffer), width=d, block_rows=ROWS, out_dtype=x.dtype,
        interpret=True,
    )
    moved = np.where(real[:, None], np.asarray(x)[token % N], 0)
    np.testing.assert_allclose(
        rows[:live], (moved * np.asarray(scale)[:, None])[:live], rtol=1e-6
    )
    np.testing.assert_allclose(
        inner[:live], (moved * np.asarray(buffer)).sum(1)[:live], rtol=1e-4,
        atol=1e-5,
    )
    weight = jax.random.uniform(ks[3], plan["token_rows"].shape, minval=0.5)
    got = moe._rows_sum(
        moe._pack(buffer), plan["token_rows"], plan["token_count"], weight,
        width=d, out_dtype=jnp.float32, interpret=True,
    )
    slots = np.asarray(plan["token_rows"])
    has = slots < token.shape[0]
    want = (
        np.where(has, np.asarray(weight), 0)[:, :, None]
        * np.asarray(buffer)[np.where(has, slots, 0)]
    ).sum(0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (np.asarray(plan["token_count"]) == has.sum(0)).all()


# -- the schedule and the counters ---------------------------------------------


def test_the_schedule_at_the_nemotron_cells_shape():
    """8,192 tokens, 22 of 512 experts each, 8 held, 1024 -> 2688: 264 row
    tiles for the worst case, 16 in use under the cell's forced selection
    in every expert layer of `MEMEMEMEM*E`."""
    assert moe.row_tiles(8192, 22, 8) == 264
    for layer in (1, 3, 5, 7, 10):
        plan = moe.plan_dispatch(forced_experts(layer, 8192, 512, 22), 0, 8)
        assert int(plan["n_tiles"][0]) == 16, layer
        assert plan["tile_expert"].shape == (264,)
    all_, live = (
        moe.moe_schedule(8192, 22, 8, 1024, 2688, live_tiles=n) for n in (None, 16)
    )
    assert all_["tiles"] == live["tiles"] == 264 == all_["live_tiles"]
    assert live["rows"] == 67584 and live["rows_touched"] == 4096
    # one weight block an expert, one grid step a row tile
    assert live["gmm_grid"] == (1, 264, 1) and live["gmm_dead_steps"] == 248
    assert live["movers"] == "moe_rows" and live["rows_take_grid_steps"] == 264
    assert live["rows_sum_grid_steps"] == 32
    weights = 2 * 8 * 1024 * 2688
    assert live["gmm_bytes"] == weights + 2 * 4096 * (1024 + 2688)
    assert live["gmm_bytes_zeroing"] - live["gmm_bytes"] == 2 * 63488 * 2688
    # zaya's: one expert a token, XLA's gathers, the tiles it had
    zaya = moe.moe_schedule(16384, 1, 8, 2048, 2048)
    assert zaya["tiles"] == 72 and zaya["movers"] == "xla_gather"
    assert zaya["gmm_grid"] == (1, 72, 1)


def test_the_tiles_in_use_follow_the_rows():
    counts = jnp.array([0, 1, 16, 17, 40])
    np.testing.assert_array_equal(
        moe.tiles_in_use(counts, ROWS), [1, 1, 1, 2, 3]
    )
    # every pair held: the tiles in use hold every pair's row, and are
    # short of the worst case by less than one tile an expert
    expert = _experts("all held", 2)
    plan = moe.plan_dispatch(expert, LO, HELD, ROWS)
    live, tiles = int(plan["n_tiles"][0]), moe.row_tiles(N, 2, HELD, ROWS)
    assert live * ROWS >= 2 * N and tiles - HELD <= live <= tiles


def test_fit_records_carry_the_row_tiles(devices):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=8, d_ff=32,
        dtype=jnp.float32, remat_policy="none", num_experts=4,
        experts_held=(1, 2),
    )
    mesh = build_mesh(MeshSpec(), devices[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=8, learning_rate=1e-2, warmup_steps=2,
                    total_steps=50, optimizer="adamw"),
        mesh, example_input_shape=(2, 16), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    data = SyntheticTokens(mesh, batch_size=8, seq_len=16, vocab_size=64)
    result = fit(trainer, data, 2, log_every=1, handle_signals=False)
    assert len(result.history) == 2
    for rec in result.history:
        # two layers of 128 tokens over 2 held experts, summed by name
        assert rec["moe_row_tiles"] == 2 * moe.row_tiles(128, 1, 2)
        assert 2 * 2 <= rec["moe_row_tiles_live"] <= rec["moe_row_tiles"]
        assert rec["moe_tokens_held"] <= 2 * 128
