"""The expert layer's passes stop at the row tiles in use (`ops/moe.py`):
the dense loop over experts at every imbalance, k and form of expert; no
result past `n_tiles` is ever written or consumed (the Pallas interpreter
fills unwritten memory with NaN) and no XLA operation passes over the row
buffer; the static schedule and the two counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import (
    KERNEL_RESULTS, TransformerConfig, TransformerLM, forced_experts,
)
from kubeflow_tpu.ops import moe
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.testing.hlo import pallas_kernel_names
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


def _setup(k, form, routing="uneven"):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    expert = _experts(routing, k)
    x = jax.random.normal(ks[0], (N, D))
    gate = jax.random.uniform(ks[1], expert.shape, minval=0.1)
    target = jax.random.normal(ks[2], (N, D))
    mine = tuple(m[LO:LO + HELD] for m in _weights(form))
    return expert, x, gate, target, mine


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("k", [0, 1, 2, 8])  # 0: `expert` [N]; 8 > held
@pytest.mark.parametrize(
    "routing", ["uneven", "none held", "one held expert", "all held"]
)
def test_value_and_every_gradient_match_a_dense_loop(routing, k, form):
    """Dead tiles hold NaN under the interpreter: finite and equal means
    nothing past `n_tiles` was read."""
    expert, x, gate, target, mine = _setup(k, form, routing)
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


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("k", [0, 2])  # one expert a token, and k > 1
def test_results_past_the_tiles_in_use_are_never_written_nor_read(
    k, form, monkeypatch
):
    """The poison is there: every result of a grouped matmul and of a row
    mover, forward and backward, is NaN past `n_tiles` (the interpreter's
    unwritten memory), and the value and every gradient that read them
    stay finite and match the dense loop."""
    expert, x, gate, target, mine = _setup(k, form)
    plan = moe.plan_dispatch(expert, LO, HELD, ROWS)
    tiles = moe.row_tiles(N, max(k, 1), HELD, ROWS)
    live = int(plan["n_tiles"][0]) * ROWS
    assert HELD <= live // ROWS < tiles
    results = []

    def recorded(name):
        kernel = getattr(moe, name)

        def call(*args, **kw):
            out = kernel(*args, **kw)
            each = out if isinstance(out, tuple) else (out,)
            results.extend((name, np.asarray(r)) for r in each)
            return out

        monkeypatch.setattr(moe, name, call)

    for name in ("_gmm", "_rows_take"):
        recorded(name)
    ours = lambda x, gate, *w: moe.expert_mlp(
        x, expert, gate, w, LO, block_rows=ROWS
    )
    theirs = lambda *a: _dense(*a, expert=expert, lo=LO)
    every = tuple(range(2 + len(mine)))
    loss = lambda f: lambda *a: (f(*a) * target).sum()
    value, got = jax.value_and_grad(loss(ours), every)(x, gate, *mine)
    want_value, want = jax.value_and_grad(loss(theirs), every)(x, gate, *mine)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=6e-5, rtol=3e-4)
    # forward: the matmuls that read the rows (a result each, the last
    # one two) and the one after; backward: one `dlhs` a matrix. At k > 1
    # the rows are taken once each way, and their weights' products.
    into = len(mine) - 1
    assert [n for n, _ in results].count("_gmm") == (into + 1) + 1 + 1 + into
    assert [n for n, _ in results].count("_rows_take") == (3 if k else 0)
    for name, r in results:
        if r.ndim == 1:  # the weights' gradient a row: dead rows unwritten too
            assert np.isfinite(r[:live]).all() and np.isnan(r[live:]).all()
            continue
        by_row = r.reshape(tiles * ROWS, -1, r.shape[1])  # sublanes where packed
        assert np.isfinite(by_row[:live, 0]).all(), name
        assert np.isnan(by_row[live:]).all(), name


@pytest.mark.parametrize("form", ["relu2", "gated"])
def test_the_activation_and_its_slope_are_the_matmuls_own(form):
    """The kernels one by one: the rows taken, the epilogue (the
    activation beside the product itself), the packed result, the slope
    formed on the way in by either product, and a `dlhs` added onto the
    one before in place."""
    expert = _experts("uneven", 2)
    plan = moe.plan_dispatch(expert, LO, HELD, ROWS)
    live = int(plan["n_tiles"][0]) * ROWS
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    *w_gate, w = (m[LO:LO + HELD] for m in _weights(form)[:-1])
    rows = moe._rows_take(
        moe._pack(x), plan["row_token"], plan["n_tiles"], width=D,
        block_rows=ROWS, out_dtype=x.dtype, interpret=True,
    )
    token = np.asarray(plan["row_token"])[:live]
    np.testing.assert_array_equal(
        rows[:live], np.where((token < N)[:, None], np.asarray(x)[token % N], 0)
    )
    mm = lambda lhs, w, *more, **how: moe._gmm(
        lhs, w, plan["tile_expert"], plan["n_tiles"], *more, block_rows=ROWS,
        interpret=True, **how,
    )
    by_row = lambda w: w[plan["tile_expert"][: live // ROWS]].repeat(ROWS, axis=0)
    left = np.asarray(mm(rows, w))
    np.testing.assert_allclose(
        left[:live], jnp.einsum("rd,rdf->rf", rows[:live], by_row(w)), atol=1e-5
    )
    gate = [mm(rows, m) for m in w_gate]
    hidden, pre = mm(rows, w, gate=gate[0] if gate else None, act=True)
    np.testing.assert_array_equal(pre[:live], left[:live])
    if form == "relu2":
        act = lambda z: jnp.square(jax.nn.relu(z))
        saved, products = (pre,), (pre[:live],)
    else:
        act = lambda a, b: jax.nn.silu(a) * b
        saved, products = (gate[0], pre), (gate[0][:live], pre[:live])
    np.testing.assert_allclose(hidden[:live], act(*products), rtol=1e-5, atol=1e-6)
    # packed: a row's one piece of F lanes in the first of eight sublanes
    packed = np.asarray(mm(rows, w, packed=True)).reshape(-1, 8, F)[:live, 0]
    np.testing.assert_array_equal(packed, left[:live])
    # the slope by each product, formed on the way in; the second call
    # adds onto the first's result, plain and packed alike
    g = jax.random.normal(jax.random.PRNGKey(3), hidden.shape)
    slopes = jax.vjp(act, *products)[1](g[:live])
    d_rows = d_packed = None
    want = 0.0
    for wrt, (m, slope) in enumerate(zip((*w_gate, w), slopes)):
        d_rows = mm(g, m, saved, onto=d_rows, wrt=wrt, transpose_rhs=True)
        d_packed = mm(
            g, m, saved, onto=d_packed, wrt=wrt, transpose_rhs=True, packed=True
        )
        want = want + jnp.einsum("rf,rdf->rd", slope, by_row(m))
        np.testing.assert_allclose(d_rows[:live], want, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(d_packed).reshape(-1, 8, D)[:live, 0], want,
            atol=1e-4, rtol=1e-4,
        )
        dw = moe._gmm_dw(
            rows, g, plan["tile_expert"], plan["n_tiles"], saved, wrt=wrt,
            n_experts=HELD, block_rows=ROWS, out_dtype=jnp.float32,
            interpret=True,
        )
        onehot = jax.nn.one_hot(
            plan["tile_expert"][: live // ROWS].repeat(ROWS), HELD
        )
        np.testing.assert_allclose(
            dw, jnp.einsum("re,rd,rf->edf", onehot, rows[:live], slope),
            atol=1e-4, rtol=1e-4,
        )


# -- the weights as they lie ------------------------------------------------------

# rows an expert: three tiles (the rounded block kept across them), none
# (its one zero tile), a part of one, one full; three tiles past them
_ROUND_COUNTS = (40, 0, 3, 16)
# the call beside the same call on `w.astype(bfloat16)`; the last three at
# shapes of their own (a module value read at trace time does not retrace)
_ROUND_CASES = {
    "forward": dict(),
    "transposed": dict(transpose_rhs=True),
    "relu2": dict(act=True),
    "gated": dict(act=True, gate=True),
    "saved": dict(transpose_rhs=True, saved=1, wrt=0),
    "saved onto": dict(transpose_rhs=True, saved=2, wrt=1, onto=True),
    "packed": dict(packed=True),
    "packed onto": dict(transpose_rhs=True, saved=2, wrt=0, onto=True, packed=True),
    "two column tiles": dict(d=48, f=256, side=128),
    "two column tiles transposed": dict(d=256, f=48, side=128, transpose_rhs=True),
    "two contraction steps": dict(d=256, f=80, weight=128 * 128),
}


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("case", _ROUND_CASES)
def test_float32_weights_rounded_in_the_kernel_equal_the_cast_to_the_bit(
    case, monkeypatch
):
    """bfloat16 rows and float32 weights, as the cells have them: every
    form of `_gmm` gives the bits of the same call on `w.astype(bfloat16)`
    (the parent's call sites), over an expert with no row, with one tile
    and with several, and nothing is written past the tiles in use."""
    how = dict(_ROUND_CASES[case])
    d, f = how.pop("d", D), how.pop("f", F)
    if "side" in how:
        monkeypatch.setattr(moe, "_TILE_SIDE", how.pop("side"))
    if "weight" in how:
        monkeypatch.setattr(moe, "_TILE_WEIGHT", how.pop("weight"))
    n_saved, gated, added = (
        how.pop("saved", 0), how.pop("gate", False), how.pop("onto", False)
    )
    turned = how.get("transpose_rhs", False)
    contract, cols = (f, d) if turned else (d, f)
    tc, to = moe._gmm_tiles(contract, cols, how.get("packed", False))
    assert (cols // to, contract // tc) == {
        "two column tiles": (2, 1), "two column tiles transposed": (2, 1),
        "two contraction steps": (1, 2),
    }.get(case, (1, 1))
    used = np.maximum(-(-np.array(_ROUND_COUNTS) // ROWS), 1)
    tiles = int(used.sum()) + 3
    tile_expert = jnp.asarray(
        np.repeat(np.arange(HELD), used).tolist() + [HELD - 1] * 3, jnp.int32
    )
    n_tiles = jnp.asarray([used.sum()], jnp.int32)
    live = int(used.sum()) * ROWS
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape).astype(jnp.bfloat16)
    lhs = draw(ks[0], tiles * ROWS, contract)
    w = jax.random.normal(ks[1], (HELD, d, f)) / 6
    assert (w.astype(jnp.bfloat16).astype(jnp.float32) != w).mean() > 0.9
    saved = tuple(draw(k, tiles * ROWS, contract) for k in ks[2:2 + n_saved])
    gate = draw(ks[4], tiles * ROWS, cols) if gated else None
    onto = None
    if added:
        onto = jax.random.normal(ks[5], (tiles * ROWS, cols))
        onto = moe._pack(onto) if how.get("packed") else onto.astype(jnp.bfloat16)

    def call(w):
        out = moe._gmm(
            lhs, w, tile_expert, n_tiles, saved, gate,
            None if onto is None else onto + 0, block_rows=ROWS,
            interpret=True, **how,
        )
        return out if isinstance(out, tuple) else (out,)

    by_row = lambda r: np.asarray(r).reshape(tiles * ROWS, -1)
    if how.get("packed"):  # the sublanes past a row's pieces: unwritten
        form = moe._Packed.of(cols)
        by_row = lambda r: np.asarray(r).reshape(
            tiles * ROWS, form.sublanes, form.lanes
        )[:, :form.pieces].reshape(tiles * ROWS, -1)
    got, want = call(w), call(w.astype(jnp.bfloat16))
    for a, b in zip(got, want, strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.isfinite(by_row(a)[:live].astype(np.float32)).all()
        np.testing.assert_array_equal(_bits(by_row(a)[:live]), _bits(by_row(b)[:live]))
        assert np.isnan(by_row(a)[live:].astype(np.float32)).all() or added


def _gmm_call(rows_dtype, weight_dtype, **how):
    """The `pallas_call` equation of one `_gmm` call."""
    tiles = 5
    jaxpr = jax.make_jaxpr(
        lambda lhs, w, te, nt: moe._gmm(
            lhs, w, te, nt, block_rows=ROWS, interpret=True, **how
        )
    )(
        jnp.zeros((tiles * ROWS, D), rows_dtype),
        jnp.zeros((HELD, D, F), weight_dtype),
        jnp.zeros((tiles,), jnp.int32), jnp.ones((1,), jnp.int32),
    )
    (call,) = (
        e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    )
    return call


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_equal_dtypes_trace_the_kernel_without_a_rounding(dtype):
    """Rows and weights of one dtype (the float32 tests; a model that
    held bfloat16 weights): the accumulator is the call's one scratch and
    the body is `_init`, `_compute`, `_write`, as before the kernels took
    float32 weights; float32 weights under bfloat16 rows add the rounded
    block, its fetch and their `pl.when`, nothing else."""
    scratch = lambda call: call.params["grid_mapping"].num_scratch_operands
    conds = lambda call: sum(
        e.primitive.name == "cond" for e in call.params["jaxpr"].eqns
    )
    blocks = lambda call: {  # the dtypes a value of the block's shape takes
        jnp.dtype(v.aval.dtype) for e in _equations(call.params["jaxpr"])
        for v in e.outvars if getattr(v.aval, "shape", None) == (D, F)
    }
    same = _gmm_call(dtype, dtype)
    assert (scratch(same), conds(same)) == (1, 3)
    assert blocks(same) == {jnp.dtype(dtype)}
    if dtype == jnp.bfloat16:
        # the rounded block, the block fetched an expert ahead and its
        # semaphore; the body's fourth `pl.when`, an expert's first tile
        mixed = _gmm_call(jnp.bfloat16, jnp.float32)
        assert (scratch(mixed), conds(mixed)) == (4, 4)
        assert blocks(mixed) == {jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}
        assert mixed.params["name"] == same.params["name"] == "moe_gmm_fwd"


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("k", [0, 2])
def test_no_cast_of_an_experts_weights_outside_the_kernels(k, form):
    """In a layer's forward and backward, rows bfloat16 and weights
    float32, no `convert_element_type` outside a `pallas_call` has an
    operand of an expert matrix's shape, `[held, d, f]` or `[held, f,
    d]`: the kernels read the parameters as they lie."""
    expert, x, gate, target, mine = _setup(k, form)
    x = x.astype(jnp.bfloat16)
    matrices = {(HELD, D, F), (HELD, F, D)}

    def loss(x, gate, *w):
        out = moe.expert_mlp(x, expert, gate, w, LO, block_rows=ROWS)
        return (out * target).sum()

    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(loss, tuple(range(2 + len(mine))))
    )(x, gate, *mine)
    casts, kernels = [], 0
    for eqn in _equations(jaxpr.jaxpr):
        kernels += eqn.primitive.name == "pallas_call"
        if eqn.primitive.name == "convert_element_type":
            casts.append(eqn.invars[0].aval.shape)
    assert kernels >= 3 * len(mine)
    assert not matrices & set(casts), casts
    # the weights' gradients leave `moe_gmm_dw` as float32, as they did
    grads = jax.grad(loss, tuple(range(2, 2 + len(mine))))(x, gate, *mine)
    assert all(g.dtype == jnp.float32 for g in grads)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    `pallas_call`'s body apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("k", [0, 2])
def test_no_xla_operation_passes_over_the_row_buffer(k, form):
    """In the gradient of `expert_mlp`, rows bfloat16 and weights float32
    as the cells have them, nothing outside a `pallas_call` computes on
    an array of the row buffer's shape, `[rows, f]`, `[rows, d]` or
    `[rows, d]` packed: with one expert a token the gathers by the
    plan's indices, which name rows in use only, are all that touches
    one; at k > 1 nothing is."""
    expert, x, gate, target, mine = _setup(k, form)
    x = x.astype(jnp.bfloat16)
    rows = moe.row_tiles(N, max(k, 1), HELD, ROWS) * ROWS
    buffer = {(rows, F), (rows, D), moe._Packed.of(D).shape(rows)}
    assert (N, D) not in buffer

    def loss(x, gate, *w):
        out = moe.expert_mlp(x, expert, gate, w, LO, block_rows=ROWS)
        return (out * target).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, tuple(range(2 + len(mine)))))(
        x, gate, *mine
    )
    holders = {"pallas_call", "pjit", "jit", "custom_vjp_call", "closed_call"}
    touching, kernels = set(), 0
    for eqn in _equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        kernels += name == "pallas_call"
        shapes = {
            getattr(v.aval, "shape", None) for v in (*eqn.invars, *eqn.outvars)
        }
        if name not in holders and shapes & buffer:
            touching.add(name)
    assert kernels >= 3 * len(mine)
    assert touching == (set() if k else {"gather"})


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


# -- the plan ---------------------------------------------------------------------


def _plan_by_loop(expert, lo, n_held, block_rows):
    """The plan as `plan_dispatch` documents it, by a loop over the held
    experts in order and over each one's tokens in order (NumPy)."""
    expert = np.asarray(expert)
    pairs = expert.reshape(expert.shape[0], -1)
    tokens, k = pairs.shape
    h = min(k, n_held)
    tiles = -(-tokens * h // block_rows) + n_held
    rows = tiles * block_rows
    row_token = np.full(rows, tokens, np.int32)
    tile_expert = np.full(tiles, n_held - 1, np.int32)
    token_rows = np.full((h, tokens), rows, np.int32)
    token_count = np.zeros(tokens, np.int32)
    hit = np.zeros((k, n_held, tokens), bool)
    place = np.zeros((h, n_held, tokens), bool)
    tile = 0
    for e in range(n_held):
        pair, its = np.nonzero((pairs == lo + e).T)
        for i, (j, n) in enumerate(sorted(zip(pair, its), key=lambda u: u[1])):
            row = tile * block_rows + i
            row_token[row] = n
            token_rows[token_count[n], n] = row
            place[token_count[n], e, n] = hit[j, e, n] = True
            token_count[n] += 1
        used = max(-(-len(its) // block_rows), 1)
        tile_expert[tile:tile + used] = e
        tile += used
    plan = {"tile_expert": tile_expert, "n_tiles": np.array([tile], np.int32)}
    if expert.ndim == 1:
        return {**plan, "dst": token_rows[0], "src": row_token}
    return {
        **plan, "row_token": row_token, "token_rows": token_rows,
        "token_count": token_count, "hit": hit, "place": place,
    }


_CELLS = {  # tokens, experts, k, held: one layer of a cell
    "qwen3-next": (16384, 512, 10, 32),
    "nemotron": (8192, 512, 22, 8),
    "laguna": (8192, 256, 10, 8),
}
_PLANS = [
    *(pytest.param(cell, layer, id=f"{cell} layer {layer}")
      for cell, layers in (
          ("qwen3-next", (0, 1, 2, 3)), ("nemotron", (1, 10)), ("laguna", (1, 4)),
      ) for layer in layers),
    *(pytest.param(routing, k, id=f"{routing} k={k}")
      for routing in ("uneven", "none held", "one held expert", "all held")
      for k in (0, 2, 8)),  # 0: `expert` [N]; 8 > held
]


@pytest.mark.parametrize("case, at", _PLANS)
def test_every_key_of_the_plan_matches_a_loop_over_the_held_experts(case, at):
    """At a layer of the three cells of more than one expert a token
    (qwen3-next: 672 tiles, 64 in use, ~10,300 rows) and at the small
    routings: no token held, every pair held, k > held, held experts with
    no row. A row past the tiles in use reads N like a row of padding."""
    if case in _CELLS:
        tokens, experts, k, held = _CELLS[case]
        expert, lo, rows = forced_experts(at, tokens, experts, k), 0, moe.BLOCK_ROWS
    else:
        expert, lo, held, rows = _experts(case, at), LO, HELD, ROWS
    got = moe.plan_dispatch(expert, lo, held, rows)
    want = _plan_by_loop(expert, lo, held, rows)
    for key, value in want.items():
        np.testing.assert_array_equal(got[key], value, err_msg=key)
    if case == "qwen3-next":
        assert want["tile_expert"].shape == (672,) and want["n_tiles"] == 64
        assert 10_000 < (want["row_token"] < 16384).sum() < 10_600
    if case == "one held expert" and at:
        assert (want["token_count"] == 1).all()  # three held experts of no row
        assert want["n_tiles"] == N // ROWS + HELD - 1


@pytest.mark.parametrize("weighted", [False, True], ids=["tokens", "weights"])
@pytest.mark.parametrize(
    "counts",
    [(40, 0, 3, 16), (0, 0, 0, 0), (16, 16, 16, 16), (1, 64, 0, 2)],
    ids=["uneven", "no row", "whole tiles", "one full"],
)
def test_the_plans_kernel_counts_a_rows_token_over_the_tiles_in_use(
    counts, weighted
):
    """`moe_plan_rows`, `moe_plan_weights` and `moe_plan_tokens` alone:
    an expert's rows name its tokens in order (or carry their values, to
    the bit, and hand them back), a tile's expert with fewer rows than the
    tile pads with N (zeros), the last tile in use is written whole, and
    the tiles past it are never visited."""
    rng = np.random.default_rng(3)
    mine = np.zeros((HELD, N), bool)
    for e, c in enumerate(counts):
        mine[e, rng.choice(N, c, replace=False)] = True
    used = np.maximum(-(-np.array(counts) // ROWS), 1)
    tiles = moe.row_tiles(N, HELD, HELD, ROWS)
    value = np.where(mine, rng.uniform(0.5, 1.5, mine.shape), 0).astype(np.float32)
    tile_expert = jnp.asarray(
        np.repeat(np.arange(HELD), used).tolist()
        + [HELD - 1] * (tiles - used.sum()), jnp.int32,
    )
    n_tiles = jnp.asarray([used.sum()], jnp.int32)
    got = moe._plan_rows(
        jnp.asarray(np.cumsum(mine, axis=1), jnp.int32), tile_expert, n_tiles,
        jnp.asarray(np.cumsum(used) - used, jnp.int32), block_rows=ROWS,
        interpret=True,
    )
    if weighted:  # the backward's: a row's value, found from its token
        in_use = jnp.arange(tiles * ROWS) // ROWS < n_tiles
        plan = {
            "tile_expert": tile_expert, "n_tiles": n_tiles,
            "row_token": jnp.where(in_use, got, N),
        }
        got = moe.rows_values(plan, jnp.asarray(value), interpret=True)
        # and back, by `moe_plan_tokens`: each token's value where it was
        back = moe.tokens_values(
            {**plan, "hit": jnp.asarray(mine)[None]}, got, interpret=True
        )
        np.testing.assert_array_equal(back, value)
    got = np.asarray(got)
    live = used.sum() * ROWS
    assert got.shape == (tiles * ROWS,) and live < got.shape[0]
    want = np.full(live, 0.0 if weighted else N, got.dtype)
    for e, first in enumerate(np.cumsum(used) - used):
        its = np.nonzero(mine[e])[0]
        want[first * ROWS:first * ROWS + len(its)] = value[e, its] if weighted else its
    np.testing.assert_array_equal(got[:live], want)
    # what the interpreter leaves in an int32 result nobody wrote (the
    # values go through the kernel as their bits)
    assert (got[live:].view(np.int32) == np.iinfo(np.int32).min).all()


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
    # the weights at the 4 bytes they lie at, each block rounded once a
    # fetch; no cast outside the kernels (6 B a parameter a pass at PR 48)
    weights = 4 * 8 * 1024 * 2688
    assert live["gmm_bytes"] == weights + 2 * 4096 * (1024 + 2688)
    assert (live["weight_itemsize"], live["weight_rounds"]) == (4, 8)
    assert live["weight_cast_bytes"] == 0
    narrow = moe.moe_schedule(
        8192, 22, 8, 1024, 2688, live_tiles=16, weight_itemsize=2
    )
    assert narrow["gmm_bytes"] == live["gmm_bytes"] - weights // 2
    assert narrow["weight_rounds"] == 0 == narrow["weight_cast_bytes"]
    assert live["activation"]["relu2"] == "kernel"
    assert live["results_past_live"] == 0


def test_the_schedule_at_the_gated_cells_shapes():
    """Both forms' activation is the kernels' and no result is written
    past the tiles in use. laguna's: 8,192 tokens, 10 of 256 experts
    each, 8 held, 3072 -> 1024: 264 row tiles, 16 in use in each of its
    four expert layers. zaya's: 16,384 tokens, one of 16 experts each, 8
    held, 2048 -> 2048: 72 tiles, about half in use, XLA's gathers."""
    for layer in (1, 2, 3, 4):
        plan = moe.plan_dispatch(forced_experts(layer, 8192, 256, 10), 0, 8)
        assert int(plan["n_tiles"][0]) == 16, layer
    laguna = moe.moe_schedule(8192, 10, 8, 3072, 1024, live_tiles=16)
    assert laguna["tiles"] == 264 and laguna["rows"] == 67584
    assert laguna["rows_touched"] == 4096 and laguna["movers"] == "moe_rows"
    assert laguna["gmm_grid"] == (1, 264, 1) and laguna["gmm_dead_steps"] == 248
    live = [  # the batch's two sequences make the same selection
        int(moe.plan_dispatch(
            jnp.tile(forced_experts(layer, 8192, 16), 2), 0, 8
        )["n_tiles"][0])
        for layer in range(8)
    ]
    assert sum(live) == 286 and 34 <= min(live) <= max(live) <= 38
    zaya = moe.moe_schedule(16384, 1, 8, 2048, 2048, live_tiles=36)
    assert zaya["tiles"] == 72 and zaya["movers"] == "xla_gather"
    assert zaya["gmm_grid"] == (1, 72, 1) and zaya["gmm_dead_steps"] == 36
    # a block an expert, fetched at 4 bytes and rounded once: 134 MB of
    # weights a call in zaya where the cast's copy was 67 read after 201
    # moved to make it
    assert zaya["weight_rounds"] == laguna["weight_rounds"] == 8
    assert zaya["gmm_bytes"] == 4 * 8 * 2048 * 2048 + 2 * 36 * 256 * 4096
    qwen = moe.moe_schedule(16384, 10, 32, 2048, 512, live_tiles=64)
    assert qwen["weight_rounds"] == 32 and qwen["gmm_grid"] == (1, 672, 1)
    for sched in (laguna, zaya, qwen):
        assert sched["weight_itemsize"] == 4 and sched["weight_cast_bytes"] == 0
    for sched in (laguna, zaya):
        assert sched["activation"] == {"relu2": "kernel", "gated": "kernel"}
        assert sched["results_past_live"] == 0
        assert "gmm_bytes_zeroing" not in sched


@pytest.mark.parametrize("d, f, grid, fetches", [
    (2048, 2048, (1, 72, 1), 8),  # zaya: a block an expert
    (1024, 8192, (2, 72, 1), 16),  # two column tiles: each expert's twice
    (8192, 4096, (1, 72, 8), 36 * 8),  # a contraction in steps: every step fetches
])
def test_the_schedule_rounds_a_block_once_a_fetch(d, f, grid, fetches):
    sched = moe.moe_schedule(16384, 1, 8, d, f, live_tiles=36)
    assert sched["gmm_grid"] == grid and sched["weight_rounds"] == fetches
    tc, to = moe._gmm_tiles(d, f)
    assert sched["gmm_bytes"] == 4 * fetches * tc * to + 2 * 36 * 256 * (d + f)


def test_the_plan_updates_at_the_cells_shapes():
    """The single elements the plan scatters: 524,288 a layer in the
    qwen3-next cell until PR 46 (`n_held x N`, to place ~10,300 rows),
    none since in any cell of more than one expert a token; zaya's one
    expert a token scatters a row's token for each of its 16,384."""
    for tokens, _, k, held in _CELLS.values():
        sched = moe.moe_schedule(tokens, k, held, 2048, 512)
        assert sched["plan_updates"] == 0
        assert sched["plan_rows_grid_steps"] == sched["tiles"]
    widest = moe.moe_schedule(16384, 10, 32, 2048, 512, live_tiles=64)
    assert widest["tiles"] == 672 and widest["plan_rows_grid_steps"] == 64
    zaya = moe.moe_schedule(16384, 1, 8, 2048, 2048)
    assert zaya["plan_updates"] == 16384 and zaya["plan_rows_grid_steps"] == 0


@pytest.mark.parametrize("k", [0, 2, 8])  # 0: `expert` [N]; 8 > held
def test_the_plans_scatters_add_up_to_plan_updates(k):
    """In the gradient of `expert_mlp` (the plan, the weights by slot and
    both backward halves) the `scatter` equations' updates are the
    schedule's `plan_updates`: nothing of `n_held x N` or `h x N` single
    elements at k > 1, where `moe_plan_rows` runs once forward and
    `moe_plan_weights` and `moe_plan_tokens` once backward; no `gather`
    of single elements either."""
    expert, x, gate, target, mine = _setup(k, "gated")

    def loss(x, gate, *w):
        out = moe.expert_mlp(x, expert, gate, w, LO, block_rows=ROWS)
        return (out * target).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, tuple(range(2 + len(mine)))))(
        x, gate, *mine
    )
    updates, plan_kernels = [], []
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name.startswith("scatter"):
            updates.append(int(np.prod(eqn.invars[2].aval.shape)))
        if k and eqn.primitive.name == "gather":  # an expert's last count
            assert np.prod(eqn.outvars[0].aval.shape) <= HELD
        if eqn.primitive.name == "pallas_call":
            plan_kernels += [eqn.params["name"]] * ("plan" in eqn.params["name"])
    sched = moe.moe_schedule(N, max(k, 1), HELD, D, F, block_rows=ROWS)
    assert sum(updates) == sched["plan_updates"] == (0 if k else N)
    assert HELD * N not in updates and min(k, HELD) * N not in updates or not k
    assert plan_kernels == [
        "moe_plan_rows", "moe_plan_weights", "moe_plan_tokens"
    ] * bool(k)


def test_the_flash_policy_does_not_count_the_rows_tokens_again():
    """`remat_policy="flash"` keeps the rows' tokens by name
    (`KERNEL_RESULTS`): `moe_plan_rows` runs once, forward, where a
    checkpoint that keeps nothing runs it again for the second forward
    (as the scatter it replaces ran twice a layer)."""
    expert, x, gate, target, mine = _setup(2, "gated")

    def loss(x, gate, *w):
        out = moe.expert_mlp(x, expert, gate, w, LO, block_rows=ROWS)
        return (out * target).sum()

    def plan_kernels(policy):
        grad = jax.grad(jax.checkpoint(loss, policy=policy), (0, 1))
        names = pallas_kernel_names(grad, x, gate, *mine)
        return names.count("moe_plan_rows"), names.count("moe_plan_weights")

    assert moe.CHECKPOINT_ROWS_NAME in KERNEL_RESULTS
    keep = jax.checkpoint_policies.save_only_these_names(*KERNEL_RESULTS)
    assert plan_kernels(keep) == (1, 1)
    assert plan_kernels(jax.checkpoint_policies.nothing_saveable) == (2, 1)


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
