"""The Qwen3-Next-style decoder (a Gated DeltaNet mixer, the delta rule with a
decay a head over grouped key heads, in three layers of four; gated softmax
attention with a head's q/k norm, a rope over a quarter of the head and a
gate an output channel in the fourth; every layer over softmax-routed top-k
experts beside a gated shared one; (1 + w) norms; an untied head), one stack
of `Block`s, against the plain reference `benchmarks/reference/qwen3_next.py`,
at a tiny size on the CPU that keeps every ratio: 4 value heads over 2 key
heads of 16, 4 query heads of 32 over 2, convolutions of 4 taps, chunks of
16, 8 experts two a token, two periods of layers."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_gdn
from benchmarks.reference import qwen3_next as ref
from kubeflow_tpu.models.transformer import (
    Attention, AttentionKind, ExpertLayer, TransformerLM,
)
from kubeflow_tpu.ops import gatenorm
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = train_gdn.model_numbers({
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 32,
    "hidden_act": "silu", "hidden_size": 32, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "mlp_only_layers": [], "moe_intermediate_size": 16, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 10_000_000, "shared_expert_intermediate_size": 16,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 64, "experts_routed": 8, "experts_first": 0,
})
ONE_PERIOD = dict(NUMBERS, num_hidden_layers=4)
B, S = 2, 32


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return dataclasses.replace(
        train_gdn.transformer_config(numbers, **how), ssm_chunk=16
    )


def _held(numbers, key, first, count):
    """The numbers and the seeded leaves of a share that holds experts
    first .. first + count - 1 (the draw `follow` makes)."""
    cut = dict(numbers, num_experts=count, experts_first=first)
    return cut, jax.jit(lambda k: ref.init_params(k, cut))(key)


def _moved(flat, key):
    """The seeded leaves with every (1 + w) scale's w off zero, so that the
    offset itself is compared and not only its slope."""
    noise = lambda i, leaf: 0.3 * jax.random.normal(
        jax.random.fold_in(key, i), leaf.shape
    )
    return {
        name: leaf + noise(i, leaf) if name.split(".")[-1] in (
            "ln_attn", "ln_mlp", "ln_final", "q_norm", "k_norm", "kda_norm"
        ) else leaf
        for i, (name, leaf) in enumerate(flat.items())
    }


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, tokens[:, :-1], tokens[:, 1:]


def _program_loss(cfg):
    model = TransformerLM(cfg)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


@pytest.fixture(scope="module")
def followed(seeded):
    """Two periods' seeded leaves (scales moved off their seed), and the
    reference's logits, summed loss and gradients over them."""
    key, tokens, labels = seeded
    numbers, flat = _held(dict(NUMBERS, router_force_balance=True), key, 0, 4)
    flat = _moved(flat, jax.random.PRNGKey(8))

    def reference(p, t, l):
        logits = ref.logits(p, t, numbers)
        log_z = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return jnp.sum(log_z - picked), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(
            jax.value_and_grad(reference, has_aux=True)
        )(flat, tokens, labels)
    return numbers, flat, loss, logits, grads


@pytest.mark.parametrize("policy", ["none", "full", "mlp", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
    seeded, followed, policy, monkeypatch
):
    """Under every remat policy; under `flash` with the delta rule's kernels
    (interpreted here), whose named results the policy keeps: one `gdn_fwd`
    and one `gdn_bwd` a delta layer, no forward kernel a second time."""
    from kubeflow_tpu.ops import kda

    _, tokens, labels = seeded
    numbers, flat, ref_loss, want, ref_grads = followed
    params = train_gdn.to_program_tree(flat)
    model = TransformerLM(_config(numbers, remat_policy=policy))
    if policy == "flash":
        real = kda.kda_scan
        monkeypatch.setattr(
            "kubeflow_tpu.models.transformer.kda_scan",
            lambda *a, **kw: real(*a, **kw, interpret=True),
        )

    def program(p, t, l):
        logits = model.apply({"params": p}, t)
        return softmax_cross_entropy(logits, l), logits

    step = jax.value_and_grad(program, has_aux=True)
    if policy == "flash":
        names = jaxpr_kernel_names(
            jax.make_jaxpr(step)(params, tokens, labels).jaxpr
        )
        assert names.count("gdn_fwd") == 6 and names.count("gdn_bwd") == 6
        assert not [n for n in names if n.startswith("kda_")]
    (loss, got), grads = jax.jit(step)(params, tokens, labels)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=2e-6)
    for name, got in train_gdn.from_program_tree(grads, list(flat)).items():
        want = np.asarray(ref_grads[name] / n_tok)
        # by the leaf's own size: two periods deep, an entry near zero of a
        # leaf that is not carries the leaf's rounding, not its own
        assert np.linalg.norm(got - want) <= 1e-3 * np.linalg.norm(want), name
        np.testing.assert_allclose(
            got, want, atol=5e-3 * np.abs(want).max(), rtol=2e-3, err_msg=name
        )
    # one stack of blocks: a delta mixer or attention by the layer's row
    for i in range(8):
        mixer = "attn" if i % 4 == 3 else "kda"
        assert set(grads[f"layer_{i}"]) == {mixer, "ln_attn", "ln_mlp", "moe"}
    assert set(grads["layer_3"]["attn"]) == {
        "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    assert set(grads["layer_0"]["kda"]) == {
        "wq", "wk", "wv", "wg", "wb", "wa", "conv_q", "conv_k", "conv_v",
        "A_log", "dt_bias", "norm_scale", "wo"}
    assert grads["layer_0"]["kda"]["wq"]["kernel"].shape == (32, 2, 16)
    assert grads["layer_0"]["kda"]["wv"]["kernel"].shape == (32, 4, 16)
    assert grads["layer_3"]["attn"]["wq"].shape == (32, 4, 64)  # q and gate
    assert set(grads["layer_0"]["moe"]) == {
        "router", "w_gate", "w_up", "w_down", "shared", "shared_gate"}
    for leaf in ("A_log", "dt_bias", "conv_q", "norm_scale", "wb", "wa"):
        assert np.any(grads["layer_1"]["kda"][leaf]), leaf
    assert np.any(grads["layer_1"]["moe"]["shared_gate"])


def test_three_adamw_steps_match_the_reference(seeded):
    """One period, the router's own choice (no forced selection)."""
    key = seeded[0]
    numbers, flat = _held(ONE_PERIOD, key, 0, 4)
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, numbers, opt, batches, rows_per_block=1)
    params = train_gdn.to_program_tree(flat)
    tx = optax.adamw(
        lambda count: opt["learning_rate"] * count / opt["warmup_steps"],
        weight_decay=opt["weight_decay"],
    )
    state, losses, first = tx.init(params), [], None
    loss_fn = jax.jit(jax.value_and_grad(_program_loss(_config(numbers))))
    for batch in batches:
        loss, grads = loss_fn(params, batch["tokens"], batch["labels"])
        first = grads if first is None else first
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    now = train_gdn.from_program_tree(params, list(flat))
    grad = train_gdn.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7), name


def test_the_gated_attention_layer_matches_the_references(seeded):
    """A head's (1 + w) norm on q and k, rope over the first quarter of a
    head, grouped K/V heads, and a gate an output channel from q's own
    matrix, with every scale off its seed."""
    cfg = _config(ONE_PERIOD)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    _, flat = _held(ONE_PERIOD, seeded[0], 0, 4)
    p = ref._xing.layer_params(_moved(flat, jax.random.PRNGKey(2)), 3)
    layer = Attention(cfg, kind=cfg.attention_kinds[1])
    params = train_gdn.to_program_tree({
        f"layer.3.{k}": v for k, v in p.items()
        if k in ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
    })["layer_3"]["attn"]
    got = layer.apply({"params": params}, x, positions)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_layer(x, p, ONE_PERIOD)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_the_sixteen_shares_and_the_gated_shared_expert_once_add_up(seeded):
    """Sixteen experts over sixteen chips, one each: what each share's
    routed expert adds, with the shared expert under its gate (which every
    chip computes alike) counted once, is the uncut reference's layer."""
    numbers = dict(ONE_PERIOD, num_experts=16, experts_routed=16)
    _, flat = _held(numbers, seeded[0], 0, 16)
    p = ref._xing.layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(9), (B, S, numbers["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, p, numbers, 1)
        shared = ref.shared_expert(h, p)
    moe = dict(train_gdn.to_program_tree({
        f"layer.1.{k}": v for k, v in p.items()
        if not k.startswith(("kda_", "ln_"))
    })["layer_1"]["moe"])
    routed = 0.0
    for first in range(16):
        cfg = dataclasses.replace(_config(numbers), experts_held=(first, 1))
        share = dict(moe)
        for leaf in ("w_gate", "w_up", "w_down"):
            share[leaf] = moe[leaf][first:first + 1]
        out, _ = ExpertLayer(cfg, layer=1).apply({"params": share}, h, None)
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, whole, atol=3e-5, rtol=3e-5)
    # a token's two experts' weights sum to one: normalised over the chosen
    expert, weight = ref.route(h, p, numbers, 1)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)
    assert expert.shape == (B, S, 2)


DELTA = AttentionKind(4, mixer="delta", key_heads=2, head_dim=16, decay="head",
                      gate_act="silu")
FULL = AttentionKind(4, rope_fraction=0.25)


@pytest.mark.parametrize("change, message", [
    (dict(attention_kinds=(dataclasses.replace(DELTA, key_heads=3), FULL)),
     "over 3 key heads"),
    (dict(attention_kinds=(dataclasses.replace(DELTA, decay="channel"), FULL)),
     "equal them where the decay is a 'channel'"),
    (dict(attention_kinds=(dataclasses.replace(DELTA, decay="row"), FULL)),
     "a decay a 'row'"),
    (dict(attention_kinds=(dataclasses.replace(DELTA, gate_act="tanh"), FULL)),
     "gated by 'tanh'"),
    (dict(attention_kinds=(DELTA, dataclasses.replace(FULL, key_heads=2))),
     "an attention row with key_heads 2"),
    (dict(attention_kinds=(DELTA, dataclasses.replace(FULL, head_dim=16))),
     "a head_dim of 16 of its own"),
    (dict(attention_kinds=(DELTA, dataclasses.replace(FULL, window=8))),
     "attention_gate 'channel'.* with a window"),
    (dict(kv_latent=8, rope_head_dim=8, n_kv_heads=None),
     "attention_gate 'channel'.* latent"),
    (dict(attention_gate="lane"), "attention_gate 'lane'"),
    (dict(attention_gate=True, cca=True), "qk_norm with latent attention or CCA"),
    (dict(router="tanh"), "router 'tanh'"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    cfg = dataclasses.replace(_config(ONE_PERIOD), **change)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=message):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)


def test_the_mixers_and_the_gates_counters_are_means_over_their_layers():
    cfg = _config(dict(ONE_PERIOD, router_force_balance=True))
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, S), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    _, mutated = model.apply(variables, tokens, mutable=["counters"])
    total = lambda name: sum(
        float(v[name]) for v in jax.tree_util.tree_leaves(
            mutated["counters"], is_leaf=lambda v: isinstance(v, dict) and name in v
        ) if isinstance(v, dict) and name in v
    )
    assert 0.5 < total("kda_decay_mean") < 1.0
    assert 0.2 < total("kda_beta_mean") < 0.8
    # one attention layer of four, four expert layers: still means
    assert 0.3 < total("attn_gate_mean") < 0.7
    assert 0.3 < total("shared_gate_mean") < 0.7
    # the model's own seed: every (1 + w) scale starts at zero, w_n at one
    params = variables["params"]
    assert not np.any(params["layer_0"]["ln_attn"]["scale"].value)
    assert not np.any(params["layer_3"]["attn"]["q_norm"].value)
    assert np.all(params["layer_0"]["kda"]["norm_scale"].value == 1.0)


# -- the gated norm: the activation apart from the order ----------------------


def _gated_norm_as_it_was(o, gate, scale, group, eps, gate_first, skip=None):
    """`ops/gatenorm.gated_norm_plain` as PR 43 wrote it, when the order
    said the activation: what kimi's and nemotron's calls computed."""
    f32 = jnp.float32
    s, gate = o.astype(f32), gate.astype(f32)
    if skip is not None:
        s = s + skip[1] * skip[0].astype(f32)
    t = s * jax.nn.silu(gate) if gate_first else s
    sums = (t * t).reshape(*t.shape[:-1], -1, group).sum(-1, keepdims=True)
    r = jax.lax.rsqrt(sums / group + eps)
    out = (t.reshape(*t.shape[:-1], -1, group) * r).reshape(t.shape) * scale
    if not gate_first:
        out = out * jax.nn.sigmoid(gate)
    return out.astype(o.dtype)


@pytest.mark.parametrize("who, how", [
    ("kimi", dict(group=128, gate_first=False, act="sigmoid")),
    ("nemotron", dict(group=256, gate_first=True)),
])
@pytest.mark.parametrize("interpret", [None, True], ids=["plain", "kernels"])
def test_kimis_and_nemotrons_gated_norm_calls_give_the_values_they_gave(
    who, how, interpret
):
    """kimi's call now names its activation (the row's default), nemotron's
    names none: both are the orders' published pairs, bit for bit the plain
    form's values, and the kernels' within a unit of bfloat16."""
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    wide = lambda key: jax.random.normal(key, (2, 128, 512)).astype(jnp.bfloat16)
    o, gate, x = wide(keys[0]), wide(keys[1]), wide(keys[2])
    scale = 1.0 + 0.3 * jax.random.normal(keys[3], (512,))
    skip = (x, 1.0 + 0.3 * jax.random.normal(keys[4], (512,))) if (
        who == "nemotron") else None
    want = _gated_norm_as_it_was(
        o, gate, scale, how["group"], 1e-5, how["gate_first"], skip
    ).astype(jnp.float32)
    got = gatenorm.gated_norm(
        o, gate, scale, eps=1e-5, skip=skip, interpret=interpret, **how
    ).astype(jnp.float32)
    if interpret is None:  # the same float32 expression, one rounding
        np.testing.assert_allclose(got, want, atol=2.0 ** -7 * float(
            jnp.max(jnp.abs(want))), rtol=0)
        assert float(jnp.mean(got != want)) < 0.01  # the sums' order alone
    else:
        np.testing.assert_allclose(
            got, want, atol=2.0 ** -7 * float(jnp.max(jnp.abs(want))), rtol=0
        )


def test_the_delta_mixers_norm_first_silu_is_neither_published_pair():
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    wide = lambda key: jax.random.normal(key, (1, 128, 256)).astype(jnp.bfloat16)
    o, gate = wide(keys[0]), wide(keys[1])
    scale = jnp.ones((256,))
    call = functools.partial(
        gatenorm.gated_norm, o, gate, scale, group=128, eps=1e-6,
        gate_first=False,
    )
    silu, sigmoid = call(act="silu"), call(act="sigmoid")
    assert not np.allclose(silu.astype(jnp.float32), sigmoid.astype(jnp.float32))
    np.testing.assert_array_equal(call(), sigmoid)
    want = (
        sigmoid.astype(jnp.float32) * gate.astype(jnp.float32)
    )  # silu(z) = z sigmoid(z)
    np.testing.assert_allclose(
        silu.astype(jnp.float32), want, atol=0.05, rtol=0.02
    )
    with pytest.raises(ValueError, match="activation 'tanh'"):
        call(act="tanh")
