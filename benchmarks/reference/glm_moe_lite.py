"""Plain reference for a GLM-4.7-Flash-style decoder (zai-org/GLM-4.7-Flash,
`model_type` `glm4_moe_lite`) WITH its multi-token module, and its training
step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no two-part or joined
operands, no low-precision storage, nothing imported from the program (the
helpers shared with `reference/lm.py` and `reference/zaya.py` — the int8
control's rounding, the learning rate, leaf norms — are the benchmark's own).

d = `hidden_size`, H = `num_attention_heads`, S the sequence, t_0 .. t_S the
S + 1 tokens of a row (`tokens` = t_0 .. t_(S-1), `labels` = t_1 .. t_S).
`n(x; w) = w x / sqrt(mean(x^2) + rms_norm_eps)`.

A layer: `x += attn(n(x; ln_attn))`, `x += ffn(n(x; ln_mlp))`; `ffn` of the
layers below `first_k_dense_replace` is a dense SwiGLU of
`intermediate_size`, of the others the expert layer.

**Latent attention** (the DeepSeek-V2/V3 model code's equations, whose keys
the configuration carries), on a sublayer's normed input u [S, d]: `c_q =
n(u W_qa; g_q)`, `W_qa` [d, q_lora_rank]; a head's `[q_n | q_r] = c_q W_qb`,
`W_qb` [q_lora_rank, H, qk_nope_head_dim + qk_rope_head_dim]; `[c | k_r] = u
W_kva`, `W_kva` [d, kv_lora_rank + qk_rope_head_dim]; `c_kv = n(c; g_kv)`; a
head's `[k_n | v] = c_kv W_kvb`, `W_kvb` [kv_lora_rank, H, qk_nope_head_dim
+ v_head_dim]: v is `v_head_dim` wide, which is NOT the own part's width
here (256 against 192). `k_r` is ONE key part for all heads. `q_r` and `k_r`
are turned by rope over all their `qk_rope_head_dim` dims, lane t with lane
t + half, frequencies `rope_theta^(-2t/r)`, no scaling (`rope_scaling`
null). `s_h[i, j] = (q_n,h[i] . k_n,h[j] + q_r,h[i] . k_r[j]) (nope +
rope)^-1/2`, causal softmax, `o_h = P_h v_h`; out = `[o_1 .. o_H] W_o`, `W_o`
[H, v_head_dim, d]. No bias.

**Experts**: `p = sigmoid(u W_r)` over `experts_routed`, the
`num_experts_per_tok` largest of `p + b` (`b` zero, no gradient, no update),
weights `routed_scaling_factor p_e / (sum of the chosen p + 1e-20)`, experts
`(silu(u W_g) * (u W_u)) W_d` of `moe_intermediate_size`, plus one shared
expert of `n_shared_experts x moe_intermediate_size` on the same input,
unweighted. Only `n_routed_experts` experts from `experts_first` on are held:
what the others would add is left out, as in the program; dense over the held
experts with a mask. With `cfg["router_force_balance"]` the chosen are the k
largest of standard normal scores from `PRNGKey(42)` folded with the layer's
index (`reference/zaya.py`'s docstring says why); the module's block folds
`num_hidden_layers`, the index after the last layer's.

**Main head**: `h = n(x_L; ln_final)`, `logits = h W_head^T` (untied);
`L_main = mean_i CE(logits_i, t_(i+1))`.

**The multi-token module** (DeepSeek-V3's report, arXiv 2412.19437 §2.2,
depth 1; leaves as the published checkpoints name them): for position i,
`e_i = Emb(t_(i+1))`, the SAME embedding matrix read at the label; `z_i =
[n(e_i; enorm) | n(h_i; hnorm)] W_eh`, `W_eh` [2d, d], the embedding's half
first, `h_i` the main stack's output AFTER its final norm; `y = Block(z)`,
one expert layer (latent attention + router + held experts + shared) with
parameters of its own, positions 0 .. S-1, causal; `logits'_i = n(y_i;
head_norm) W_head^T`, the SAME head; `L_mtp = (1 / (S - 1)) sum over i < S -
1 of CE(logits'_i, t_(i+2))`: the labels shifted by one more, the last
position masked. **`L = L_main + mtp_weight L_mtp`**.

**Assumed** (the configuration file lists the same): the concatenation's
order; which `h` the module reads; rope's pairing; the router's zero
correction; `mtp_weight`. Weights: normal, std 0.02 for the embedding,
1/sqrt(fan_in) for every matrix (`eh_proj`'s and the head's too), scales 1:
the plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.lm import _einsum, leaf_norms, learning_rate
from benchmarks.reference.zaya import _rms_norm

QUERY_BLOCK = 256  # 20 heads x 256 x 8192 float32 scores are 0.17 GB
FORCED_ROUTING_SEED = 42
MTP = "mtp.block."

# -- weights ---------------------------------------------------------------


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def _block_specs(cfg: dict, pre: str, dense: bool) -> dict[str, tuple]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held, routed = cfg["n_routed_experts"], cfg["experts_routed"]
    ff = cfg["moe_intermediate_size"]
    sff, wide = cfg["n_shared_experts"] * ff, cfg["intermediate_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    specs = {
        pre + "ln_attn": const((d,), 1.0),
        pre + "wq_a": mat((d, ql), d),
        pre + "q_norm": const((ql,), 1.0),
        pre + "wq_b": mat((ql, h, dn + dr), ql),
        pre + "wkv_a": mat((d, kvl + dr), d),
        pre + "kv_norm": const((kvl,), 1.0),
        pre + "wkv_b": mat((kvl, h, dn + dv), kvl),
        pre + "wo": mat((h, dv, d), h * dv),
        pre + "ln_mlp": const((d,), 1.0),
    }
    if dense:
        specs[pre + "mlp_gate"] = mat((d, wide), d)
        specs[pre + "mlp_up"] = mat((d, wide), d)
        specs[pre + "mlp_down"] = mat((wide, d), wide)
    else:
        specs[pre + "router"] = mat((d, routed), d)
        specs[pre + "router_bias"] = const((routed,), 0.0)
        specs[pre + "w_gate"] = mat((held, d, ff), d)
        specs[pre + "w_up"] = mat((held, d, ff), d)
        specs[pre + "w_down"] = mat((held, ff, d), ff)
        specs[pre + "shared_gate"] = mat((d, sff), d)
        specs[pre + "shared_up"] = mat((d, sff), d)
        specs[pre + "shared_down"] = mat((sff, d), sff)
    return specs


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, (std, mean)): the leaf is mean + std * normal."""
    if cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("this reference follows ONE multi-token module")
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i in range(cfg["num_hidden_layers"]):
        specs.update(_block_specs(cfg, f"layer.{i}.", is_dense(cfg, i)))
    specs["ln_final"] = const((d,), 1.0)
    specs["lm_head"] = mat((v, d), d)
    specs["mtp.enorm"] = const((d,), 1.0)
    specs["mtp.hnorm"] = const((d,), 1.0)
    specs["mtp.eh_proj"] = mat((2 * d, d), 2 * d)
    specs.update(_block_specs(cfg, MTP, dense=False))
    specs["mtp.head_norm"] = const((d,), 1.0)
    return specs


def init_leaf(key, index: int, shape, how):
    std, mean = how
    leaf = jnp.full(shape, mean, jnp.float32)
    if std:
        leaf = leaf + std * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32
        )
    return leaf


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, *spec)
        for i, (name, spec) in enumerate(param_specs(cfg).items())
    }


# -- the model ---------------------------------------------------------------


def _rope(x, theta: float):
    """x [B, S, ..., r], all of it turned: lane t with lane t + r/2."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angles = angles.reshape(1, x.shape[1], *(1,) * (x.ndim - 3), -1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q_n, q_r, k_n, k_r, v, scale: float, quant):
    """Causal softmax attention with two-part scores: q_n, k_n
    [B, S, H, dn], q_r [B, S, H, r], k_r [B, S, r] (broadcast over the
    heads), v [B, S, H, dv]; the whole row of scores under a mask, a block
    of queries at a time."""
    s = q_n.shape[1]
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} does not divide into blocks of {block}")
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qn_blk, qr_blk, start = args
        scores = scale * (
            _einsum("bqhk,bshk->bhqs", qn_blk, k_n, quant)
            + _einsum("bqhr,bsr->bhqs", qr_blk, k_r, quant)
        )
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("bhqs,bshk->bqhk", probs, v, quant)

    blocks = lambda u: jnp.moveaxis(
        u.reshape(u.shape[0], s // block, block, *u.shape[2:]), 1, 0
    )
    out = jax.lax.map(one, (blocks(q_n), blocks(q_r), jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(*q_n.shape[:3], v.shape[-1])


def attention_layer(u, p: dict, cfg: dict, quant=None):
    eps, dn = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"]
    kvl, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    c_q = _rms_norm(_einsum("bsd,dl->bsl", u, p["wq_a"], quant), p["q_norm"], eps)
    q = _einsum("bsl,lhk->bshk", c_q, p["wq_b"], quant)
    joint = _einsum("bsd,dl->bsl", u, p["wkv_a"], quant)
    c_kv = _rms_norm(joint[..., :kvl], p["kv_norm"], eps)
    kv = _einsum("bsl,lhk->bshk", c_kv, p["wkv_b"], quant)
    att = _attention(
        q[..., :dn], _rope(q[..., dn:], theta), kv[..., :dn],
        _rope(joint[..., kvl:], theta), kv[..., dn:],
        (dn + cfg["qk_rope_head_dim"]) ** -0.5, quant,
    )
    return _einsum("bqhk,hkd->bqd", att, p["wo"], quant)


def _swiglu(h, w_gate, w_up, w_down, quant):
    hidden = jax.nn.silu(_einsum("bsd,df->bsf", h, w_gate, quant)) * _einsum(
        "bsd,df->bsf", h, w_up, quant
    )
    return _einsum("bsf,fd->bsd", hidden, w_down, quant)


def forced_experts(layer: int, seq_len: int, routed: int, k: int):
    scores = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(FORCED_ROUTING_SEED), layer),
        (seq_len, routed), jnp.float32,
    )
    return jax.lax.top_k(scores, k)[1]


def route(h, p: dict, cfg: dict, layer: int, quant=None):
    """(expert [B, S, k], weight [B, S, k])."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.sigmoid(_einsum("bsd,de->bse", h, p["router"], quant))
    if cfg.get("router_force_balance"):
        expert = jnp.broadcast_to(
            forced_experts(layer, h.shape[1], probs.shape[-1], k),
            (*h.shape[:2], k),
        )
    else:
        _, expert = jax.lax.top_k(
            probs + jax.lax.stop_gradient(p["router_bias"]), k
        )
    chosen = jnp.take_along_axis(probs, expert, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return expert, cfg["routed_scaling_factor"] * chosen


def routed_experts(h, p: dict, cfg: dict, layer: int, quant=None):
    """What the experts held here add (no shared expert)."""
    expert, weight = route(h, p, cfg, layer, quant)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_gate, w_up, w_down, index = args
        out = _swiglu(h, w_gate, w_up, w_down, quant)
        mine = jnp.sum(jnp.where(expert == index, weight, 0.0), axis=-1)
        return acc + mine[..., None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"],
         cfg["experts_first"] + jnp.arange(held)),
    )
    return out


def expert_layer(h, p: dict, cfg: dict, layer: int, quant=None):
    return routed_experts(h, p, cfg, layer, quant) + _swiglu(
        h, p["shared_gate"], p["shared_up"], p["shared_down"], quant
    )


def layer(x, p: dict, cfg: dict, index: int, dense: bool, quant=None):
    eps = cfg["rms_norm_eps"]
    x = x + attention_layer(_rms_norm(x, p["ln_attn"], eps), p, cfg, quant)
    u = _rms_norm(x, p["ln_mlp"], eps)
    if dense:
        return x + _swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], quant)
    return x + expert_layer(u, p, cfg, index, quant)


def sub_params(params: dict, pre: str) -> dict:
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def both_logits(params: dict, tokens, labels, cfg: dict, quant=None):
    """(logits [B, S, V] of t_(i+1), the module's logits' [B, S, V] of
    t_(i+2)): the docstring's equations."""
    eps, n = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    # Save only each layer's input for the backward pass: memory, not
    # arithmetic.
    run = lambda x, pre, i, dense: jax.checkpoint(
        lambda x, p: layer(x, p, cfg, i, dense, quant)
    )(x, sub_params(params, pre))
    x = params["embedding"][tokens]
    for i in range(n):
        x = run(x, f"layer.{i}.", i, is_dense(cfg, i))
    h = _rms_norm(x, params["ln_final"], eps)
    logits = _einsum("bsd,vd->bsv", h, params["lm_head"], quant)
    e = params["embedding"][labels]
    z = _einsum(
        "bsk,kd->bsd",
        jnp.concatenate([
            _rms_norm(e, params["mtp.enorm"], eps),
            _rms_norm(h, params["mtp.hnorm"], eps),
        ], axis=-1),
        params["mtp.eh_proj"], quant,
    )
    y = _rms_norm(run(z, MTP, n, False), params["mtp.head_norm"], eps)
    return logits, _einsum("bsd,vd->bsv", y, params["lm_head"], quant)


def _summed_ce(z, labels):
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(log_z - picked)


def summed_losses(params: dict, tokens, labels, cfg: dict, quant=None):
    """(sum over a block's rows' positions of the objective, (of the main
    cross entropy, of the module's over i < S - 1)): the first over B S is
    L, so a position of the module's counts S / (S - 1) in it."""
    s = tokens.shape[1]
    logits, further = both_logits(params, tokens, labels, cfg, quant)
    main = _summed_ce(logits, labels)
    mtp = _summed_ce(further[:, :-1], labels[:, 1:])
    return main + cfg["mtp_weight"] * mtp * s / (s - 1), (main, mtp)


def losses(params: dict, tokens, labels, cfg: dict, quant=None):
    """(L, L_main, L_mtp) of a batch."""
    total, (main, mtp) = summed_losses(params, tokens, labels, cfg, quant)
    n = tokens.size
    return total / n, main / n, mtp / (n - tokens.shape[0])


# -- the training step -------------------------------------------------------


def follow(key, cfg: dict, opt: dict, batches, *, rows_per_block=None, quant=None):
    """Train from the seeded weights over `batches` (each `{"tokens",
    "labels"}`) and report, as plain numbers, what the comparison reads:
    every step's `loss` (L), `main_loss` and `mtp_loss`, the norm of the
    first gradient of L by leaf, and the norm of the parameters' change
    over all the steps by leaf. `reference/zaya.follow`'s step (the gradient
    summed over blocks of `rows_per_block` rows, AdamW at optax's defaults
    leaf by leaf, both moments waiting on the host: placement, not
    arithmetic), over this module's two losses."""
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, opt["weight_decay"]
    seeded = lambda k: init_params(k, cfg)
    params = jax.jit(seeded)(key)

    @jax.jit
    def grad_block(p, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(summed_losses, has_aux=True)(
                p, tokens, labels, cfg, quant
            )

    keep = lambda tree: {k: np.asarray(x) for k, x in tree.items()}
    add = lambda a, b: {k: a[k] + np.asarray(b[k]) for k in a}

    @functools.partial(jax.jit, donate_argnums=0)
    def mean_and_norms(summed, n_tok):
        mean = {k: x / n_tok for k, x in summed.items()}
        return mean, leaf_norms(mean)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, lr, count):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps)
        return p - lr * (step + wd * p), m, v

    m = {k: np.zeros(x.shape, np.float32) for k, x in params.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in params.items()}
    out = {"loss": [], "main_loss": [], "mtp_loss": []}
    first = None
    for i, batch in enumerate(batches):
        rows, seq = batch["tokens"].shape
        per = rows_per_block or rows
        sums, grads = np.zeros(3), None
        for start in range(0, rows, per):
            (total, (main, mtp)), g = grad_block(
                params, batch["tokens"][start:start + per],
                batch["labels"][start:start + per],
            )
            sums += [float(total), float(main), float(mtp)]
            if start + per < rows:  # more to come: this block's waits
                g = keep(g)
            grads = g if grads is None else add(grads, g)
            del g
        n_tok = rows * seq
        grads, norms = mean_and_norms(
            {k: jnp.asarray(x) for k, x in grads.items()}, jnp.float32(n_tok)
        )
        out["loss"].append(sums[0] / n_tok)
        out["main_loss"].append(sums[1] / n_tok)
        out["mtp_loss"].append(sums[2] / (n_tok - rows))
        first = norms if first is None else first
        lr, count = jnp.float32(learning_rate(i, opt)), jnp.float32(i + 1)
        for name in list(params):
            params[name], new_m, new_v = update(
                params[name], grads.pop(name), jnp.asarray(m[name]),
                jnp.asarray(v[name]), lr, count,
            )
            m.update(keep({name: new_m}))
            v.update(keep({name: new_v}))
    del m, v
    change = jax.jit(lambda p, k: leaf_norms(
        {name: p[name] - leaf for name, leaf in seeded(k).items()}
    ))(params, key)
    plain = lambda tree: {k: float(n) for k, n in tree.items()}
    return {
        **out, "first_grad_norm": plain(first), "change_norm": plain(change),
    }
