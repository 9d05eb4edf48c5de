"""Plain reference for a decoder-only language model and its training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no mixed precision,
no sharding rules, nothing imported from the program. It follows the
published OLMo-1B block (pre-norm, rotary attention over all heads, SwiGLU,
tied output head, no biases) with one stated departure: the norm is an
RMSNorm with a learned scale, as the program's is, where OLMo-1B has a
non-parametric LayerNorm (`assumed` in the configuration files).

It also owns the seeded weights. `init_params(key, cfg)` makes every leaf
from the key and the leaf's index alone, so the driver can hand the same
numbers to the program and this file can make them again later, when the
program's state is gone: the reference takes nothing the program made.

`follow(...)` is the training reference: AdamW (optax's defaults, decay on
every leaf, a linear warm-up from 0) over the first steps of the run, in
blocks of rows so that it fits beside nothing. `quant` puts a lower
precision in every matmul, forward and backward — the control that the
comparison has to fail (`benchmarks/README.md`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# -- weights ---------------------------------------------------------------


def param_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """name -> (shape, std); std None means a vector of ones (norm scales).

    Standard deviations are the program's own initializers': 0.02 for the
    embedding, 1/sqrt(fan_in) for every matrix.
    """
    d, h, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    specs: dict[str, tuple[tuple[int, ...], float | None]] = {
        "embedding": ((v, d), 0.02)
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs[p + "ln_attn"] = ((d,), None)
        for w in ("wq", "wk", "wv"):
            specs[p + w] = ((d, h, hd), 1 / math.sqrt(d))
        specs[p + "wo"] = ((h, hd, d), 1 / math.sqrt(h * hd))
        specs[p + "ln_mlp"] = ((d,), None)
        specs[p + "w_gate"] = ((d, ff), 1 / math.sqrt(d))
        specs[p + "w_up"] = ((d, ff), 1 / math.sqrt(d))
        specs[p + "w_down"] = ((ff, d), 1 / math.sqrt(ff))
    specs["ln_final"] = ((d,), None)
    return specs


def init_leaf(key, index: int, shape, std):
    if std is None:
        return jnp.ones(shape, jnp.float32)
    return std * jax.random.normal(
        jax.random.fold_in(key, index), shape, jnp.float32
    )


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, shape, std)
        for i, (name, (shape, std)) in enumerate(param_specs(cfg).items())
    }


# -- lower precision, for the control --------------------------------------


def int8_quant(x):
    """Symmetric per-tensor int8: the nearest precision below bf16 that
    this chip multiplies in (393 TOP/s int8 on a v5e)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _quant_forward(x, quant):
    """q(x) in the forward pass, the gradient passed straight through."""
    return x + jax.lax.stop_gradient(quant(x) - x)


def _quant_backward(quant):
    @jax.custom_vjp
    def ident(y):
        return y

    ident.defvjp(lambda y: (y, None), lambda _, g: (quant(g),))
    return ident


def _einsum(spec: str, a, b, quant):
    """`einsum` at full precision, or with both operands — and, on the way
    back, the cotangent — rounded by `quant`."""
    if quant is None:
        return jnp.einsum(spec, a, b)
    out = jnp.einsum(spec, _quant_forward(a, quant), _quant_forward(b, quant))
    return _quant_backward(quant)(out)


# -- the model ---------------------------------------------------------------


def _rms_norm(x, scale, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x: [B, S, H, D]; the half-split rotation, positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(x, p: dict, cfg: dict, quant):
    hd = cfg["head_dim"]
    y = _rms_norm(x, p["ln_attn"])
    q = _rope(_einsum("bsd,dhk->bshk", y, p["wq"], quant), cfg["rope_theta"])
    k = _rope(_einsum("bsd,dhk->bshk", y, p["wk"], quant), cfg["rope_theta"])
    v = _einsum("bsd,dhk->bshk", y, p["wv"], quant)
    scores = _einsum("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(hd)
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = _einsum("bhqs,bshk->bqhk", probs, v, quant)
    x = x + _einsum("bqhk,hkd->bqd", att, p["wo"], quant)
    y = _rms_norm(x, p["ln_mlp"])
    gate = _einsum("bsd,df->bsf", y, p["w_gate"], quant)
    up = _einsum("bsd,df->bsf", y, p["w_up"], quant)
    return x + _einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"], quant)


LAYER_LEAVES = (
    "ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up", "w_down",
)


def stack_layers(flat: dict, cfg: dict) -> dict:
    """`layers.<i>.<leaf>` for every i -> one `layers.<leaf>` with a leading
    layer axis: the layers are then one scan, built once."""
    out = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    for leaf in LAYER_LEAVES:
        out["layers." + leaf] = jnp.stack([
            flat[f"layers.{i}.{leaf}"] for i in range(cfg["num_hidden_layers"])
        ])
    return out


def by_layer(stacked: dict, cfg: dict) -> dict:
    """A per-leaf number of stacked leaves (`layers.<leaf>`: a vector over
    layers) back under the flat names `layers.<i>.<leaf>`."""
    out = {}
    for k, v in stacked.items():
        if k.startswith("layers."):
            leaf = k.split(".", 1)[1]
            for i in range(cfg["num_hidden_layers"]):
                out[f"layers.{i}.{leaf}"] = v[i]
        else:
            out[k] = v
    return out


def leaf_norms(stacked: dict) -> dict:
    """The norm of every leaf; of a stacked leaf, of each layer's slice."""
    return {
        k: jnp.sqrt(jnp.sum(
            jnp.square(v),
            axis=tuple(range(1, v.ndim)) if k.startswith("layers.") else None,
        ))
        for k, v in stacked.items()
    }


def logits(params: dict, tokens, cfg: dict, quant=None):
    """`params` with stacked layers (`stack_layers`)."""
    x = params["embedding"][tokens]
    layers = {
        k.split(".", 1)[1]: v for k, v in params.items()
        if k.startswith("layers.")
    }
    # Save only each layer's input for the backward pass: memory, not
    # arithmetic (the recomputed values are the same float32 values).
    body = jax.checkpoint(functools.partial(_layer, cfg=cfg, quant=quant))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, layers)
    x = _rms_norm(x, params["ln_final"])
    return _einsum("bsd,vd->bsv", x, params["embedding"], quant)


def summed_loss(params: dict, tokens, labels, cfg: dict, quant=None):
    """Sum over tokens of the next-token cross entropy (divide by the count)."""
    z = logits(params, tokens, cfg, quant)
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(log_z - picked)


# -- the training step -------------------------------------------------------


def learning_rate(count: int, opt: dict) -> float:
    """optax.warmup_cosine_decay_schedule from 0, at update number `count`."""
    warm, peak = opt["warmup_steps"], opt["learning_rate"]
    if count < warm:
        return peak * count / warm
    decay = max(opt["schedule_steps"], warm + 1) - warm
    frac = min(count - warm, decay) / decay
    return peak * 0.5 * (1 + math.cos(math.pi * frac))


def make_step(
    cfg: dict, opt: dict, *, rows_per_block: int = 1, quant=None,
    place=None, place_rows=None,
):
    """One training step of the reference as a jitted function
    `(params, m, v, tokens[B, S], labels, lr, count) ->
    (params, m, v, loss, gradient's norm by leaf)`, the three trees with
    stacked layers (`stack_layers`) and given up to the call.

    The gradient is summed over blocks of `rows_per_block` rows (a scan, so
    that one block's activations are alive at a time), the layers are a
    scan over their stacked leaves, then comes the AdamW update.
    `place(tree)` may constrain where a tree of leaves lives and
    `place_rows(x)` where a block's rows do (the four-chip cell spreads
    both over its chips, for room and time); the arithmetic is the same.
    """
    place = place or (lambda tree: tree)
    place_rows = place_rows or (lambda x: x)
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, opt["weight_decay"]

    def gradient(params, tokens, labels):
        rows, n_tok = tokens.shape[0], tokens.shape[0] * tokens.shape[1]
        blocks = (
            tokens.reshape(rows // rows_per_block, rows_per_block, -1),
            labels.reshape(rows // rows_per_block, rows_per_block, -1),
        )

        def block(carry, xs):
            acc, total = carry
            loss, g = jax.value_and_grad(summed_loss)(
                params, place_rows(xs[0]), place_rows(xs[1]), cfg, quant
            )
            return (jax.tree_util.tree_map(jnp.add, acc, g), total + loss), None

        zeros = place(jax.tree_util.tree_map(jnp.zeros_like, params))
        (acc, total), _ = jax.lax.scan(block, (zeros, jnp.float32(0)), blocks)
        return total / n_tok, {k: g / n_tok for k, g in acc.items()}

    def step(params, m, v, tokens, labels, lr, count):
        with jax.default_matmul_precision("highest"):
            loss, grads = gradient(params, tokens, labels)
        new_p, new_m, new_v, norms = {}, {}, {}, leaf_norms(grads)
        for k, p in params.items():
            g = grads[k]
            new_m[k] = b1 * m[k] + (1 - b1) * g
            new_v[k] = b2 * v[k] + (1 - b2) * g * g
            m_hat = new_m[k] / (1 - b1 ** count)
            v_hat = new_v[k] / (1 - b2 ** count)
            new_p[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
        return place(new_p), place(new_m), place(new_v), loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def follow(
    key, cfg: dict, opt: dict, batches, *, place=None, **how
) -> dict:
    """Train from the seeded weights over `batches` (each `{"tokens",
    "labels"}`, whole arrays) and report, as plain numbers, what the
    comparison reads: every step's loss, the norm of the first gradient by
    leaf, and the norm of the parameters' change over all the steps by leaf.

    The step is compiled once, ahead of time, and called for every batch.
    """
    hold = place or (lambda tree: tree)
    seeded = jax.jit(lambda k: hold(stack_layers(init_params(k, cfg), cfg)))
    params = seeded(key)
    zeros = jax.jit(lambda p: hold(jax.tree_util.tree_map(jnp.zeros_like, p)))
    m, v = zeros(params), zeros(params)
    lrs = [jnp.float32(learning_rate(i, opt)) for i in range(len(batches))]
    step = make_step(cfg, opt, place=place, **how).lower(
        params, m, v, batches[0]["tokens"], batches[0]["labels"], lrs[0],
        jnp.float32(1),
    ).compile()
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, m, v, loss, norms = step(
            params, m, v, batch["tokens"], batch["labels"], lrs[i],
            jnp.float32(i + 1),
        )
        losses.append(float(loss))
        first = norms if first is None else first
    del m, v
    change = jax.jit(lambda p, k: leaf_norms(
        {name: p[name] - leaf for name, leaf in
         stack_layers(init_params(k, cfg), cfg).items()}
    ))(params, key)
    plain = lambda tree: {k: float(n) for k, n in by_layer(tree, cfg).items()}
    return {
        "loss": losses, "first_grad_norm": plain(first),
        "change_norm": plain(change),
    }
