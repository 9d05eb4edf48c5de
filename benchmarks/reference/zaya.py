"""Plain reference for a ZAYA1-style decoder and its training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no sort, no mixed
precision, nothing imported from the program (the few helpers shared with
`reference/lm.py` — the int8 control's rounding, the learning rate, leaf
norms — are the benchmark's own). One layer is a CCA attention sublayer and
an expert sublayer; with `h = RMSNorm(x)`, `d` the head size, `t` the
position and everything before the first token zero:

*CCA.* `q~ = h W_q` (H heads), `k~ = h W_k` (Hkv heads), `v = h W_v` with
the second half of the kv heads taken from the token before. q~ and k~ go
through two causal convolutions along the sequence, `Conv0` depthwise
(kernel `cca_time0`), `Conv1` grouped by head (kernel `cca_time1`: a head's
channels mix among themselves); tap j multiplies the value j tokens back.
`q' = Conv(q~) + (q~ + rep(k~)) / 2`, `k' = Conv(k~) + (mean(q~) + k~) / 2`
(`rep` repeats a kv head over its H/Hkv query heads, `mean` averages them).
`q^ = sqrt(d) q' / |q'|`, `k^ = tau sqrt(d) k' / |k'|` per head, rope on the
first `partial_rotary_factor` of a head's dims, then causal softmax
attention at scale 1/sqrt(d) with H/Hkv query heads to a kv head, and
`x <- x + o W_o`.

*Router.* `r_l = h W_r + gamma_l * r_{l-1}` (`r_0 = 0`), `p = softmax(W3
gelu(W2 gelu(W1 RMSNorm(r_l))))`, `e = argmax p`, gate `g = p_e`.

With `cfg["router_force_balance"]` (the cell's workload sets it) e is not
the argmax of p: it is the argmax over experts of standard normal scores
drawn for (position, expert) from `PRNGKey(42)` folded with the layer's
index, the same for every row, step and run; g is still `p_e`.

*Experts.* `x <- x + g (silu(h W_gate,e) * (h W_up,e)) W_down,e` for the
tokens whose e is one of the `num_experts` held here (from
`experts_first` on; the router scores all `experts_routed`), `x <- x` for
the others: what the absent experts would add is left out, as in the
program. Computed densely over the held experts with a mask.

**Assumed** (what `config.json` does not fix; the configuration file lists
the same): no biases anywhere; the convolutions have no bias and no
activation and are separate for q and k (both are per head, so "on the 10
heads together" is the same thing); the L2 norm's epsilon is
`rms_norm_eps` on the mean square; tau is one learned scalar a kv head,
1 at the seed; gamma is a learned vector, 0.5 at the seed, present in the
first layer too where it multiplies the zero state; gelu is the tanh
approximation; the router's norm has a learned scale; no balancing bias
and no auxiliary loss; the expert sublayer reads the same `h` for the
router and the experts; rope is the half-split rotation over the turned
dims. Weights: normal, std 0.02 for the embedding and 1/sqrt(fan_in) for
every matrix and convolution, scales 1: the same plain draw as
`reference/lm.py`'s, and nothing shaped to steer the routing.

**Why the cell forces the selection.** config.json names no balancing
term, so none is assumed, and a dropless layer's work follows its tokens.
An untrained router is far from even on uniform random tokens (my chip
runs and CPU runs at the cell's size, PR 28): attention over thousands of
random tokens hands every late token nearly the same vector and the
experts' outputs swamp the embedding, so from the third layer on a
layer's routers see nearly the same input for every token and a held
share of 0.01 to 0.97 a layer; AdamW's first steps then move it on. The
rate followed the seed's routing, 1.4 % between seeds, which no
benchmark can admit. Neither a better-scaled draw, nor router logits
standardised over the batch, nor a selection bias solved on every batch
evens it out (the bias cannot split tokens whose scores coincide). So
the cell does what Megatron-Core's `--moe-router-force-load-balancing`
does for the same purpose: the selection is drawn evenly at random, by
position and layer and not from `--seed`, every expert gets about 1,024
of a step's 16,384 tokens in every run, and the router, the gate and all
the rest are the model's own.

`follow(...)` is the training reference: AdamW as `reference/lm.py` has it,
over the first steps of the run. Its state is 16 bytes a parameter where
the program's is 10, so both Adam moments wait in host memory and the
update goes leaf by leaf: the arithmetic is the same. Attention is
computed a block of queries at a time and an expert at a time, each
recomputed in the backward pass: memory, not arithmetic.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.lm import _einsum, leaf_norms, learning_rate

QUERY_BLOCK = 1024

# -- weights ---------------------------------------------------------------


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, std, mean): the leaf is mean + std * normal."""
    d, h, hk = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, rh = cfg["head_dim"], cfg["moe_intermediate_size"], cfg["router_hidden_size"]
    held, routed, v = cfg["num_experts"], cfg["experts_routed"], cfg["vocab_size"]
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    mat = lambda shape, fan_in: (shape, 1 / math.sqrt(fan_in), 0.0)
    const = lambda shape, value: (shape, 0.0, value)
    specs = {"embedding": ((v, d), 0.02, 0.0)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        specs[p + "ln_attn"] = const((d,), 1.0)
        specs[p + "wq"] = mat((d, h, hd), d)
        specs[p + "wk"] = mat((d, hk, hd), d)
        specs[p + "wv"] = mat((d, hk, hd), d)
        specs[p + "conv0_q"] = mat((k0, h, hd), k0)
        specs[p + "conv0_k"] = mat((k0, hk, hd), k0)
        specs[p + "conv1_q"] = mat((k1, h, hd, hd), k1 * hd)
        specs[p + "conv1_k"] = mat((k1, hk, hd, hd), k1 * hd)
        specs[p + "tau"] = const((hk,), 1.0)
        specs[p + "wo"] = mat((h, hd, d), h * hd)
        specs[p + "ln_moe"] = const((d,), 1.0)
        specs[p + "router_in"] = mat((d, rh), d)
        specs[p + "router_carry"] = const((rh,), 0.5)
        specs[p + "router_norm"] = const((rh,), 1.0)
        specs[p + "router_w1"] = mat((rh, rh), rh)
        specs[p + "router_w2"] = mat((rh, rh), rh)
        specs[p + "router_out"] = mat((rh, routed), rh)
        specs[p + "w_gate"] = mat((held, d, ff), d)
        specs[p + "w_up"] = mat((held, d, ff), d)
        specs[p + "w_down"] = mat((held, ff, d), ff)
    specs["ln_final"] = const((d,), 1.0)
    return specs


def init_leaf(key, index: int, shape, std: float, mean: float):
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


def layer_leaves(cfg: dict) -> list[str]:
    return [
        k.split(".", 2)[2] for k in param_specs(cfg) if k.startswith("layers.0.")
    ]


def stack_layers(flat: dict, cfg: dict) -> dict:
    """`layers.<i>.<leaf>` for every i -> one `layers.<leaf>` with a leading
    layer axis: the layers are then one scan."""
    out = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    for leaf in layer_leaves(cfg):
        out["layers." + leaf] = jnp.stack([
            flat[f"layers.{i}.{leaf}"] for i in range(cfg["num_hidden_layers"])
        ])
    return out


def by_layer(stacked: dict, cfg: dict) -> dict:
    """A per-leaf number of stacked leaves back under the flat names."""
    out = {}
    for k, v in stacked.items():
        if k.startswith("layers."):
            for i in range(cfg["num_hidden_layers"]):
                out[f"layers.{i}.{k.split('.', 1)[1]}"] = v[i]
        else:
            out[k] = v
    return out


# -- the model ---------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _shift(x, steps: int):
    """x[t - steps] at position t (axis 1), zeros before the first token."""
    if steps == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def _rope(x, theta: float, share: float):
    """x: [B, S, H, D]; the half-split rotation of the first share * D
    dims, positions 0..S-1."""
    turned = int(x.shape[-1] * share)
    freqs = 1.0 / theta ** (jnp.arange(0, turned, 2, dtype=jnp.float32) / turned)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x[..., :turned], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., turned:]], axis=-1
    )


def _conv_mix(u, w0, w1, quant):
    c0 = sum(w0[j] * _shift(u, j) for j in range(w0.shape[0]))
    return sum(
        _einsum("bshd,hde->bshe", _shift(c0, j), w1[j], quant)
        for j in range(w1.shape[0])
    )


def _attention(q, k, v, quant):
    """Causal softmax attention, q [B, S, H, d] over k, v [B, S, H, d], a
    block of queries at a time."""
    s, hd = q.shape[1], q.shape[-1]
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} does not divide into blocks of {block}")
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        q_blk, start = args
        scores = _einsum("bqhk,bshk->bhqs", q_blk, k, quant) / math.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("bhqs,bshk->bqhk", probs, v, quant)

    blocks = q.reshape(q.shape[0], s // block, block, *q.shape[2:])
    out = jax.lax.map(
        one, (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, s, block))
    )
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _layer(carry, p: dict, cfg: dict, quant):
    x, r_prev = carry
    eps = cfg["rms_norm_eps"]
    hk = cfg["num_key_value_heads"]
    group = cfg["num_attention_heads"] // hk
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + eps
    )

    h = _rms_norm(x, p["ln_attn"], eps)
    q = _einsum("bsd,dhk->bshk", h, p["wq"], quant)
    k = _einsum("bsd,dhk->bshk", h, p["wk"], quant)
    v = _einsum("bsd,dhk->bshk", h, p["wv"], quant)
    q_mean = q.reshape(*q.shape[:2], hk, group, -1).mean(axis=3)
    q_mix = _conv_mix(q, p["conv0_q"], p["conv1_q"], quant) + 0.5 * (
        q + jnp.repeat(k, group, axis=2)
    )
    k_mix = _conv_mix(k, p["conv0_k"], p["conv1_k"], quant) + 0.5 * (q_mean + k)
    share = cfg["partial_rotary_factor"]
    q_hat = _rope(unit(q_mix), cfg["rope_theta"], share)
    k_hat = _rope(unit(k_mix) * p["tau"][:, None], cfg["rope_theta"], share)
    now = hk - hk // 2
    v = jnp.concatenate([v[:, :, :now], _shift(v[:, :, now:], 1)], axis=2)
    att = _attention(
        q_hat, jnp.repeat(k_hat, group, axis=2), jnp.repeat(v, group, axis=2),
        quant,
    )
    x = x + _einsum("bqhk,hkd->bqd", att, p["wo"], quant)

    h = _rms_norm(x, p["ln_moe"], eps)
    r = _einsum("bsd,dr->bsr", h, p["router_in"], quant) + p["router_carry"] * r_prev
    z = _rms_norm(r, p["router_norm"], eps)
    z = jax.nn.gelu(_einsum("bsr,rt->bst", z, p["router_w1"], quant))
    z = jax.nn.gelu(_einsum("bsr,rt->bst", z, p["router_w2"], quant))
    probs = jax.nn.softmax(
        _einsum("bsr,re->bse", z, p["router_out"], quant), axis=-1
    )
    if cfg.get("router_force_balance"):
        scores = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(42), p["index"]),
            (x.shape[1], probs.shape[-1]), jnp.float32,
        )
        expert = jnp.broadcast_to(jnp.argmax(scores, axis=-1), x.shape[:2])
    else:
        expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[..., None], axis=-1)[..., 0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_gate, w_up, w_down, index = args
        hidden = jax.nn.silu(_einsum("bsd,df->bsf", h, w_gate, quant)) * _einsum(
            "bsd,df->bsf", h, w_up, quant
        )
        out = _einsum("bsf,fd->bsd", hidden, w_down, quant)
        return acc + jnp.where((expert == index)[..., None], out, 0.0), None

    held = p["w_gate"].shape[0]
    added, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["w_gate"], p["w_up"], p["w_down"],
         cfg["experts_first"] + jnp.arange(held)),
    )
    return (x + gate[..., None] * added, r), expert


def _run_layers(params: dict, tokens, cfg: dict, quant):
    x = params["embedding"][tokens]
    layers = {
        k.split(".", 1)[1]: v for k, v in params.items() if k.startswith("layers.")
    }
    layers["index"] = jnp.arange(cfg["num_hidden_layers"])
    # Save only each layer's input for the backward pass: memory, not
    # arithmetic (the recomputed values are the same float32 values).
    body = jax.checkpoint(functools.partial(_layer, cfg=cfg, quant=quant))
    state = jnp.zeros((*tokens.shape, cfg["router_hidden_size"]), jnp.float32)
    (x, _), experts = jax.lax.scan(body, (x, state), layers)
    return _rms_norm(x, params["ln_final"], cfg["rms_norm_eps"]), experts


def logits(params: dict, tokens, cfg: dict, quant=None):
    """`params` with stacked layers (`stack_layers`)."""
    x, _ = _run_layers(params, tokens, cfg, quant)
    return _einsum("bsd,vd->bsv", x, params["embedding"], quant)


def routing(params: dict, tokens, cfg: dict):
    """[layers, B, S]: the expert every layer's router chose."""
    return _run_layers(params, tokens, cfg, None)[1]


def summed_loss(params: dict, tokens, labels, cfg: dict, quant=None):
    """Sum over tokens of the next-token cross entropy (divide by the count)."""
    z = logits(params, tokens, cfg, quant)
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(log_z - picked)


# -- the training step -------------------------------------------------------


def follow(
    key, cfg: dict, opt: dict, batches, *, rows_per_block: int | None = None,
    quant=None, model=None,
) -> dict:
    """Train from the seeded weights over `batches` (each `{"tokens",
    "labels"}`) and report, as plain numbers, what the comparison reads:
    every step's loss, the norm of the first gradient by leaf, and the norm
    of the parameters' change over all the steps by leaf.

    The gradient is summed over blocks of `rows_per_block` rows (all rows
    in one block by default); AdamW (optax's defaults, decay on every
    leaf) then updates leaf by leaf. The moments, and the running sum of
    the blocks' gradients, wait in host memory: placement, not arithmetic.
    `model` is the module whose `init_params`, `stack_layers`, `by_layer`
    and `summed_loss` are followed: this one, or `reference/lm_long.py` for
    a dense decoder whose own `follow` does not fit beside a long sequence.
    """
    model = model or sys.modules[__name__]
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, opt["weight_decay"]
    seeded = lambda k: model.stack_layers(model.init_params(k, cfg), cfg)
    params = jax.jit(seeded)(key)

    @jax.jit
    def grad_block(p, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(model.summed_loss)(
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
    losses, first = [], None
    for i, batch in enumerate(batches):
        rows = batch["tokens"].shape[0]
        per = rows_per_block or rows
        total, grads = 0.0, None
        for start in range(0, rows, per):
            loss, g = grad_block(
                params, batch["tokens"][start:start + per],
                batch["labels"][start:start + per],
            )
            total += float(loss)
            if start + per < rows:  # more to come: this block's waits
                g = keep(g)
            grads = g if grads is None else add(grads, g)
            del g
        n_tok = batch["tokens"].size
        grads, norms = mean_and_norms(
            {k: jnp.asarray(x) for k, x in grads.items()}, jnp.float32(n_tok)
        )
        losses.append(total / n_tok)
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
    plain = lambda tree: {
        k: float(n) for k, n in model.by_layer(tree, cfg).items()
    }
    return {
        "loss": losses, "first_grad_norm": plain(first),
        "change_norm": plain(change),
    }
