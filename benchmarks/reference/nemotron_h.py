"""Plain reference for a Nemotron-H-style decoder (Nemotron-3-Super) and its
training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no chunked scan, no
sort, no mixed precision, nothing imported from the program (the helpers
shared with `reference/lm.py` and `reference/zaya.py` — the int8 control's
rounding, attention a block of queries at a time, the AdamW step that keeps
its moments on the host — are the benchmark's own).

The stack is `hybrid_override_pattern`, one letter a layer. Every layer is
`x <- x + f(RMSNorm(x))` (`layer_norm_epsilon`, a learned scale), f by the
letter; after the last a final RMSNorm and an untied head; the loss is the
mean cross-entropy of the next token. With `h = RMSNorm(x)` [S, D]:

*`M`, Mamba-2.* H heads of P channels, G groups, a state of N, `d_in = H P`.
`[z | xBC | dt] = h W_in`, `W_in` [D, 2 d_in + 2 G N + H], no bias.
`xBC <- silu(conv(xBC))`, `conv(u)_t = sum_j w_j u_(t-j) + bias`, depthwise
and causal, `conv_kernel` taps. Split x [S, H, P], B, C [S, G, N].
`dt <- softplus(dt + dt_bias)` [S, H], `a = -exp(A_log)` [H]. Head h (group
g = h // (H/G)), state s [P, N], `s_0 = 0`:
`s_t = exp(dt_t a) s_(t-1) + dt_t x_t (x) B_t`, `y_t = s_t C_t + D_h x_t`.
Then `y <- RMSNorm_group(y * silu(z))` over each group's `d_in / G` channels
with a learned scale, and `out = y W_out`. The recurrence is NOT computed
in chunks here: it is the masked form over the whole sequence,
`y_t = sum_(s<=t) (C_t . B_s) exp(sum_(s<r<=t) dt_r a) dt_s x_s + D x_t`,
a block of query rows at a time, the exponent's sums taken from the
block's first row outwards so that nearby positions lose no precision to
a sequence-long running sum.

*`E`, the latent expert layer.* `p = sigmoid(h W_r)` over `experts_routed`;
chosen = the `num_experts_per_tok` largest of `p + b` (`b` zeros at the
seed, no gradient); weights `w_e = routed_scaling_factor p_e / (sum over
the chosen of p + 1e-20)` (`norm_topk_prob`). `l = h W_latent_in`
[D, moe_latent_size]; expert e is `relu(l W1_e)^2 W2_e`;
`out = (sum over the chosen AND held e of w_e E_e(l)) W_latent_out +
relu(h Ws1)^2 Ws2`. Only `n_routed_experts` experts from `experts_first`
on are held: what the others would add is left out, as in the program.
Dense over the held experts with a mask. With `cfg["router_force_balance"]`
the chosen are not the router's: they are the k largest of standard normal
scores drawn for (position, expert) from `PRNGKey(42)` folded with the
layer's index, the same for every row, step and run; the weights are
still the router's p (why: `reference/zaya.py`'s docstring).

*`*`, attention.* q [S, Hq hd], k, v [S, Hkv hd], causal softmax at
1/sqrt(hd), query head j over K/V head j // (Hq/Hkv), no bias, no rotation.

**Assumed** (what `config.json` does not fix; the configuration file lists
the same): no rotary embedding in attention (`config.json` carries
`rope_theta`; the family's published model code applies none); the
convolution's tap order and its bias; `A_log` = log of 1..16 spread evenly
over the heads held, `D` ones, `dt_bias` the inverse softplus of steps
drawn log-uniformly in [`time_step_min`, `time_step_max`] and floored at
`time_step_floor`; the gated norm's epsilon is `layer_norm_epsilon`; the
1e-20 in the weights' normalisation; the correction `b` is present, zero
and never updated (`config.json` names no rule for it); `n_group` =
`topk_group` = 1 mean no group-limited choice; `rescale_prenorm_residual`
is an initialisation detail and not followed. Weights: normal, std 0.02 for
the embedding, 1/sqrt(fan_in) for every matrix (the head's too) and the
convolution, scales 1: the plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.reference import zaya as _zaya
from benchmarks.reference.lm import _einsum
from benchmarks.reference.zaya import _attention, _rms_norm, _shift

SCAN_QUERY_BLOCK = 256
FORCED_ROUTING_SEED = 42

# -- weights ---------------------------------------------------------------


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, how): `how` is (std, mean) for mean + std * normal,
    or the name of a special draw (`init_leaf`)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    d_in, taps = h * p, cfg["conv_kernel"]
    hq, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held, routed = cfg["n_routed_experts"], cfg["experts_routed"]
    lat, ff = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    sff = cfg["moe_shared_expert_intermediate_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"layer.{i}."
        specs[pre + "ln"] = const((d,), 1.0)
        if kind == "M":
            specs[pre + "in_proj"] = mat((d, 2 * d_in + 2 * gn + h), d)
            specs[pre + "conv_kernel"] = mat((taps, d_in + 2 * gn), taps)
            specs[pre + "conv_bias"] = const((d_in + 2 * gn,), 0.0)
            specs[pre + "dt_bias"] = ((h,), "dt_bias")
            specs[pre + "A_log"] = ((h,), "A_log")
            specs[pre + "D"] = const((h,), 1.0)
            specs[pre + "norm_scale"] = const((d_in,), 1.0)
            specs[pre + "out_proj"] = mat((d_in, d), d_in)
        elif kind == "E":
            specs[pre + "router"] = mat((d, routed), d)
            specs[pre + "router_bias"] = const((routed,), 0.0)
            specs[pre + "latent_in"] = mat((d, lat), d)
            specs[pre + "w_in"] = mat((held, lat, ff), lat)
            specs[pre + "w_down"] = mat((held, ff, lat), ff)
            specs[pre + "latent_out"] = mat((lat, d), lat)
            specs[pre + "shared_in"] = mat((d, sff), d)
            specs[pre + "shared_out"] = mat((sff, d), sff)
        elif kind == "*":
            specs[pre + "wq"] = mat((d, hq, hd), d)
            specs[pre + "wk"] = mat((d, hk, hd), d)
            specs[pre + "wv"] = mat((d, hk, hd), d)
            specs[pre + "wo"] = mat((hq, hd, d), hq * hd)
        else:
            raise ValueError(f"layer kind {kind!r}: the reference has M, E and *")
    specs["ln_final"] = const((d,), 1.0)
    specs["lm_head"] = mat((v, d), d)
    return specs


def init_leaf(key, index: int, shape, how, cfg: dict):
    if how == "A_log":
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
    key = jax.random.fold_in(key, index)
    if how == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        step = jnp.maximum(
            jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)),
            cfg["time_step_floor"],
        )
        return step + jnp.log(-jnp.expm1(-step))  # softplus's inverse
    std, mean = how
    leaf = jnp.full(shape, mean, jnp.float32)
    if std:
        leaf = leaf + std * jax.random.normal(key, shape, jnp.float32)
    return leaf


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, *spec, cfg)
        for i, (name, spec) in enumerate(param_specs(cfg).items())
    }


# The layers differ in kind, so nothing is stacked: `follow` (zaya's) gets
# the flat tree both ways, and a layer's leaves are `layer.<i>.<leaf>`
# (`layers.` is what `lm.leaf_norms` takes for a stacked leaf).
stack_layers = lambda flat, cfg: flat
by_layer = lambda tree, cfg: tree

# -- the model ---------------------------------------------------------------


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def scan_masked(x, dt, a, b, c, quant=None, block: int = SCAN_QUERY_BLOCK):
    """The state-space recurrence in its masked form. x [B, S, H, P], dt
    [B, S, H], a [H], b, c [B, S, G, N] -> y [B, S, H, P] (without the
    skip). A block of query rows at a time, each recomputed in the
    backward pass: memory, not arithmetic."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    block = min(block, s)
    pad = -s % block
    if pad:  # rows past the end: queries whose result is dropped
        grow = lambda u: jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (u.ndim - 2))
        return scan_masked(
            grow(x), grow(dt), a, grow(b), grow(c), quant, block
        )[:, :s]
    step = dt * a  # [B, S, H]
    pos = jnp.arange(s)
    heads = lambda u: jnp.repeat(u, h // g, axis=2)  # a group's B, C to its heads
    bh = heads(b)

    @jax.checkpoint
    def one(start):
        rows = start + jnp.arange(block)
        # sum of `step` over (start, t] for t in the block, and over
        # (s, start] for the positions before it (minus the former for
        # those inside it): the exponent is their sum, with no
        # sequence-long running sum in between.
        after = jnp.cumsum(
            jnp.where((pos > start)[None, :, None], step, 0.0), axis=1
        )
        upto = jnp.where((pos <= start)[None, :, None], step, 0.0)
        before = jnp.flip(jnp.cumsum(jnp.flip(upto, 1), axis=1), 1) - upto
        e_t = jax.lax.dynamic_slice_in_dim(after, start, block, axis=1)
        gap = e_t[:, :, None] + (before - after)[:, None]  # [B, t, s, H]
        seen = (rows[:, None] >= pos[None, :])[None, :, :, None]
        decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))
        c_blk = heads(jax.lax.dynamic_slice_in_dim(c, start, block, axis=1))
        scores = _einsum("bthn,bshn->btsh", c_blk, bh, quant)
        m = scores * decay * dt[:, None]
        return _einsum("btsh,bshp->bthp", m, x, quant)

    out = jax.lax.map(one, jnp.arange(0, s, block))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape)


def mamba_mixer(h, p: dict, cfg: dict, quant=None):
    nh, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_in, gn = nh * hp, g * n
    proj = _einsum("bsd,de->bse", h, p["in_proj"], quant)
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * gn], axis=-1)
    w = p["conv_kernel"]
    xbc = jax.nn.silu(
        sum(w[j] * _shift(xbc, j) for j in range(w.shape[0])) + p["conv_bias"]
    )
    x, b, c = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    x = x.reshape(*x.shape[:2], nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = scan_masked(
        x, dt, -jnp.exp(p["A_log"]), b.reshape(*b.shape[:2], g, n),
        c.reshape(*c.shape[:2], g, n), quant,
    )
    y = (y + p["D"][:, None] * x).reshape(*h.shape[:2], d_in) * jax.nn.silu(z)
    grouped = y.reshape(*y.shape[:2], g, d_in // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg["layer_norm_epsilon"]
    )
    y = grouped.reshape(y.shape) * p["norm_scale"]
    return _einsum("bse,ed->bsd", y, p["out_proj"], quant)


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


def expert_layer(h, p: dict, cfg: dict, layer: int, quant=None):
    expert, weight = route(h, p, cfg, layer, quant)
    latent = _einsum("bsd,dl->bsl", h, p["latent_in"], quant)
    held = p["w_in"].shape[0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_in, w_down, index = args
        out = _einsum(
            "bsf,fl->bsl", _relu2(_einsum("bsl,lf->bsf", latent, w_in, quant)),
            w_down, quant,
        )
        mine = jnp.sum(jnp.where(expert == index, weight, 0.0), axis=-1)
        return acc + mine[..., None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(latent),
        (p["w_in"], p["w_down"], cfg["experts_first"] + jnp.arange(held)),
    )
    shared = _einsum(
        "bsf,fd->bsd", _relu2(_einsum("bsd,df->bsf", h, p["shared_in"], quant)),
        p["shared_out"], quant,
    )
    return _einsum("bsl,ld->bsd", routed, p["latent_out"], quant) + shared


def attention_layer(h, p: dict, cfg: dict, quant=None):
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    q = _einsum("bsd,dhk->bshk", h, p["wq"], quant)
    k = _einsum("bsd,dhk->bshk", h, p["wk"], quant)
    v = _einsum("bsd,dhk->bshk", h, p["wv"], quant)
    att = _attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), quant
    )
    return _einsum("bqhk,hkd->bqd", att, p["wo"], quant)


def sublayer(x, p: dict, cfg: dict, layer: int, kind: str, quant=None):
    h = _rms_norm(x, p["ln"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba_mixer(h, p, cfg, quant)
    if kind == "E":
        return x + expert_layer(h, p, cfg, layer, quant)
    return x + attention_layer(h, p, cfg, quant)


def layer_params(params: dict, i: int) -> dict:
    pre = f"layer.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def logits(params: dict, tokens, cfg: dict, quant=None):
    x = params["embedding"][tokens]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        # Save only each layer's input for the backward pass: memory, not
        # arithmetic.
        body = jax.checkpoint(
            lambda x, p, i=i, kind=kind: sublayer(x, p, cfg, i, kind, quant)
        )
        x = body(x, layer_params(params, i))
    x = _rms_norm(x, params["ln_final"], cfg["layer_norm_epsilon"])
    return _einsum("bsd,vd->bsv", x, params["lm_head"], quant)


def summed_loss(params: dict, tokens, labels, cfg: dict, quant=None):
    """Sum over tokens of the next-token cross entropy (divide by the count)."""
    z = logits(params, tokens, cfg, quant)
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(log_z - picked)


def follow(key, cfg: dict, opt: dict, batches, *, rows_per_block=None, quant=None):
    """The training reference: `reference/zaya.follow` (AdamW leaf by leaf,
    both moments waiting on the host) over this module's model."""
    return _zaya.follow(
        key, cfg, opt, batches, rows_per_block=rows_per_block, quant=quant,
        model=sys.modules[__name__],
    )
