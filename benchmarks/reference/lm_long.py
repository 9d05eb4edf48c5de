"""`reference/lm.py`'s decoder with its attention computed a block of
queries at a time, for sequences whose [heads, S, S] float32 scores do not
fit beside the reference's state (S = 8192, 16 heads: 4.3 GB a tensor, and
the int8 control keeps more of them). The same weights (`lm.init_params`),
the same layer equations with the same helpers, the same values up to the
order of the softmax's sums; only what is alive at once differs.
`reference/zaya.follow(model=...)` follows it.
"""

from __future__ import annotations

import functools

import jax

from benchmarks.reference.lm import (  # noqa: F401  (the model's interface)
    _einsum, _rms_norm, _rope, by_layer, init_params, stack_layers,
)
from benchmarks.reference.zaya import _attention


def _layer(x, p: dict, cfg: dict, quant):
    y = _rms_norm(x, p["ln_attn"])
    q = _rope(_einsum("bsd,dhk->bshk", y, p["wq"], quant), cfg["rope_theta"])
    k = _rope(_einsum("bsd,dhk->bshk", y, p["wk"], quant), cfg["rope_theta"])
    v = _einsum("bsd,dhk->bshk", y, p["wv"], quant)
    att = _attention(q, k, v, quant)
    x = x + _einsum("bqhk,hkd->bqd", att, p["wo"], quant)
    y = _rms_norm(x, p["ln_mlp"])
    gate = _einsum("bsd,df->bsf", y, p["w_gate"], quant)
    up = _einsum("bsd,df->bsf", y, p["w_up"], quant)
    return x + _einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"], quant)


def summed_loss(params: dict, tokens, labels, cfg: dict, quant=None):
    """Sum over tokens of the next-token cross entropy."""
    x = params["embedding"][tokens]
    layers = {
        k.split(".", 1)[1]: v for k, v in params.items() if k.startswith("layers.")
    }
    body = jax.checkpoint(functools.partial(_layer, cfg=cfg, quant=quant))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, layers)
    x = _rms_norm(x, params["ln_final"])
    z = _einsum("bsd,vd->bsv", x, params["embedding"], quant)
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jax.numpy.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jax.numpy.sum(log_z - picked)
