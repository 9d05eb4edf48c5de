"""Operations and bytes a Xing4.0-style step needs: latent attention with
two-part scores, residual streams round every sublayer, leading dense
layers and gated top-k experts.

**Model FLOPs per token** (`mla_flops_per_token`, by part in
`flops_by_part`) — `lib/flops.py`'s accounting, 6 FLOP per matmul parameter
a token a time it is used (2 forward, 4 backward), over what a token passes
through in a layer:

- the latent projections: `d·q_lora_rank`, `q_lora_rank·H·(nope + rope)`,
  `d·(kv_lora_rank + rope)`, `kv_lora_rank·H·(nope + v)` and the output's
  `H·v·d`;
- attention itself: a causal pair costs 2 FLOP a lane QK^T contracts and a
  lane PV produces, `2·(nope + rope) + 2·v` a head, forward, and twice that
  backward, over `(S + 1) / 2` pairs a token: `3·H·(S + 1) / 2·(2·(nope +
  rope) + 2·v)`;
- the streams' maps, round each of the layer's two sublayers: the product
  with `phi`, `n·d·(n² + 2n)` parameters, and the mixes, which are
  multiply-adds like a matmul's, `n` (into the sublayer) `+ n² + n` (out of
  it) products of `d` lanes a token, 2 FLOP each forward and twice that
  backward: 6 a product;
- a dense layer's SwiGLU, `3·d·intermediate_size`; a sparse layer's router
  `d·experts_routed`, its shared expert `3·d·shared` and its routed experts,
  `3·d·moe_intermediate_size` times the rows a token had routed to an
  expert held here — read from the program's counters, never assumed;
- the untied head over the sliced vocabulary, once (the lookup is free).

Recomputation is not counted.

**Two-part flash calls** (`mla_call_cost`) — the least one call of a
`flash_*mla*` kernel needs, whatever its schedule: a matmul over the causal
triangle's pairs at its TRUE width, so the forward is `2·pairs·((nope +
rope) + v)` FLOP a head (QK^T over both parts, PV) and the fused backward
`2·pairs·(2·(nope + rope) + 2·v)` (dV and dP at v's width, dQ and dK at
nope + rope; the scores it forms again are not needed work). Bytes: q's two
parts, o, dO and dq's two parts once a head, `k_n`, v and their gradients
once a head, `k_r` and `dk_r` ONCE (all heads share them), the log-sum-exp
left out. The kernels' 64-lane products fill half the MXU and a diagonal
block computes more pairs than the triangle has, so a share of the roofline
from these cannot pass 100 %.

**The streams' least traffic** (`hc_bytes`) — bytes a step's `hc.*` scopes
must move for `tokens` tokens if every pass is taken once: a sublayer
forward reads X and writes X' (n·d lanes each) and writes h and reads y
(d each); backward it reads X and dX' and writes dX, and reads dh and
writes dy: `(5·n + 4)·d` lanes a token a sublayer at the streams' 2 bytes.
The maps' own arrays (24 floats a token) are left out. XLA reads X again
for the norm and for each mix, so the share says how many times over.
"""

from __future__ import annotations


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def flops_by_part(cfg: dict, seq_len: int, rows_held_a_token: float) -> dict:
    """Model FLOPs a token by part of the step."""
    d, h, n = cfg["hidden_size"], cfg["num_attention_heads"], cfg["hc_mult"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers, sparse = cfg["num_hidden_layers"], sparse_layers(cfg)
    ff = cfg["moe_intermediate_size"]
    latent = d * ql + ql * h * (dn + dr) + d * (kvl + dr) + kvl * h * (dn + dv)
    maps = n * d * (n * n + 2 * n) + (n * n + 2 * n) * d
    return {
        "latent_projections": 6.0 * layers * (latent + h * dv * d),
        "attention": layers * 3.0 * h * (seq_len + 1) / 2 * (
            2 * (dn + dr) + 2 * dv
        ),
        "streams": 6.0 * 2 * layers * maps,
        "dense_mlp": 6.0 * (layers - sparse) * 3 * d * cfg["intermediate_size"],
        "shared_and_router": 6.0 * sparse * d * (
            cfg["experts_routed"] + 3 * cfg["n_shared_experts"] * ff
        ),
        "routed_experts": 6.0 * sparse * 3 * d * ff * rows_held_a_token,
        "head": 6.0 * cfg["vocab_size"] * d,
    }


def mla_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float
) -> float:
    return float(sum(flops_by_part(cfg, seq_len, rows_held_a_token).values()))


# By what the kernel computes, at the true widths (qk = nope + rope):
# (products contracting or producing qk lanes, those of v lanes; tensors
# once a head of qk / of v lanes; tensors once a call of rope lanes).
_MLA_KINDS = {
    "fwd": (1, 1, 1, 2, 1),        # QK^T | PV; q | v, o; k_r  (k_n below)
    "bwd_fused": (2, 2, 2, 4, 2),  # dQ, dK | dV, dP; q, dq | v, o, dO, dv; k_r, dk_r
    "dq": (1, 1, 2, 2, 1),         # dQ | dP; q, dq | v, dO; k_r
    "dkv": (1, 2, 1, 3, 2),        # dK | dV, dP; q | v, dO, dv; k_r, dk_r
}
# k_n (and dk_n where the kernel writes it): tensors once a head, nope lanes.
_MLA_K = {"fwd": 1, "bwd_fused": 2, "dq": 1, "dkv": 2}


def mla_kernel_kind(event_name: str) -> str | None:
    """Which entry of the tables a trace event's name belongs to: a
    `flash_` call with `mla` in its name, by what it computes. None for
    every other event (`flash_delta`, which is elementwise, among them)."""
    if not event_name.startswith("flash_") or "mla" not in event_name:
        return None
    for prefix, kind in (
        ("flash_fwd", "fwd"), ("flash_bwd", "bwd_fused"), ("flash_dq", "dq"),
        ("flash_dkv", "dkv"),
    ):
        if event_name.startswith(prefix):
            return kind
    return None


def mla_call_cost(
    kind: str, *, batch: int, heads: int, seq_len: int, nope: int, rope: int,
    v_dim: int, dtype_bytes: int = 2,
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device,
    for the causal two-part attention of `batch` sequences of `seq_len`."""
    qk_products, v_products, qk_tensors, v_tensors, rope_tensors = _MLA_KINDS[kind]
    pairs = seq_len * (seq_len + 1) // 2
    flops = 2.0 * pairs * batch * heads * (
        qk_products * (nope + rope) + v_products * v_dim
    )
    a_head = qk_tensors * (nope + rope) + v_tensors * v_dim + _MLA_K[kind] * nope
    lanes = heads * a_head + rope_tensors * rope
    return flops, float(lanes * seq_len * batch * dtype_bytes)


def hc_bytes(cfg: dict, tokens: int, dtype_bytes: int = 2) -> float:
    """Bytes a step's stream passes must move, each taken once."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    sublayers = 2 * cfg["num_hidden_layers"]
    return float(sublayers * tokens * (5 * n + 4) * d * dtype_bytes)
