"""Operations a GLM-4.7-Flash-style step needs: latent attention whose
values are as wide as a head's two parts together, a leading dense layer,
gated top-k experts beside a shared one, and the multi-token module: one
further expert layer over a projection of `[embedding | hidden]`, and the
shared head a second time.

**Model FLOPs per token** (`mtp_flops_per_token`, by part in
`flops_by_part`) — `lib/flops.py`'s accounting, 6 FLOP per matmul parameter
a token a time it is used (2 forward, 4 backward), over what a token passes
through:

- a block's latent projections: `d·q_lora_rank`, `q_lora_rank·H·(nope +
  rope)`, `d·(kv_lora_rank + rope)`, `kv_lora_rank·H·(nope + v)` and the
  output's `H·v·d`. The one rope key is counted once, as the equations
  have it; repeating it a head for the one-part kernels is the program's
  own work;
- attention itself: a causal pair costs 2 FLOP a lane QK^T contracts and a
  lane PV produces, `2·(nope + rope) + 2·v` a head, forward, and twice that
  backward, over `(S + 1) / 2` pairs a token;
- the dense layer's SwiGLU, `3·d·intermediate_size`; a sparse block's router
  `d·experts_routed`, its shared expert `3·d·shared` and its routed experts,
  `3·d·moe_intermediate_size` times the rows a token had routed to an
  expert held here — read from the program's counters, never assumed;
- the module: its block (the four entries above under `mtp_block`, an
  expert layer's), its projection `2d·d` (`mtp_proj`) and the head a second
  time (`mtp_head`); its loss masks one position in S, which is not taken
  off;
- the untied head over the sliced vocabulary (the lookups are free).

Recomputation is not counted.
"""

from __future__ import annotations


def flops_by_part(cfg: dict, seq_len: int, rows_held_a_token: float) -> dict:
    """Model FLOPs a token by part of the step."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"]
    sparse = layers - cfg["first_k_dense_replace"]
    ff, modules = cfg["moe_intermediate_size"], cfg["num_nextn_predict_layers"]
    latent = 6.0 * (
        d * ql + ql * h * (dn + dr) + d * (kvl + dr) + kvl * h * (dn + dv)
        + h * dv * d
    )
    attention = 3.0 * h * (seq_len + 1) / 2 * (2 * (dn + dr) + 2 * dv)
    shared = 6.0 * d * (
        cfg["experts_routed"] + 3 * cfg["n_shared_experts"] * ff
    )
    routed = 6.0 * 3 * d * ff * rows_held_a_token
    head = 6.0 * cfg["vocab_size"] * d
    return {
        "latent_projections": layers * latent,
        "attention": layers * attention,
        "dense_mlp": 6.0 * (layers - sparse) * 3 * d * cfg["intermediate_size"],
        "shared_and_router": sparse * shared,
        "routed_experts": sparse * routed,
        "head": head,
        "mtp_block": modules * (latent + attention + shared + routed),
        "mtp_proj": modules * 6.0 * 2 * d * d,
        "mtp_head": modules * head,
    }


def mtp_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float
) -> float:
    return float(sum(flops_by_part(cfg, seq_len, rows_held_a_token).values()))
