"""Operations and bytes a Laguna-style step needs: a stack that mixes global
and window attention layers, a leading dense layer and gated top-k experts.

**Model FLOPs per token** (`window_flops_per_token`, by layer in
`flops_by_part`) — `lib/flops.py`'s accounting, 6 FLOP per matmul parameter
a token a time it is used (2 forward, 4 backward), over what a token passes
through in layer l:

- attention's projections, `d·(H_l + 2·Hkv)·hd` in and `H_l·hd·d` out, and
  the gate's `d·H_l`, with `H_l = num_attention_heads_per_layer[l]`;
- a `dense` layer's SwiGLU, `3·d·intermediate_size`; a `sparse` layer's
  router `d·experts_routed`, its shared expert `3·d·shared` and its routed
  experts, `3·d·moe_intermediate_size` times the rows a token had routed to
  an expert held here — read from the program's counters, never assumed;
- the untied head over the sliced vocabulary, once (the embedding lookup is
  free);

plus attention itself: a `full_attention` layer `6·S·H_l·hd` a token, as
`lib/flops.lm_flops_per_token` counts the causal half; a
`sliding_attention` layer `12·(band pairs / S)·H_l·hd`, where the band's
pairs are what the window lets a sequence's queries see, `sum_i min(i + 1,
W)` (`band_pairs`): QK^T and PV are 2 FLOP a pair and head lane each,
forward, and twice that backward. Counted by the triangle a window of 512
at 8k would read 8.3 times too high. Recomputation is not counted.

**Window attention calls** (`window_call_cost`) — the least one call of a
`flash_*window*` kernel needs, whatever its schedule: a matmul over the
band is `2·pairs·hd` FLOP a query head; the forward needs two (QK^T, PV),
the fused backward four (dV, dP, dQ, dK; the scores it forms again are not
needed work), each pass of the two-pass backward two. Bytes: q, o, dO and
dq once a QUERY head, K, V, dK and dV once a K/V head (with 9 query heads
to a K/V head the kernels' own per-query-head partial dK and dV are theirs,
not needed), the log-sum-exp left out. Both under-count what a kernel does
(its edge blocks compute masked pairs), so a share of the roofline from
them cannot pass 100 %.
"""

from __future__ import annotations


def band_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a sequence's attention needs: key <= query, and
    under a window key > query - window."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def flops_by_part(cfg: dict, seq_len: int, rows_held_a_token: float) -> dict:
    """Model FLOPs a token by part of the step."""
    d, hd, hk = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    parts = dict.fromkeys(
        ("projections", "dense_mlp", "global_attention", "window_attention",
         "shared_and_router", "routed_experts", "head"), 0.0,
    )
    for i in range(cfg["num_hidden_layers"]):
        h = cfg["num_attention_heads_per_layer"][i]
        parts["projections"] += 6.0 * (d * (h + 2 * hk) * hd + h * hd * d + d * h)
        if cfg["layer_types"][i] == "sliding_attention":
            pairs = band_pairs(seq_len, cfg["sliding_window"])
            parts["window_attention"] += 12.0 * pairs / seq_len * h * hd
        else:
            parts["global_attention"] += 6.0 * seq_len * h * hd
        if cfg["mlp_layer_types"][i] == "dense":
            parts["dense_mlp"] += 6.0 * 3 * d * cfg["intermediate_size"]
        else:
            parts["shared_and_router"] += 6.0 * d * (
                cfg["experts_routed"] + 3 * cfg["shared_expert_intermediate_size"]
            )
            parts["routed_experts"] += (
                6.0 * 3 * d * cfg["moe_intermediate_size"] * rows_held_a_token
            )
    parts["head"] = 6.0 * cfg["vocab_size"] * d
    return parts


def window_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float
) -> float:
    return float(sum(flops_by_part(cfg, seq_len, rows_held_a_token).values()))


# (needed matmuls over the band, tensors a query head, tensors a K/V head)
# by what the kernel computes; the names are `ops/flash.py`'s.
_WINDOW_KINDS = {
    "fwd": (2, 2, 2),         # QK^T, PV; q -> o; k, v
    "bwd_fused": (4, 4, 4),   # dV, dP, dQ, dK; q, o, dO -> dq; k, v -> dk, dv
    "dq": (2, 3, 2),          # dP, dQ; q, dO -> dq; k, v
    "dkv": (2, 2, 4),         # dV, dK; q, dO; k, v -> dk, dv
}


def window_kernel_kind(event_name: str) -> str | None:
    """Which entry of the table a trace event's name belongs to: a
    `flash_` call with `window` in its name, by what it computes. None for
    every other event, a global layer's `flash_*` calls among them."""
    if not event_name.startswith("flash_") or "window" not in event_name:
        return None
    if event_name.startswith("flash_fwd"):
        return "fwd"
    if event_name.startswith("flash_bwd"):
        return "bwd_fused"
    if event_name.startswith("flash_dq"):
        return "dq"
    if event_name.startswith("flash_dkv"):
        return "dkv"
    return None


def window_call_cost(
    kind: str, *, batch: int, heads: int, kv_heads: int, seq_len: int,
    window: int, head_dim: int, dtype_bytes: int = 2,
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device,
    for the window attention of `batch` sequences of `seq_len` with `heads`
    query heads over `kv_heads` K/V heads."""
    matmuls, per_query_head, per_kv_head = _WINDOW_KINDS[kind]
    flops = matmuls * 2.0 * band_pairs(seq_len, window) * head_dim * batch * heads
    tensor = seq_len * head_dim * dtype_bytes * batch
    return flops, float(tensor * (per_query_head * heads + per_kv_head * kv_heads))
