"""Operations and bytes a Nemotron-H-style step needs, from shapes and from
the rows the router really sent to the experts held here.

**Model FLOPs per token** (`hybrid_flops_per_token`) — `lib/flops.py`'s
accounting, 6 FLOP per matmul parameter a token a time it is used (2
forward, 4 backward), over what a token passes through, by layer kind:

- `M`: the in-projection `d·(2·d_in + 2·G·N + H)` and the out-projection
  `d_in·d` (the convolution, the gate and the norm are elementwise and not
  counted), plus the scan (below);
- `E`: the router `d·E` with E the experts it scores, the projections into
  and out of the latent `2·d·l`, the shared expert `2·d·s`, and the routed
  experts `2·l·f` times the rows a token has on experts HELD HERE — read
  from the program's counters (`moe_tokens_held` counts rows), never
  assumed: a row routed elsewhere costs this chip no expert FLOP;
- `*`: the projections `d·(Hq + 2·Hkv)·hd + Hq·hd·d`, plus `6·S·Hq·hd` a
  token for causal attention, as `lib/flops.lm_flops_per_token` counts it;
- the untied head over the sliced vocabulary, `V·d`, once (the embedding is
  a lookup).

**The scan** (`scan_flops_per_token`, forward) — the chunked algorithm the
configuration names (`chunk_size` Q), its matmuls over the causal half of a
chunk where the chunk's square is masked: a token's share of `C B^T`,
`G·Q·N` (2·Q·N a group, half of it under the mask); of `M X`, `H·Q·P`; the
state read `C S`, `2·H·N·P`; the state's update `X^T B`, `2·H·N·P`. The
backward needs two matmuls for each of these: three times the forward in
all, as 6 is to 2.

**A scan kernel's call** (`ssd_call_cost`) — the least one `ssd_fwd` /
`ssd_bwd` call needs: the FLOPs above (`ssd_bwd`: twice the forward's; the
`C B^T` and the decay matrix it forms again are not needed work) and every
operand read once and every result written once: forward x, B, C (2 bytes)
and dt (4) in, y out; backward x, dy, B, C, dt in, dx, dB, dC, d(dt) out.
The chunk states the forward saves and the backward reads, the second
copies of dt and the cumulative sums are the kernels' own and left out, as
is everything a kernel computes above a chunk's diagonal: a share of the
roofline from these cannot pass 100 %.
"""

from __future__ import annotations


def scan_flops_per_token(cfg: dict) -> float:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    return float(g * q * n + h * q * p + 4 * h * n * p)


def matmul_params_per_token(cfg: dict, rows_held_a_token: float) -> float:
    """Matmul parameters one token passes through; `rows_held_a_token` is
    the mean number of a token's rows an expert layer routed to an expert
    held here."""
    d = cfg["hidden_size"]
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    hq, hk, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    lat, ff = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    by_kind = {
        "M": d * (2 * d_in + 2 * gn + cfg["mamba_num_heads"]) + d_in * d,
        "E": d * cfg["experts_routed"] + 2 * d * lat
        + 2 * d * cfg["moe_shared_expert_intermediate_size"]
        + 2 * lat * ff * rows_held_a_token,
        "*": d * (hq + 2 * hk) * hd + hq * hd * d,
    }
    layers = sum(by_kind[kind] for kind in cfg["hybrid_override_pattern"])
    return layers + cfg["vocab_size"] * d


def hybrid_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float
) -> float:
    pattern = cfg["hybrid_override_pattern"]
    latent = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(
        6 * matmul_params_per_token(cfg, rows_held_a_token)
        + 3 * pattern.count("M") * scan_flops_per_token(cfg)
        + 6 * pattern.count("*") * seq_len * latent
    )


SSD_KINDS = ("ssd_fwd", "ssd_bwd")


def ssd_kernel_kind(event_name: str) -> str | None:
    for kind in SSD_KINDS:
        if event_name.startswith(kind):
            return kind
    return None


def ssd_call_cost(
    kind: str, cfg: dict, *, batch: int, seq_len: int
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device."""
    tokens = batch * seq_len
    wide = 2 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]  # bytes of x a token
    bc = 2 * 2 * cfg["n_groups"] * cfg["ssm_state_size"]       # of B and C
    dt = 4 * cfg["mamba_num_heads"]
    if kind == "ssd_fwd":
        return tokens * scan_flops_per_token(cfg), float(
            tokens * (2 * wide + bc + dt)
        )
    if kind == "ssd_bwd":
        return 2 * tokens * scan_flops_per_token(cfg), float(
            tokens * (3 * wide + 2 * bc + 2 * dt)
        )
    raise ValueError(f"no scan kernel {kind!r}")
