"""Operations and bytes the algorithms need, from shapes alone.

Two pieces of arithmetic, both written down so a reviewer can check them:

**Model FLOPs per token** (`lm_flops_per_token`) — a copy of `bench.py`'s
MaxText-style accounting: 6 FLOP per matmul parameter per token (2 forward,
4 backward) over the layers' matmuls (4·d·d_attn attention projections and
3·d·d_ff SwiGLU matrices a layer) and the tied head (V·d, counted once; the
embedding lookup is free), plus 6·L·S·d_attn per token for *causal*
attention (QK^T and PV are 2·S·d_attn FLOP a token each over the full
square, half of it under the causal mask: 2·S·d_attn forward, twice that
backward). Recomputation (remat, the flash backward's own recompute of the
scores) is not counted: that is the point of MFU.

**Flash attention calls** (`flash_call_cost`) — the least a causal attention
call needs, whatever the schedule: a matmul over the causal half of the
S x S square is 2·(S·(S+1)/2)·d FLOP a head. The forward needs two (QK^T,
PV); the backward needs four (dV = P^T·dO, dP = dO·V^T, dQ = dS·K,
dK = dS^T·Q) — the recompute of the scores that every flash backward
makes is *not* needed work and is left out, as is whatever a kernel
computes above the diagonal of its diagonal blocks. Bytes: every operand
read once and every result written once (forward q, k, v -> o; backward
q, k, v, o, dO -> dq, dk, dv), the few bytes of log-sum-exp left out.
Both under-count what a real kernel does, so a share of the roofline from
them cannot pass 100 %.
"""

from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = cfg["num_hidden_layers"] * (
        4 * d * d_attn + 3 * d * cfg["intermediate_size"]
    )
    return layers + cfg["vocab_size"] * d  # tied head, once


def lm_flops_per_token(cfg: dict, seq_len: int) -> float:
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(
        6 * lm_matmul_params(cfg)
        + 6 * cfg["num_hidden_layers"] * seq_len * d_attn
    )


# Needed matmuls over the causal half, by what the kernel computes. The
# names are `ops/flash.py`'s `pallas_call` names up to the layout suffix.
_FLASH_MATMULS = {
    "flash_fwd": 2,        # QK^T, PV
    "flash_bwd_fused": 4,  # dV, dP, dQ, dK in one pass
    "flash_dq": 2,         # dP, dQ (two-pass backward, first pass)
    "flash_dkv": 2,        # dV, dK (second pass; dP is recomputed there)
    "flash_delta": 0,      # rowsum(dO * O): elementwise, no matmul
}
# Tensors of shape [B, H, S, d] each kernel must read + write at least once.
_FLASH_TENSORS = {
    "flash_fwd": 4,        # q k v -> o
    "flash_bwd_fused": 8,  # q k v o dO -> dq dk dv
    "flash_dq": 5,         # q k v dO -> dq
    "flash_dkv": 6,        # q k v dO -> dk dv
    "flash_delta": 2,      # o dO -> [B, H, S] (left out)
}


def flash_kernel_kind(event_name: str) -> str | None:
    """Which entry of the tables a trace event's name belongs to."""
    for kind in sorted(_FLASH_MATMULS, key=len, reverse=True):
        if event_name.startswith(kind):
            return kind
    return None


def flash_call_cost(
    kind: str, *, batch: int, heads: int, seq_len: int, head_dim: int,
    dtype_bytes: int = 2,
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device,
    for the causal attention of `batch` x `heads` sequences of `seq_len`."""
    causal_pairs = seq_len * (seq_len + 1) // 2
    flops = _FLASH_MATMULS[kind] * 2.0 * causal_pairs * head_dim
    nbytes = _FLASH_TENSORS[kind] * seq_len * head_dim * dtype_bytes
    return flops * batch * heads, float(nbytes * batch * heads)


def least_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The roofline: the least time the chip could take, and which bound."""
    by_flops = flops / peaks.flops_bf16
    by_bytes = nbytes / peaks.hbm_bytes_per_s
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
