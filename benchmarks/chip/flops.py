"""Operations that the model and its kernels require, from shapes alone
(a kernel's bytes are read from its call in the trace).  Recomputation
(remat, a kernel's recomputed hidden tile) is never counted, so a count
does not change with how the work is done."""
from __future__ import annotations


def matmul_params(a: dict) -> int:
    """Weights that multiply every token: the layers and the output head
    (the embedding is a lookup)."""
    d, q = a["d_model"], a["n_heads"] * a["head_dim"]
    kv, f = a["n_kv_heads"] * a["head_dim"], a["d_ff"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return a["n_layers"] * per_layer + a["vocab"] * d


def attn_flops(a: dict, context: float) -> float:
    """Forward attention operations of one token over `context` keys:
    scores and the weighted sum, 2 * 2 * q_dim each key, every layer."""
    return 4.0 * a["n_heads"] * a["head_dim"] * context * a["n_layers"]


def train_flops_per_token(a: dict, seq: int) -> float:
    """Forward and backward (3x the forward) per trained token, with causal
    attention over (seq + 1) / 2 keys on average."""
    return 3.0 * (2.0 * matmul_params(a) + attn_flops(a, (seq + 1) / 2))


def swiglu_fwd(m: int, d: int, f: int) -> float:
    """Operations of (silu(x wg) * (x wu)) wd on (m, d) rows."""
    return 6.0 * m * d * f


def swiglu_bwd_dx(m: int, d: int, f: int) -> float:
    """Operations of its backward's dX kernel: dy wd^T, then dg wg^T and
    du wu^T; the recomputed forward products are not counted."""
    return 6.0 * m * d * f


def swiglu_bwd_dw(m: int, d: int, f: int) -> float:
    """Operations of its backward's dW kernel: x^T dg, x^T du and t^T dy."""
    return 6.0 * m * d * f
