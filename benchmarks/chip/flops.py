"""Operations that the kernels require, from shapes alone (a kernel's
bytes are read from its call in the trace).  Recomputation (remat, a
kernel's recomputed hidden tile) is never counted, so a count does not
change with how the work is done.  A whole trained token's count is its
configuration's reference's (`train_flops_per_token`)."""
from __future__ import annotations


def attn_flops(a: dict, context: float) -> float:
    """Forward attention operations of one token over `context` keys:
    scores and the weighted sum, 2 * 2 * q_dim each key, every layer."""
    return 4.0 * a["n_heads"] * a["head_dim"] * context * a["n_layers"]


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
