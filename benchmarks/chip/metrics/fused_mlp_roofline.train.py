"""Share of the roofline that the fused SwiGLU kernels reach in training.

The kernels are found in the trace by the shapes of their calls (the
program gives them no name): operands x (m, d), wg and wu (d, f), wd
(f, d), and for the backward dy (m, d).  The forward returns (m, d); the
backward's dX kernel returns (m, d) from five operands and its dW kernel
the three weight gradients.  For every call, the least time the chip could
take -- the larger of its operations (`flops.py`; recompute not counted) over
the bf16 peak and of the bytes of its operands and outputs over the HBM
peak -- is summed and divided by the kernels' device time."""
import flops
from trace_reduce import call_bytes, kernel_calls


def _kind(sig: dict, m: int, d: int, f: int) -> str | None:
    ops = [dims for _, dims in sig["operands"]]
    outs = [dims for _, dims in sig["outputs"]]
    if sig["target"] != "tpu_custom_call" or ops[:4] != [
            (m, d), (d, f), (d, f), (f, d)]:
        return None
    if len(ops) == 4 and outs == [(m, d)]:
        return "fwd"
    if len(ops) == 5 and ops[4] == (m, d):
        if outs == [(m, d)]:
            return "bwd_dx"
        if outs == [(d, f), (d, f), (f, d)]:
            return "bwd_dw"
    return None


def read(rec: dict):
    tr, peaks = rec.get("trace"), rec.get("peaks")
    if not tr or not peaks:
        return None
    m, d, f = rec["shapes"]["m"], rec["shapes"]["d"], rec["shapes"]["f"]
    ops = {"fwd": flops.swiglu_fwd(m, d, f),
           "bwd_dx": flops.swiglu_bwd_dx(m, d, f),
           "bwd_dw": flops.swiglu_bwd_dw(m, d, f)}
    least = secs = 0.0
    for sig, calls, s in kernel_calls(tr, lambda g: _kind(g, m, d, f)):
        kind = _kind(sig, m, d, f)
        least += calls * max(ops[kind] / peaks["bf16_flops_per_s"],
                             call_bytes(sig) / peaks["hbm_bytes_per_s"])
        secs += s
    return 100.0 * least / secs if secs > 0 else None
