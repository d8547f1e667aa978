"""Model FLOP utilization of the whole training step on the device: the
model's operations of every step in the traced window (forward and
backward, no recompute) over the seconds in which an operation ran on the
device (the trace's busy time) and the chip's bf16 peak.  Host gaps between
steps are `device_idle.train`'s, so this share moves apart from
`train_tokens_s` when the device work itself changes."""


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or not tr["busy_s"] or not rec.get("steps") \
            or not rec.get("peaks"):
        return None
    flops = rec["steps"] * rec["tokens_per_step"] * rec["flops_per_token"]
    return 100.0 * flops / tr["busy_s"] / rec["peaks"]["bf16_flops_per_s"]
