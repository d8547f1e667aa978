"""Attention sites (forward and backward) that the kitsune lowering bound to
the Pallas flash-attention kernels, from `TracedApp.lowering`: keys that
start with `flash_attention` (the forward, and `flash_attention_bwd`, the
dQ / dK-dV pair) and say `:lowered:`.  A program without these kernels
gives no such key, and the metric gives nothing."""


def read(rec: dict):
    sites = rec.get("lowering")
    if not sites:
        return None
    hits = [n for key, n in sites.items()
            if key.startswith("flash_attention") and ":lowered:" in key]
    return sum(hits) if hits else None
