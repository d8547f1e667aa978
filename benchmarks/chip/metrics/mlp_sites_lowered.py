"""Fused-MLP sites (forward and gated backward) that the kitsune lowering
bound to the Pallas kernels, from `TracedApp.lowering`."""


def read(rec: dict):
    sites = rec.get("lowering")
    if not sites:
        return None
    return sum(n for key, n in sites.items()
               if key.startswith("fused_mlp") and ":lowered:" in key)
