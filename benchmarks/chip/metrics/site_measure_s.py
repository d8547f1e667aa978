"""Seconds the kitsune compiler spends timing kernel sites on the device
while it compiles: the program's `autotune` spans (tile searches) and
`verdict_measure` spans (lowering microbenchmarks).  The two never nest:
tiles are searched while the sites are matched, verdicts measured after."""
from program_spans import totals


def read(rec: dict):
    t = totals()
    if not t:
        return None
    return sum(t.get(name, {}).get("seconds", 0.0)
               for name in ("autotune", "verdict_measure"))
