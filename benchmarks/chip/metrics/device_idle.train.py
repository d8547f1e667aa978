"""Share of the traced training window in which no operation ran on the
device."""


def read(rec: dict):
    tr = rec.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]
