"""Device programs launched per training step in the traced window (the
executor's launches between which the device can idle)."""


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or not rec.get("steps") or not tr["programs"]:
        return None
    return tr["programs"] / rec["steps"]
