"""Seconds the kitsune compiler takes to trace the training step into its
graph: the program's `pass/trace` span (pass 0 of `repro.compile`)."""
from program_spans import totals


def read(rec: dict):
    row = totals().get("pass/trace")
    return None if row is None else row["seconds"]
