"""Seconds the executor takes to lower and compile its plan's programs, or
to load them from JAX's compile cache, on the step's first call: the
program's `compile_program` spans."""
from program_spans import totals


def read(rec: dict):
    row = totals().get("compile_program")
    return None if row is None else row["seconds"]
