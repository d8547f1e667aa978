"""Host milliseconds of one call of the kitsune step in the window: the
median of the program's `run` spans over the window's steps (the last
`steps` calls; nothing calls the step after the window)."""
import statistics

from program_spans import durations


def read(rec: dict):
    steps = rec.get("steps")
    runs = durations("run")
    if not steps or not runs:
        return None
    return 1e3 * statistics.median(runs[-steps:])
