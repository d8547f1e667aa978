"""Seconds the kitsune compiler takes to build the training step: trace,
passes, lowering verdicts and autotuning (host span around
`compile_train_step`)."""


def read(rec: dict):
    return rec.get("kitsune_compile_s")
