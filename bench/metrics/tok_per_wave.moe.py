"""Batcher: tokens per wave (how many requests a wave merged), counted by
``BatchServer.n_waves`` over the window."""


def read(facts):
    if not facts.get("waves"):
        return None
    return facts["tokens"] / facts["waves"]
