"""Tokens through the model step per second: prompt and generated tokens of
every request of the window, over the whole window."""


def read(facts):
    if "tokens" not in facts:
        return None
    return facts["tokens"] / facts["window_s"]
