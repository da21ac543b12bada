"""Vector elements added per second: rows of every job of the window, over
the whole window."""


def read(facts):
    if "rows" not in facts:
        return None
    return facts["rows"] / facts["window_s"]
