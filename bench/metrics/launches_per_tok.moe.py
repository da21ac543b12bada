"""Pool dispatch: program-kernel launches per token (the program's
``pool.launches`` counter over the window)."""


def read(facts):
    n = facts["counters"].get("pool.launches")
    if not n or not facts.get("tokens"):
        return None
    return n / facts["tokens"]
