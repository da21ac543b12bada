"""MoE dispatch: (token, expert) pairs routed to an expert held on this chip,
per token (the program's ``moe.pairs_held`` counter over the window)."""


def read(facts):
    n = facts["counters"].get("moe.pairs_held")
    if n is None or not facts.get("tokens"):
        return None
    return n / facts["tokens"]
