"""Pool dispatch: program-kernel launches per job (the program's
``pool.launches`` counter over the window)."""


def read(facts):
    n = facts["counters"].get("pool.launches")
    if not n or not facts.get("jobs"):
        return None
    return n / facts["jobs"]
