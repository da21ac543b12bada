"""Device: per cent of the traced window in which no operation ran (every
``device_idle.<part>`` metric)."""


def read(facts):
    t = facts.get("trace")
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
