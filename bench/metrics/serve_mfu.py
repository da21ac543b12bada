"""Model step: per cent of the chip's peak that the served tokens required.

Per token, the AP programs' digit operations at the int8 peak and the float
layers' operations (attention projections and scores, the tied head) at the
bf16 peak; times tokens, over the window."""


def read(facts):
    peaks = facts.get("peaks")
    if peaks is None or not facts.get("tokens"):
        return None
    need_s = (facts["kernel_work"]["ops"] / peaks["int8_ops_per_s"]
              + facts["float_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * need_s / facts["window_s"]
