"""Program kernel: per cent of its roofline over the window (work from the
launched programs' schedules, time from the device trace); every
``kernel_roofline.<part>`` metric."""
import work
from trace_reduce import kernel_seconds


def read(facts):
    if facts.get("trace") is None:
        return None
    ks = kernel_seconds(facts["trace"]["op_seconds"], work.KERNEL_PATTERN)
    return work.roofline_share(facts["kernel_work"], ks, facts["peaks"])
