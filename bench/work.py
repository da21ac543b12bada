"""Work counts of the program kernel and of the model step.

The counts follow the simulated contract, not a formulation of the kernel:
a row of a launch does, per schedule step, one digit compare per key and
compared column, and one digit write per written column.  A rewrite of the
kernel's layout leaves these counts alone; a program with fewer steps has
less work.
"""
from __future__ import annotations

import math

import numpy as np


def compared_digits(step) -> int:
    return len(step.keys) * len(step.compare_cols)


def written_digits(step) -> int:
    return len(step.write_cols)


def ops_per_row(compiled) -> int:
    """Digit operations one row of a launch of ``compiled`` takes."""
    return sum(compared_digits(s) + written_digits(s) for s in compiled.steps)


def compare_cycles(compiled) -> int:
    """One compare cycle per key of a step, as APStats charges them."""
    return sum(len(s.keys) for s in compiled.steps)


def hist_row_compares(compiled) -> int:
    """Compares per row that land in the mismatch histogram."""
    return sum(len(s.keys) for s in compiled.steps if s.in_hist)


def schedule_bytes(compiled) -> int:
    """The schedule words a launch reads: the six dense schedule tensors."""
    return int(sum(np.asarray(t).nbytes for t in compiled.schedule_tensors))


def launch_bytes(compiled, block_rows: int, n_cols: int) -> int:
    """One launch: its digit block into and out of HBM, plus the schedule."""
    return 2 * block_rows * n_cols + schedule_bytes(compiled)


def run_work(compiled, n_rows: int, block_rows: int, n_cols: int
             ) -> dict[str, int]:
    """Work of one pooled run of ``compiled`` over ``n_rows`` valid rows."""
    launches = math.ceil(n_rows / block_rows)
    return {"ops": n_rows * ops_per_row(compiled),
            "bytes": launches * launch_bytes(compiled, block_rows, n_cols),
            "launches": launches,
            "row_compares": n_rows * hist_row_compares(compiled)}


def add_work(total: dict, part: dict, times: int = 1) -> dict:
    for k, v in part.items():
        total[k] = total.get(k, 0) + times * v
    return total


def attention_flops(d_model: int, n_heads: int, n_kv_heads: int,
                    head_dim: int, context: int) -> int:
    """Float operations of one token through one attention layer: the q, k,
    v and output projections, and scores and values over ``context``
    positions."""
    proj = 2 * d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return proj + 4 * n_heads * head_dim * context


def head_flops(d_model: int, vocab: int) -> int:
    """The tied output head of one token."""
    return 2 * d_model * vocab


# The program kernel in a device trace: the custom call (the Pallas kernel of
# ``kernels/tap_pass``) of the jitted ``_tap_run_program_jit``, e.g.
# "%_tap_run_program_jit.1 = (s8[4096,41]..., s32[8,128]...) custom-call(...".
KERNEL_PATTERN = r"tap_run_program\S* = .*custom-call"


def roofline_share(kernel_work: dict, kernel_s: float, peaks: dict
                   ) -> float | None:
    """Per cent of the kernel's roofline: the least time the chip needs for
    the work (its digit operations at the int8 peak, or its bytes at HBM
    bandwidth, whichever is longer) over the time the kernel took."""
    if not kernel_s or peaks is None or not kernel_work.get("ops"):
        return None
    least = max(kernel_work["ops"] / peaks["int8_ops_per_s"],
                kernel_work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
