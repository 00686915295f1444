"""A kernel category's share of its roofline, shared by the
``<kernel>_roofline`` layer metrics."""

from __future__ import annotations

from typing import Optional

# the trace category that holds convolutions and matrix products alike
# (fedbench/op_categories.json): the trace cannot tell them apart, the
# configuration's flops file says which of the two its work is
MXU = "mxu"


def least_seconds(flops: float, n_bytes: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time one chip could take, the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    and which of the two it is."""
    by_compute = flops / peaks["flops_per_s_bf16"]
    by_memory = n_bytes / peaks["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))


def roofline_share(reduced, cell, kernel: str) -> Optional[float]:
    """Percent: least seconds for the traced rounds' required work of
    the ``kernel`` category, a device, over the device time of the matrix
    unit's ops (mean over the cell's devices). ``None`` where there
    is no trace, the configuration counts another kernel, or no op of
    the category ran."""
    required = cell["required"]
    if reduced is None or required["kernel"] != kernel:
        return None
    times = [d["category_s"].get(MXU, 0.0)
             for d in reduced["devices"].values()]
    if not all(t > 0 for t in times):
        return None
    least, _ = least_seconds(required["kernel_flops_per_round"],
                             required["kernel_bytes_per_round"],
                             cell["peaks"])
    least_per_device = least * reduced["n_rounds"] / len(times)
    return 100.0 * least_per_device / (sum(times) / len(times))
