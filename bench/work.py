"""The work one call needs, from its shapes: operations and bytes.

These count what the algorithm needs, not what an implementation does:
Omega is regenerated and never read, A is read once, the outputs are
written once.  So the same number is read whatever implements the call.
"""
from __future__ import annotations

from typing import Dict


def sketch(n1: int, n2: int, r: int, itemsize: int = 4) -> Dict[str, float]:
    """B = A·Omega, A n1 x n2, Omega n2 x r."""
    return {"flops": 2.0 * n1 * n2 * r,
            "bytes": float(itemsize) * (n1 * n2 + n1 * r)}


OPS = {"sketch": lambda c: sketch(c["n"], c["n"], c["r"])}


def least_seconds(work: Dict[str, float], peak: Dict[str, float],
                  chips: int) -> Dict[str, float]:
    """The least time ``chips`` chips could take for ``work``: the larger of
    operations over peak FLOP/s and bytes over HBM bandwidth.  Returns both
    bounds and which one binds."""
    compute = work["flops"] / (chips * peak["flops_bf16"])
    memory = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return {"seconds": max(compute, memory), "compute_s": compute,
            "memory_s": memory,
            "bound": "compute" if compute >= memory else "memory"}
