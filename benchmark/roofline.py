"""Operations and bytes of the kernels whose roofline share is reported.

The counts come from shapes, by functions kept here, so that no PR that
claims a gain can change them. The peaks are ``peaks.json``'s.
"""

from typing import Dict, Tuple


def compensate_bytes(t: int, grad_itemsize: int, state_itemsize: int) -> int:
    """HBM bytes the fused compensate pass must move for a compressed
    block of ``t`` coordinates: it reads the gradient, the momentum, the
    velocity and the previous step's transmit record (one bit per
    coordinate), and writes the momentum and the velocity. The selection
    candidates it also emits (two per 1024-wide segment) are under 1% of
    that and are left out, which makes the share a little low."""
    return t * (grad_itemsize + 4 * state_itemsize) + t // 8


def compensate_flops(t: int) -> int:
    """u <- m*u + g, v <- v + u, and the mask applied to both on read:
    five float operations per coordinate."""
    return 5 * t


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((by_memory, "hbm_bandwidth") if by_memory >= by_compute
            else (by_compute, "compute"))
