"""Operations and bytes of a GEMM, and the least time a chip could take.

A GEMM ``[M, K] @ [K, N]`` takes ``2 M K N`` operations and, at best, reads
A and B once and writes C once: ``(M K + K N + M N) * itemsize`` bytes. Its
ideal time on a chip is the larger of operations over the peak rate and
bytes over the memory bandwidth. Which GEMMs a model step makes, and its
other operations, are its architecture's (``weight_gemms`` and the counts
of ``chipbench/arch/<name>.py``).
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def gemm_bytes(m: int, k: int, n: int, itemsize: int = BF16) -> int:
    return (m * k + k * n + m * n) * itemsize


def ideal_s(flops: float, nbytes: float, peak_flops: float,
            bw: float) -> float:
    return max(flops / peak_flops, nbytes / bw)


def step_gemm_ideal_s(gemms: Iterable[Tuple[int, int, int]],
                      peak_flops: float, bw: float) -> float:
    """Least time the chip could take for one step's weight GEMMs, given as
    ``(M, K, N)`` (an architecture's ``weight_gemms``)."""
    return sum(ideal_s(gemm_flops(*g), gemm_bytes(*g), peak_flops, bw)
               for g in gemms)
