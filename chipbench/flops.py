"""Operations and bytes of the dense decoder, computed from its shapes.

A GEMM ``[M, K] @ [K, N]`` takes ``2 M K N`` operations and, at best, reads
A and B once and writes C once: ``(M K + K N + M N) * itemsize`` bytes. Its
ideal time on a chip is the larger of operations over the peak rate and
bytes over the memory bandwidth.
"""
from __future__ import annotations

from typing import List, Tuple

from chipbench.weights import layer_shapes

BF16 = 2


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def gemm_bytes(m: int, k: int, n: int, itemsize: int = BF16) -> int:
    return (m * k + k * n + m * n) * itemsize


def ideal_s(flops: float, nbytes: float, peak_flops: float,
            bw: float) -> float:
    return max(flops / peak_flops, nbytes / bw)


def weight_gemms(arch: dict, rows: int,
                 head_rows: int | None = None) -> List[Tuple[int, int, int]]:
    """Every weight GEMM of one model step over ``rows`` token rows: seven
    per layer and the LM head over ``head_rows`` rows (a prefill takes the
    logits of its last position only), as ``(M, K, N)``."""
    per_layer = [(rows, k, n) for k, n in layer_shapes(arch).values()]
    head = (rows if head_rows is None else head_rows, arch["hidden_size"],
            arch["vocab_size"])
    return per_layer * arch["num_hidden_layers"] + [head]


def step_gemm_ideal_s(arch: dict, rows: int, peak_flops: float, bw: float,
                      head_rows: int | None = None) -> float:
    """Least time the chip could take for one step's weight GEMMs."""
    return sum(ideal_s(gemm_flops(*g), gemm_bytes(*g), peak_flops, bw)
               for g in weight_gemms(arch, rows, head_rows))


def layer_params(arch: dict) -> int:
    """Weights each token multiplies by in the layers."""
    per_layer = sum(k * n for k, n in layer_shapes(arch).values())
    return arch["num_hidden_layers"] * per_layer


def matmul_params(arch: dict) -> int:
    """Weights a decoded token multiplies by: the layers and the LM head."""
    return layer_params(arch) + arch["hidden_size"] * arch["vocab_size"]


def attention_flops(arch: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return 4 * arch["num_hidden_layers"] * q_dim * context


def decode_token_flops(arch: dict, position: int) -> int:
    """Model operations of one token decoded at ``position`` (it attends to
    ``position + 1`` keys, itself included)."""
    return 2 * matmul_params(arch) + attention_flops(arch, position + 1)


def prefill_flops(arch: dict, length: int) -> int:
    """Model operations of a causal prompt of ``length`` tokens, with the
    logits of its last position."""
    causal_keys = length * (length + 1) // 2
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return (2 * layer_params(arch) * length
            + 2 * arch["hidden_size"] * arch["vocab_size"]
            + 4 * arch["num_hidden_layers"] * q_dim * causal_keys)
