"""Operation and byte counts of a dense pre-LN transformer, from its
shapes. ``bench/counts/<config>.py`` binds them to one configuration.

Counts are of the work the algorithm needs, whatever implements it: a
matrix product (M, K) @ (K, N) is 2*M*K*N operations and reads x and W
once and writes y once; attention over a context of T keys is
2*2*T*d operations per query per layer (scores and the weighted sum).
"""

from __future__ import annotations

from typing import Tuple


def projections(d: int, ff: int) -> Tuple[Tuple[int, int], ...]:
    """(K, N) of the perturbed projections of one layer: q, k, v, o,
    fc1, fc2."""
    return ((d, d), (d, d), (d, d), (d, d), (d, ff), (ff, d))


def matmul_work(m: int, k: int, n: int, x_bytes: int, w_bytes: int,
                y_bytes: int) -> Tuple[float, float]:
    return 2.0 * m * k * n, float(m * k * x_bytes + k * n * w_bytes
                                  + m * n * y_bytes)


def zo_matmul_step(d: int, ff: int, layers: int, tokens: int,
                   act_bytes: int, w_bytes: int,
                   forwards: int = 2) -> Tuple[float, float]:
    """Work of the layer projections of one fused ZO step: ``forwards``
    perturbed forwards over ``tokens`` rows. z is generated, not read."""
    flops = bytes_ = 0.0
    for k, n in projections(d, ff):
        f, b = matmul_work(tokens, k, n, act_bytes, w_bytes, act_bytes)
        flops += f
        bytes_ += b
    return forwards * layers * flops, forwards * layers * bytes_


def matmul_params(d: int, ff: int, layers: int, head_out: int) -> int:
    """Parameters of every matrix a token multiplies (not embeddings)."""
    return layers * sum(k * n for k, n in projections(d, ff)) + d * head_out


def forward_flops(d: int, ff: int, layers: int, head_out: int,
                  tokens: int, context: float) -> float:
    """One forward over ``tokens`` tokens whose queries each see
    ``context`` keys on average."""
    return tokens * (2.0 * matmul_params(d, ff, layers, head_out)
                     + 4.0 * layers * d * context)

