"""Operation and byte counts of roberta-large
(bench/configs/roberta-large.json): 24 layers, d 1024, ff 4096,
2-class head, f32 weights and activations."""

from bench.harness import counts as C

D, FF, LAYERS, CLASSES = 1024, 4096, 24, 2
W_BYTES = ACT_BYTES = 4


def zo_matmul_step(batch: int, seq: int):
    """(flops, bytes) of the perturbed projections of one fused step."""
    return C.zo_matmul_step(D, FF, LAYERS, batch * seq, ACT_BYTES, W_BYTES)


def train_step_flops(batch: int, seq: int) -> float:
    """Model operations of one fused step: two bidirectional forwards
    (the 2-class head reads one position per row)."""
    body = C.forward_flops(D, FF, LAYERS, 0, batch * seq, seq)
    return 2 * (body + 2.0 * batch * D * CLASSES)
