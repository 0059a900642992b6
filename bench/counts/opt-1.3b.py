"""Operation and byte counts of opt-1.3b (bench/configs/opt-1.3b.json):
24 layers, d 2048, ff 8192, vocab 50272, bf16 weights and activations."""

from bench.harness import counts as C

D, FF, LAYERS, VOCAB = 2048, 8192, 24, 50272
W_BYTES = ACT_BYTES = KV_BYTES = 2


def zo_matmul_step(batch: int, seq: int):
    """(flops, bytes) of the perturbed projections of one fused step.
    The LM head (2048 x 50272) is not kernel-aligned and runs outside
    ``zo_matmul``, so it is not counted."""
    return C.zo_matmul_step(D, FF, LAYERS, batch * seq, ACT_BYTES, W_BYTES)


def train_step_flops(batch: int, seq: int) -> float:
    """Model operations of one fused step: two causal forwards."""
    return 2 * C.forward_flops(D, FF, LAYERS, VOCAB, batch * seq,
                               (seq + 1) / 2)


def token_flops(context: float, head: bool = True) -> float:
    """One token's forward at ``context`` keys, with or without the LM
    head (a prompt token whose logits are not needed)."""
    f = C.forward_flops(D, FF, LAYERS, VOCAB, 1, context)
    return f if head else f - 2.0 * D * VOCAB


def attention_work(q_keys: float, slot_keys: float):
    """Paged attention over the live positions: ``q_keys`` summed keys
    seen by every query row, ``slot_keys`` summed keys read per
    (call, slot)."""
    return {"flops": 4.0 * q_keys * D * LAYERS,
            "bytes": 2.0 * slot_keys * D * KV_BYTES * LAYERS}
