"""Share of its roofline that the fused perturbed matmul reaches on the
FFN projections (fc1, fc2) of the train step: the least time the chip
needs for their work in the step programs traced (two forwards x layers,
``bench/counts``), over the summed device time of ``zo_matmul`` events
under a ``zo_matmul.<path>/mlp/`` scope."""

from bench.harness import program as P

UNIT, BETTER, MOVES = "%", "higher", "train_tok_s"


def read(view):
    return P.matmul_roofline(view, "mlp", slice(4, 6))
