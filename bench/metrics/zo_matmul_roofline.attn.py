"""Share of its roofline that the fused perturbed matmul reaches on the
attention projections (q, k, v, o) of the train step: the least time the
chip needs for their work in the step programs traced (two forwards x
layers, ``bench/counts``), over the summed device time of ``zo_matmul``
events under a ``zo_matmul.<path>/attn/`` scope."""

from bench.harness import program as P

UNIT, BETTER, MOVES = "%", "higher", "train_tok_s"


def read(view):
    return P.matmul_roofline(view, "attn", slice(0, 4))
