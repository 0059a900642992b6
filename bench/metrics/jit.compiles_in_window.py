"""Compiles (or compile-cache loads) inside the traced window: the
program's ``repro.jit.compile`` spans that begin there. None where the
program records no span of its own."""

from bench.harness import program as P

UNIT, BETTER, MOVES = "count", "lower", "train_tok_s"


def read(view):
    prog = P.load(view)
    if prog is None or not prog.has_spans():
        return None
    return prog.count(P.obs.PREFIX + P.obs.COMPILE)
