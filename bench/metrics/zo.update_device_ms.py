"""Device time of the update sweep in the train step: ms of ops under the
program's ``zo.update`` scope per run of the step program traced."""

from bench.harness import program as P

UNIT, BETTER, MOVES = "ms", "lower", "train_tok_s"


def read(view):
    if P.obs is None:
        return None
    return P.per_step_ms(view, P.segment(P.obs.UPDATE))
