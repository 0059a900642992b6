"""Device time of the attention core in the train step (scores, mask,
softmax and the weighted sum; the projections carry their own
``zo_matmul.<path>`` scopes): ms of ops under the program's
``runtime.attention`` scope per run of the step program traced. None
where the program names no such scope."""

from bench.harness import program as P

UNIT, BETTER, MOVES = "ms", "lower", "train_tok_s"


def read(view):
    scope = getattr(P.obs, "ATTENTION", None)
    if scope is None:
        return None
    return P.per_step_ms(view, P.segment(scope))
