"""Device time of one fused ZO step: the summed device time of the
trainer's step program (jit ``_jit_step_donate``) in the traced window
over the number of its runs there."""

UNIT, BETTER, MOVES = "ms", "lower", "train_tok_s"
PROGRAM = r"_jit_step"


def read(view):
    s, n = view.device_s(PROGRAM, modules=True)
    return s / n * 1e3 if n else None
