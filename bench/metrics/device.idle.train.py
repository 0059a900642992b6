"""Share of the traced window of a train cell in which no operation ran
on the chip: 1 - (union of device op intervals) / window."""

from bench.harness import trace as TR

UNIT, BETTER, MOVES = "%", "lower", "train_tok_s"


def read(view):
    w = TR.window_ns(view.events)
    return 100.0 * (1.0 - TR.busy_ns(view.events) / w) if w else None
