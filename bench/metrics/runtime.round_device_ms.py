"""Device time of one speculative round: the summed device time of the
engine's draft, verify and commit programs in the traced window over the
number of draft programs (one per round)."""

UNIT, BETTER, MOVES = "ms", "lower", "itl_p90_ms"
ROUND = r"draft_spec|verify_spec|commit_spec"
DRAFT = r"draft_spec"


def read(view):
    s, _ = view.device_s(ROUND, modules=True)
    _, rounds = view.device_s(DRAFT, modules=True)
    return s / rounds * 1e3 if rounds else None
