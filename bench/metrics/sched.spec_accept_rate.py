"""Share of drafted tokens the verifier accepted in the window
(``EngineStats.spec_accepted / spec_drafted``, counted over the window
only)."""

UNIT, BETTER, MOVES = "%", "higher", "itl_p90_ms"


def read(view):
    s = view.record["serve"]
    return 100.0 * s["spec_accepted"] / s["spec_drafted"] \
        if s["spec_drafted"] else None
