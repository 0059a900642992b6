"""90th percentile of the time requests finished in the window waited
in the engine's queue before admission (``Completion.queue_wait_s``, the
engine's own clock from the request's due time)."""

import numpy as np

UNIT, BETTER, MOVES = "ms", "lower", "ttft_p90_ms"


def read(view):
    w = view.record["serve"]["queue_wait_s"]
    return 1e3 * float(np.percentile(w, 90)) if w else None
