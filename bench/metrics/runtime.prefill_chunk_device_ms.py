"""Device time per chunked-prefill program run (``prefill_chunk``) in
the traced window."""

UNIT, BETTER, MOVES = "ms", "lower", "ttft_p90_ms"
PROGRAM = r"prefill_chunk"


def read(view):
    s, n = view.device_s(PROGRAM, modules=True)
    return s / n * 1e3 if n else None
