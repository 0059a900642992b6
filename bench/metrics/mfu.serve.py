"""Operations that the tokens served in the window require, over the
wall time spent inside ``ServeEngine.step`` in the window, over the
chip's bf16 peak. Counted: each prompt token prefilled (its forward
without the LM head, the last one with it) and each token committed
(a forward with the head at its context); rejected drafts and discarded
verify positions are not."""

UNIT, BETTER, MOVES = "%", "higher", "itl_p90_ms"


def read(view):
    s = view.record["serve"]
    if not s["step_s"]:
        return None
    flops = 0.0
    c = view.counts
    for rid, done in s["prefill_tokens"].items():
        flops += sum(c.token_flops(p + 1, head=False) for p in range(done))
        flops += c.token_flops(done, head=True) - c.token_flops(done, False)
    for rid, n in s["generated"].items():
        plen = s["prompt_len"][rid]
        flops += sum(c.token_flops(plen + i) for i in range(1, n))
    return 100.0 * flops / s["step_s"] / view.peaks["bf16_flops"]
