"""Model operations of the fused steps completed in the window (two
forwards each, ``bench/counts``) per second of the window, over the
chip's bf16 peak. roberta-large runs in float32, for which the v5e
states no peak; its share is taken against the bf16 peak too."""

UNIT, BETTER, MOVES = "%", "higher", "train_tok_s"


def read(view):
    t = view.record["train"]
    if not t["steps"]:
        return None
    rate = t["steps"] * view.counts.train_step_flops(t["batch"], t["seq"]) \
        / view.record["window_s"]
    return 100.0 * rate / view.peaks["bf16_flops"]
