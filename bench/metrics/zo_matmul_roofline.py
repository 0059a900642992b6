"""Share of its roofline that the fused perturbed matmul reaches in the
train step: the least time the chip needs for the work of the layer
projections of the steps in the window (``bench/counts``: operations
over the bf16 peak, or bytes over HBM bandwidth, whichever is larger),
over the summed device time of the ``zo_matmul`` kernel's events."""

UNIT, BETTER, MOVES = "%", "higher", "train_tok_s"
KERNEL = r"zo_matmul"


def read(view):
    s, n = view.device_s(KERNEL)
    steps = view.record["train"]["steps"]
    if not n or not steps:
        return None
    t = view.record["train"]
    flops, bytes_ = view.counts.zo_matmul_step(t["batch"], t["seq"])
    # the steps traced: every step program in the window
    _, n_prog = view.device_s(r"_jit_step", modules=True)
    least = max(flops / view.peaks["bf16_flops"],
                bytes_ / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least * n_prog / s
