"""Share of its roofline that paged attention reaches in serving: the
least time for the attention work of the live positions of every draft,
verify and prefill dispatch in the window (counted per call by the
benchmark; operations over the bf16 peak or K/V page bytes over HBM
bandwidth, whichever is larger), over the summed device time of the
paged-attention kernels' events."""

UNIT, BETTER, MOVES = "%", "higher", "itl_p90_ms"
KERNELS = r"flash_decode|flash_verify|flash_prefill|_paged_kernel|paged"


def read(view):
    att = view.record["serve"]["attention"]
    s, n = view.device_s(KERNELS)
    if not att or not n or not att["q_keys"]:
        return None
    work = view.counts.attention_work(att["q_keys"], att["slot_keys"])
    least = max(work["flops"] / view.peaks["bf16_flops"],
                work["bytes"] / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s
