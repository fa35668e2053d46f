"""The window's share of the chip's bf16 peak: the FLOPs Algorithm 1
requires of every client of every round in the window
(``flops/<config>.py``), over the window and the peak."""


def read(view):
    flops = sum(view.flops.client_flops(view.sizes, view.traffic, blocks)
                for cohort in view.cohorts for blocks in cohort)
    if not flops:
        return None
    return 100.0 * flops / view.window_s / view.peak["bf16_flops_per_s"]
