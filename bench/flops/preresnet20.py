"""FLOPs that FeDepth's Algorithm 1 requires of PreResNet-20, counted from
the configuration's shapes (never from a compiled program).

Counted: the multiply-adds of convolutions and the classifier, as 2 FLOPs
each; normalisation, activations and pooling are left out.  Per SGD step
of subproblem [lo, hi): forward and backward of its units and the head,
3x their forward.  The frozen prefix runs forward once per distinct batch:
the stem and units [0, lo_last), telescoped, since the ResNet prefix does
not change while later blocks train.  Recomputation is not counted.
"""
from __future__ import annotations


def _units(sizes):
    """Forward FLOPs per image of the stem and of each residual unit."""
    h = sizes["image_size"]
    w0 = sizes["base_widths"][0]
    stem = 2 * h * h * 9 * sizes["in_channels"] * w0
    units, c_in = [], w0
    for s, (n, w) in enumerate(zip(sizes["stage_blocks"],
                                   sizes["base_widths"])):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            ho = h // stride
            f = 2 * ho * ho * 9 * (c_in * w + w * w)
            if stride != 1 or c_in != w:
                f += 2 * ho * ho * c_in * w
            units.append(f)
            c_in, h = w, ho
    return stem, units


def client_flops(sizes, traffic, blocks) -> float:
    """One client's depth-wise update over ``blocks``, the [lo, hi) of
    each subproblem."""
    stem, units = _units(sizes)
    head = 2 * sizes["base_widths"][-1] * sizes["num_classes"]
    bs = traffic["batch_size"]
    n_batches = max(1, traffic["samples_per_client"] // bs)
    steps = traffic["local_steps"] * n_batches
    train = sum(3 * (sum(units[lo:hi]) + head) * steps * bs
                for lo, hi in blocks)
    lo_last = blocks[-1][0]
    prefix = (stem + sum(units[:lo_last])) * bs * n_batches
    return float(train + prefix)


def kernels(sizes, traffic) -> dict:
    """This configuration runs no Pallas kernel."""
    return {}
