"""FLOPs and bytes that FeDepth's Algorithm 1 and the Pallas kernels
require of mamba2-370m, counted from the configuration's shapes (never
from a compiled program).

Model FLOPs per token, forward: in_proj and out_proj (2 per multiply-add),
the depthwise conv, and the state-space recurrence (5 H P N: decay,
input outer product and add for the state, C . state for the output);
the tied head's logits, 2 d V.  Per SGD step of subproblem [lo, hi):
forward and backward of its layers and the head, 3x their forward.  The
frozen prefix runs forward from scratch once per distinct batch in every
subproblem: the tied embedding trains with the head, so the prefix
changes between subproblems.  Recomputation (remat) is not counted.
"""
from __future__ import annotations

SSD_BLOCK_T = 128   # the SSD kernel's time tile (``ops.mamba2`` default)
F32 = 4


def _dims(sizes):
    d = sizes["d_model"]
    din = sizes["ssm_expand"] * d
    return (d, din, sizes["ssm_state_dim"], sizes["ssm_num_heads"],
            sizes["ssm_head_dim"], sizes["vocab_size"], sizes["conv_kernel"])


def layer_flops_per_token(sizes) -> float:
    d, din, N, H, P, _, K = _dims(sizes)
    proj = 2 * d * (2 * din + 2 * N + H) + 2 * din * d
    return float(proj + 2 * K * din + 5 * H * P * N)


def head_flops_per_token(sizes) -> float:
    d, _, _, _, _, V, _ = _dims(sizes)
    return float(2 * d * V)


def client_flops(sizes, traffic, blocks) -> float:
    """One client's depth-wise update over ``blocks``, the [lo, hi) of
    each subproblem."""
    tokens = traffic["batch_size"] * traffic["seq_len"]
    n_batches = max(1, traffic["samples_per_client"] // traffic["batch_size"])
    steps = traffic["local_steps"] * n_batches
    layer, head = layer_flops_per_token(sizes), head_flops_per_token(sizes)
    train = sum(3 * ((hi - lo) * layer + head) * steps
                for lo, hi in blocks)
    prefix = sum(lo * layer for lo, _ in blocks) * n_batches
    return float((train + prefix) * tokens)


def kernels(sizes, traffic) -> dict:
    """FLOPs and least bytes of one call of each kernel at the cell's
    shapes.  SSD: the chunked form the kernel computes, per time tile Q
    and head, 2 Q^2 (N + P) + 4 Q N P; bytes of x, dt, B, C, A, D, the
    initial and final state and y, each moved once.  CE: the logits,
    2 BT d V; bytes of the hidden states, the head weight, the labels and
    the per-token losses, each once."""
    d, _, N, H, P, V, _ = _dims(sizes)
    B, T = traffic["batch_size"], traffic["seq_len"]
    Q = min(SSD_BLOCK_T, T)
    chunks = -(-T // Q)
    ssd_flops = B * H * chunks * (2 * Q * Q * (N + P) + 4 * Q * N * P)
    ssd_bytes = F32 * (2 * B * T * H * P + B * T * H + 2 * B * T * N
                       + 2 * B * H * P * N + 2 * H)
    BT = B * T
    ce_flops = 2 * BT * d * V
    ce_bytes = F32 * (BT * d + d * V + 2 * BT)
    return {"ssd": (float(ssd_flops), float(ssd_bytes)),
            "ce": (float(ce_flops), float(ce_bytes))}
