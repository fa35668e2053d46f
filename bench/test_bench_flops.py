"""The analytic FLOP and byte counts against hand counts for one small
decomposition of each configuration."""
import pytest

import harness

RESNET = {"stage_blocks": [1, 1, 1], "base_widths": [4, 8, 16],
          "image_size": 8, "in_channels": 3, "num_classes": 10}
LM = {"num_layers": 4, "d_model": 8, "vocab_size": 32, "ssm_state_dim": 4,
      "ssm_head_dim": 4, "ssm_num_heads": 4, "ssm_expand": 2,
      "conv_kernel": 4}


def flops_module(config):
    return harness.load_module(harness.BENCH / "flops" / f"{config}.py")


def test_preresnet_client_flops_by_hand():
    f = flops_module("preresnet20")
    # per image, forward: stem 2*8*8*9*3*4 = 13824
    # unit 0 (4->4, s1, 8x8): 2*64*9*(16+16) = 36864
    # unit 1 (4->8, s2, 4x4): 2*16*9*(32+64) + proj 2*16*32 = 28672
    # unit 2 (8->16, s2, 2x2): 2*4*9*(128+256) + proj 2*4*128 = 28672
    # head 2*16*10 = 320
    traffic = {"batch_size": 2, "samples_per_client": 4, "local_steps": 3}
    blocks = ((1, 2), (2, 3))
    steps, bs, nb = 3 * 2, 2, 2
    train = 3 * ((28672 + 320) + (28672 + 320)) * steps * bs
    prefix = (13824 + 36864 + 28672) * bs * nb   # stem + units [0, 2)
    assert f.client_flops(RESNET, traffic, blocks) == train + prefix
    assert f.kernels(RESNET, traffic) == {}


def test_mamba2_client_flops_by_hand():
    f = flops_module("mamba2-370m")
    # per token, one layer: in_proj 2*8*(16+16+8+4)=704, out_proj
    # 2*16*8=256, conv 2*4*16=128, scan 5*4*4*4=320 -> 1408; head 2*8*32
    layer, head = 1408, 512
    assert f.layer_flops_per_token(LM) == layer
    assert f.head_flops_per_token(LM) == head
    traffic = {"batch_size": 2, "seq_len": 16, "samples_per_client": 2,
               "local_steps": 2}
    blocks = ((1, 3), (3, 4))
    tokens, steps = 32, 2
    train = 3 * ((2 * layer + head) + (layer + head)) * steps
    prefix = (1 + 3) * layer          # from scratch in every subproblem
    assert f.client_flops(LM, traffic, blocks) == (train + prefix) * tokens


def test_mamba2_kernel_costs_by_hand():
    f = flops_module("mamba2-370m")
    traffic = {"batch_size": 2, "seq_len": 16}
    k = f.kernels(LM, traffic)
    # SSD, one time tile of 16: B*H*(2*Q^2*(N+P) + 4*Q*N*P)
    assert k["ssd"][0] == 2 * 4 * (2 * 256 * 8 + 4 * 16 * 16)
    # bytes: x and y 2*B*T*H*P, dt B*T*H, B and C 2*B*T*N, states
    # 2*B*H*P*N, A and D 2*H; float32
    assert k["ssd"][1] == 4 * (2 * 512 + 128 + 2 * 128 + 2 * 128 + 8)
    # CE: logits 2*BT*d*V; hidden, weight, labels and losses once
    assert k["ce"] == (2 * 32 * 8 * 32, 4 * (32 * 8 + 8 * 32 + 64))


@pytest.mark.parametrize("config", ["preresnet20", "mamba2-370m"])
def test_flops_grow_with_the_work(config):
    f = flops_module(config)
    sizes = RESNET if config == "preresnet20" else LM
    traffic = {"batch_size": 2, "samples_per_client": 4, "local_steps": 1,
               "seq_len": 16}
    one = ((1, 3),)
    two = ((1, 2), (2, 3))
    assert f.client_flops(sizes, traffic, two) > f.client_flops(
        sizes, traffic, one)
    assert f.client_flops(sizes, {**traffic, "local_steps": 2}, one) \
        > f.client_flops(sizes, traffic, one)
