"""Each shapes->operations function against a count made by hand."""
import json
import os

from perf.refs import gpt2_medium, resnet50_v1

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet_first_convolution_by_hand():
    cfg = _cfg("resnet50_v1")
    name, out_ch, in_ch, k, stride, pad, bias, hw, dgrad = \
        resnet50_v1.conv_layers(cfg)[0]
    # 7x7, 3 -> 64 channels, stride 2, 224 px -> 112 px; the image needs no
    # gradient
    assert (name, out_ch, in_ch, k, stride, pad, bias, hw, dgrad) == \
        ("conv0", 64, 3, 7, 2, 3, False, 112, False)
    # multiply-adds per image: 64*3*7*7 * 112*112 = 118,013,952
    assert out_ch * in_ch * k * k * hw * hw == 118_013_952


def test_resnet_whole_network():
    cfg = _cfg("resnet50_v1")
    layers = resnet50_v1.conv_layers(cfg)
    assert len(layers) == 53                   # 1 + 3*16 + 4 shortcuts
    assert [l[7] for l in layers if l[0].endswith("conv0")] == \
        [112, 56, 28, 14, 7]
    fwd = resnet50_v1.fwd_flops(cfg, {"batch": 1})
    # the well-known 4.1 G multiply-adds of ResNet-50 at 224 px (stride on
    # the 1x1 convolution, as v1 has it: 3.86 G)
    assert 3.8e9 < fwd / 2 < 4.2e9
    step = resnet50_v1.step_flops(cfg, {"batch": 1})
    assert step == 3 * fwd - 2 * 118_013_952


def test_gpt2_one_layer_by_hand():
    cfg = dict(_cfg("gpt2_medium"), n_layer=1, vocab_size=0)
    wl = {"batch": 1, "seq_len": 1024}
    # per token: q, k, v, out: 4 * 1024^2; ffn: 2 * 1024 * 4096 multiply-adds
    per_token = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert per_token == 12_582_912
    # causal scores and values: 2 products * 2 ops * T*T/2 * d
    attn = 2 * 1024 * 1024 * 1024
    assert gpt2_medium.fwd_flops(cfg, wl) == 2 * per_token * 1024 + attn


def test_gpt2_whole_step():
    cfg = _cfg("gpt2_medium")
    wl = {"batch": 2, "seq_len": 1024}
    per_token = 24 * 12_582_912 + 1024 * 50257
    fwd = 2 * per_token * 2048 + 24 * 2 * (2 * 1024 * 1024 * 1024)
    assert gpt2_medium.step_flops(cfg, wl) == 3 * fwd
    n = sum(int.__mul__(*(s + (1,))[:2]) if len(s) == 2 else s[0]
            for _, s, _, _ in gpt2_medium.param_spec(cfg))
    assert 404e6 < n < 408e6                   # 405 M with the untied head
