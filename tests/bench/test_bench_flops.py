"""The analytic counts on a toy shape, worked by hand."""
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)

from bench import flops

# d=4, 2 layers, 2 heads / 1 kv head of dim 2, ff 8, vocab 10, SwiGLU
TOY = {"hidden_size": 4, "num_hidden_layers": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
       "vocab_size": 10, "hidden_act": "silu"}


def test_params_by_hand():
    m = flops.dims(TOY)
    # attention 4*2*(2*2 + 2*1) = 48, MLP 3*4*8 = 96
    assert m.layer_params == 144
    assert m.body_params == 288
    assert m.unembed_params == 40
    # k and v, 1 kv head of dim 2, 2 layers, 2 bytes
    assert m.kv_line_bytes == 2 * 2 * 1 * 2 * 2


def test_prefill_by_hand():
    m = flops.dims(TOY)
    # positions 0,1,2 attend 1,2,3: 4*2*2*2*(1+2+3) = 192
    f, b = flops.prefill(m, 3)
    assert f == 2 * 288 * 3 + 2 * 40 + 192
    assert b == (288 + 40) * 2 + 3 * 4 * 2 + 3 * 16


def test_decode_step_by_hand():
    m = flops.dims(TOY)
    f, b = flops.decode_step(m, [4, 9])
    assert f == 2 * 2 * (288 + 40) + 4 * 2 * 2 * 2 * (5 + 10)
    assert b == (288 + 40) * 2 + 2 * (4 * 2 + 16) + (5 + 10) * 16
    assert flops.decode_step(m, []) == (0, 0)


def test_window_caps_attended():
    m = flops.dims(dict(TOY, sliding_window=4, hidden_act="gelu_pytorch_tanh"))
    assert m.layer_params == 48 + 2 * 4 * 8
    assert [m.attended(p) for p in (0, 3, 4, 100)] == [1, 4, 4, 4]


def test_min_time_picks_the_binding_roof():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert flops.min_time(1000, 50, peaks) == (10.0, "compute")
    assert flops.min_time(100, 500, peaks) == (50.0, "memory")
    with pytest.raises(KeyError):
        flops.min_time(1, 1, {})
