"""Peaks and the operation and byte counts the roofline shares read."""

import pytest

from chipbench import roofline

DANUBE = dict(n_layers=24, d_model=3840, n_heads=32, n_kv_heads=8,
              d_ff=10240, vocab_size=32000, sliding_window=4096)
STARCODER = dict(n_layers=16, d_model=4608, n_heads=36, n_kv_heads=4,
                 d_ff=18432, vocab_size=49152, mlp_variant="gelu")


def test_int4_linear_cost_matches_a_hand_count():
    # 16 rows through danube's up projection, 3840 -> 10240
    ops, nbytes = roofline.int4_linear_cost(16, 3840, 10240)
    assert ops == 2 * 16 * 3840 * 10240 == 1_258_291_200
    # nibbles 19_660_800 + f32 scales 40_960 + int8 in 61_440
    # + int32 out 655_360
    assert nbytes == 19_660_800 + 40_960 + 61_440 + 655_360


def test_min_time_takes_the_binding_bound():
    peak = roofline.peaks("TPU v5 lite")
    ops, nbytes = roofline.int4_linear_cost(16, 3840, 10240)
    t = roofline.min_time_s(ops, nbytes, peak["int8_ops"],
                            peak["hbm_bytes_per_s"])
    assert t == pytest.approx(nbytes / 819e9)      # a GEMV: bandwidth
    ops, nbytes = roofline.int4_linear_cost(4096, 4608, 18432)
    t = roofline.min_time_s(ops, nbytes, peak["int8_ops"],
                            peak["hbm_bytes_per_s"])
    assert t == pytest.approx(ops / 393e12)        # 4096 rows: compute


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_layer_linears_follow_the_mlp_variant():
    assert [n for n, _, _ in roofline.layer_linears(DANUBE)] == \
        ["wq", "wk", "wv", "wo", "up", "gate", "down"]
    shapes = roofline.layer_linears(STARCODER)
    assert [n for n, _, _ in shapes] == ["wq", "wk", "wv", "wo", "up", "down"]
    assert ("wk", 4608, 512) in shapes


def test_forward_min_time_sums_every_linear():
    peak = roofline.peaks("TPU v5 lite")
    one = dict(DANUBE, n_layers=1)
    want = sum(roofline.min_time_s(*roofline.int4_linear_cost(16, k, n),
                                   393e12, 819e9)
               for _, k, n in roofline.layer_linears(DANUBE))
    assert roofline.forward_min_time_s(one, 16, peak, False) == \
        pytest.approx(want)
    head = roofline.min_time_s(*roofline.int4_linear_cost(16, 3840, 32000),
                               393e12, 819e9)
    assert roofline.forward_min_time_s(one, 16, peak, True) == \
        pytest.approx(want + head)


def test_prompt_flops_is_the_sum_over_its_positions():
    for model in (DANUBE, STARCODER, dict(DANUBE, sliding_window=8)):
        assert roofline.prompt_flops(model, 40) == sum(
            roofline.position_flops(model, p) for p in range(40))


def test_matmul_params_of_starcoder2():
    layers, head = roofline.matmul_params(STARCODER)
    per_layer = 4608 * 4608 * 2 + 4608 * 512 * 2 + 2 * 4608 * 18432
    assert layers == 16 * per_layer
    assert head == 4608 * 49152


def test_model_flops_spreads_the_prompt_over_its_prefill():
    """A prompt's FLOPs are spread over the steps from submit to first
    token (however long each took); decoded positions and the LM head
    count when their token lands."""
    from chipbench.harness import Rec, model_flops

    model = dict(n_layers=2, d_model=256, n_heads=2, n_kv_heads=1,
                 d_ff=512, vocab_size=1000)
    r = Rec(client=0, prompt_len=64, max_new=3, t_submit=0.0,
            times=[10.0, 11.0, 12.0])
    # a slow first step (a compile), then three more before the first token
    steps = [7.0, 8.0, 9.0, 10.0, 11.0, 12.0]
    head = roofline.lm_head_flops(model)
    prompt = roofline.prompt_flops(model, 64)
    got = model_flops(model, [r], steps, 8.5, 11.0)
    want = prompt / 2 + 2 * head + roofline.position_flops(model, 64)
    assert got == pytest.approx(want)
    # a window before the first step holds none of the prompt
    assert model_flops(model, [r], steps, 0.0, 6.0) == 0
    assert model_flops(model, [r], steps, 0.0, 7.0) == pytest.approx(
        prompt / 4)
