"""The FLOP and byte functions against hand-worked values: the chip's in
`lib/arith.py`, the model's in its family's `arith.py`."""
import pytest

from lib import arith as chip, harness

arith = harness.load_family("gpt2").arith
GPT2M = harness.load_json("configs", "gpt2-medium.json")
CEREBRAS = harness.load_json("configs", "cerebras-gpt-1.3b.json")


def test_matmul_parameters():
    # 24 * (4 * 1024^2 + 2 * 1024 * 4096) + 1024 * 50257
    assert arith.matmul_params(GPT2M) == 301_989_888 + 51_463_168
    # 24 * (4 * 2048^2 + 2 * 2048 * 8192) + 2048 * 50257
    assert arith.matmul_params(CEREBRAS) == 1_207_959_552 + 102_926_336
    assert round(arith.param_count(GPT2M) / 1e6, 1) == 405.1
    assert round(arith.param_count(CEREBRAS) / 1e9, 2) == 1.41


def test_train_flops_per_token_matches_pr22s_convention():
    # 6 * 353,453,056 + 6 * 24 * 1024 * 1024 = 2.2717e9; PR 22 read 35.141 %
    # MFU at 30,469 tokens/s on a 197 TFLOP/s chip with the same arithmetic
    f = arith.train_flops_per_token(GPT2M, 1024)
    assert f == 6 * 353_453_056 + 150_994_944
    assert 100 * f * 30469 / 197e12 == pytest.approx(35.14, abs=0.01)


def test_flash_kernels_need_1_44_tflop_a_step():
    # per layer forward 2 * 8 * 1024^2 * 1024 = 1.718e10, times 3.5, 24 layers
    assert arith.flash_train_flops(GPT2M, 8, 1024) == pytest.approx(1.443e12,
                                                                    rel=1e-3)
    # 12 tensors of 8 * 1024 * 1024 bf16 a layer
    assert arith.flash_train_bytes(GPT2M, 8, 1024) == 24 * 12 * 2 * 8 * 2**20
    t, bound = chip.roofline_seconds(
        arith.flash_train_flops(GPT2M, 8, 1024),
        arith.flash_train_bytes(GPT2M, 8, 1024), "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(7.32e-3, rel=1e-2)


def test_a_tensor_parallel_chip_holds_its_share_of_the_heads():
    # what `metrics/train_flash_roofline.py` asks for on dp 2 x tp 2: 4 rows
    # and half of the width a chip
    assert arith.flash_train_flops(CEREBRAS, 4, 2048, 2) == (
        24 * 3.5 * 2.0 * 4 * 2048 * 2048 * 1024)
    assert arith.flash_train_bytes(CEREBRAS, 4, 2048, 2) == (
        24 * 12.0 * 4 * 2048 * 1024 * 2)
    assert arith.kv_bytes_per_token(CEREBRAS) == 196_608


def test_forward_flops():
    assert arith.forward_flops(CEREBRAS, 10, 0) == 20 * 1_310_885_888
    assert arith.forward_flops(CEREBRAS, 0, 5) == 4 * 24 * 2048 * 5


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        chip.peaks("TPU v9")
