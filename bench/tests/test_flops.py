import pytest

import flops
import model


def _glm():
    return model.sizes_of(model.load_config("glm4-9b"))


def test_matmul_params_by_hand():
    s = _glm()
    # q and o: 4096 x 32 x 128 each; k and v: 4096 x 2 x 128 each;
    # SwiGLU: three 4096 x 13696
    assert flops.matmul_params_per_layer(s) == \
        2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696


def test_decode_and_prefill_flops_by_hand():
    s = _glm()
    per_tok = 2 * (2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696) * 20
    head = 2 * 9728 * 4096
    # one token at position 9 attends over 10 keys: 4 * heads * head_dim
    assert flops.decode_flops(s, 1, 10) == per_tok + 4 * 32 * 128 * 20 * 10 + head
    # a 3-token prompt: keys 1 + 2 + 3, one head product
    assert flops.prefill_flops(s, 3) == 3 * per_tok + 4 * 32 * 128 * 20 * 6 + head
    assert flops.model_flops(s, [3], [(1, 10)]) == \
        flops.prefill_flops(s, 3) + flops.decode_flops(s, 1, 10)


def test_kernel_costs_by_hand():
    f, b = flops.shard_matmul_cost((2, 128, 256), (256, 8))
    assert f == 2 * 256 * 256 * 8
    assert b == 4 * (256 * 256 + 256 * 8 + 256 * 8)
    f, b = flops.gen_parity_cost(n=100, L=1024, D=512, C=4)
    assert f == 2 * 4 * (1024 * 512 + 100 * 1024)
    assert b == 4 * (1024 * 512 + 512 * 4 + 100 * 4 + 100)


def test_products_least_seconds_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    calls = [{"tiles": (1, 128, 128), "x": (128, 1), "parity": []}]
    t, bound = flops.products_least_seconds(calls, peaks)
    f, b = flops.shard_matmul_cost((1, 128, 128), (128, 1))
    assert bound == "memory" and t == pytest.approx(b / 1e9)
    assert t > f / 1e12
