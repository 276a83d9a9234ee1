"""Operation and byte counts against shapes worked out by hand, for both
configurations of the benchmark."""

import json
import os

import pytest

from bench.harness import model_math as M

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


# stablelm-3b: MHA 32 x 80 = 2560 wide; q, k, v, o each 2560 x 2560;
# SwiGLU MLP 3 x 2560 x 6912; two layer norms (scale and bias) per layer
STABLELM_LAYER = 4 * 2560 * 2560 + 3 * 2560 * 6912            # 79,298,560
STABLELM_TOTAL = (32 * (STABLELM_LAYER + 2 * 2 * 2560)
                  + 2 * 50304 * 2560 + 2 * 2560)             # 2,795,443,200
# yi-9b-24l: q and o 4096 x 4096, k and v 4096 x (4 x 128); MLP 3 x 4096
# x 11008; two RMS norms (scale) per layer; 24 layers
YI_LAYER = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
YI_TOTAL = 24 * (YI_LAYER + 2 * 4096) + 2 * 64000 * 4096 + 4096


def test_param_counts_by_hand():
    s, y = cfg("stablelm-3b"), cfg("yi-9b-24l")
    assert M.layer_matmul_params(s) == STABLELM_LAYER == 79_298_560
    assert M.param_counts(s)["total"] == STABLELM_TOTAL == 2_795_443_200
    assert M.layer_matmul_params(y) == YI_LAYER
    assert M.param_counts(y)["total"] == YI_TOTAL == 4_676_849_664


@pytest.mark.parametrize("name", ["stablelm-3b", "yi-9b-24l"])
def test_param_counts_match_the_program(name):
    """The program's own parameter tree (shapes only) has as many."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models.model_zoo import analytic_param_count

    c = cfg(name)
    base = get_arch(c["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    arch = dataclasses.replace(base, **{k: c[k] for k in c["reduced"]
                                        if k in fields})
    assert analytic_param_count(arch) == M.param_counts(c)["total"]


def test_kv_bytes_per_token():
    assert M.kv_bytes_per_token(cfg("stablelm-3b")) == 2 * 32 * 32 * 80 * 2
    assert M.kv_bytes_per_token(cfg("stablelm-3b")) == 327_680
    assert M.kv_bytes_per_token(cfg("yi-9b-24l")) == 2 * 24 * 4 * 128 * 2
    assert M.kv_bytes_per_token(cfg("yi-9b-24l")) == 49_152


def test_token_and_prefill_flops():
    s = cfg("stablelm-3b")
    head = 2 * 2560 * 50304
    attn = 4 * 32 * 32 * 80               # per position of context
    assert M.token_flops(s, 100) == 2 * 32 * STABLELM_LAYER + attn * 100 \
        + head
    assert M.token_flops(s, 100, with_head=False) == \
        2 * 32 * STABLELM_LAYER + attn * 100
    # a 3-token prompt: contexts 1, 2, 3 and one head
    assert M.prefill_flops(s, 3) == 3 * 2 * 32 * STABLELM_LAYER \
        + attn * 6 + head


def test_decode_step_cost():
    y = cfg("yi-9b-24l")
    flops, byts = M.decode_step_cost(y, [100, 300])
    assert flops == M.token_flops(y, 100) + M.token_flops(y, 300)
    weights = YI_TOTAL - 64000 * 4096       # every weight but the table
    assert byts == 2 * (weights + 2 * 4096) + 400 * 49_152
    # at 8 lanes decode is bound by memory: ~8 operations per byte
    f8, b8 = M.decode_step_cost(y, [576] * 8)
    t, bound = M.least_time(f8, b8, {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(b8 / 819e9)
    t, bound = M.least_time(1e15, 1.0, {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
