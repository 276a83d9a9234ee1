"""The DeepSeek-V3 share's cell: its configuration file is what the program
runs, its operation and byte counts are the algorithm's, its reference
judges no token where a rounding can choose other held experts, and a whole
run of the harness at a test size is correct while its float8 control is
not."""

import argparse
import copy
import dataclasses
import json
import os

import numpy as np
import pytest

from bench.harness import mla_moe_math as mm
from bench.harness.catalog import ROOT, Catalog
from bench.harness.clients import Record
from bench.harness.context import RunContext

NAME = "deepseek-v3-ep32-5l"
CELL = NAME + ".reason"
CONFIG_FILE = f"bench/configs/{NAME}.json"


def config_file() -> dict:
    with open(os.path.join(ROOT, CONFIG_FILE)) as f:
        return json.load(f)


def test_config_file_is_the_registry_entry():
    """``register()`` checks the dense fields; the MoE, MLA and YaRN keys
    it does not know are tied to the registry entry here."""
    from bench.run import register
    from repro.configs import get_arch

    cfg = config_file()
    assert register(cfg) == NAME
    a = get_arch(cfg["arch"])
    m, mla, y = a.moe, a.mla, a.yarn
    want = {
        "num_hidden_layers": a.num_layers, "num_layers": a.num_layers,
        "first_k_dense_replace": m.n_dense_layers,
        "n_dense_layers": m.n_dense_layers, "hidden_size": a.d_model,
        "intermediate_size": m.dense_d_ff,
        "moe_intermediate_size": m.d_ff, "d_ff": m.d_ff,
        "n_routed_experts": m.experts_held, "experts_held": m.experts_held,
        "experts_routed": m.num_experts, "expert_offset": m.expert_offset,
        "n_shared_experts": m.num_shared_experts,
        "num_experts_per_tok": m.top_k, "n_group": m.n_group,
        "topk_group": m.topk_group,
        "routed_scaling_factor": m.routed_scaling_factor,
        "q_lora_rank": mla.q_lora_rank,
        "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim,
        "v_head_dim": mla.v_head_dim, "num_attention_heads": a.num_heads,
        "num_key_value_heads": a.num_kv_heads, "vocab_size": a.vocab_size,
        "rope_theta": a.rope_theta,
        "tie_word_embeddings": a.tie_embeddings,
    }
    for k, v in want.items():
        assert cfg[k] == v, k
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn" and y.enabled
    for k in ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "mscale", "mscale_all_dim"):
        assert rs[k] == getattr(y, k), k
    # the held path's router is noaux_tc's, sigmoid-scored
    assert cfg["topk_method"] == "noaux_tc" and m.experts_held
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["norm_topk_prob"] is True
    # the published depth, dense layers and experts beside the cut
    base = get_arch("deepseek-v3-671b")
    assert cfg["published_num_layers"] == base.num_layers == 61
    assert cfg["published_n_dense_layers"] == base.moe.n_dense_layers == 3
    assert cfg["experts_routed"] == base.moe.num_experts == 256
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "num_nextn_predict_layers"]
    assert cfg["source"] == ("https://huggingface.co/deepseek-ai/DeepSeek-V3"
                             "/blob/main/config.json")


def toy() -> dict:
    return {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
            "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
            "v_head_dim": 2, "intermediate_size": 6,
            "moe_intermediate_size": 3, "vocab_size": 10,
            "experts_routed": 4, "experts_held": 2, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "num_hidden_layers": 2,
            "first_k_dense_replace": 1}


def test_counts_by_hand_at_a_toy_size():
    c = toy()
    # 8*4 + 4*2*4 + 8*6 + 4*2*2 + 4*2*2 + 2*2*8
    assert mm.mla_params(c) == 176
    pc = mm.param_counts(c)
    # attention 176 + norms 24; dense FFN 3*8*6; router 32 + bias 4;
    # shared expert 72; two held experts of 72
    assert pc["dense_layer"] == 344 and pc["moe_layer"] == 452
    assert pc["total"] == 344 + 452 + 80 + 80 + 8
    assert mm.latent_bytes_per_token(c) == 2 * 6 * 2
    # products: dense 2*(176+144), expert layer 2*(176+32+72+72*2*2/4);
    # latent attention 2*2*2*(4+4+2)*3; head 2*8*10
    assert mm.token_flops(c, 3) == 640 + 704 + 240 + 160
    # prefill of 3: products 3*1344, attention 2*2*2*(2+2+2)*6, head 160
    assert mm.prefill_flops(c, 3) == 3 * 1344 + 288 + 160
    # one lane reads one of the two held experts in expectation
    assert mm.held_experts_read(c, 1) == 1.0
    assert mm.step_weight_bytes(c, 1) == 2 * (964 - 80 - 144 + 72 + 8)
    f, b = mm.decode_step_cost(c, [3])
    assert f == 1744 and b == 1640 + 3 * 24
    f2, b2 = mm.decode_steps_cost(c, [3, 3], 2)
    assert f2 == 2 * f and b2 == 2 * b


def _ctx(cfg, ctxs, steps, seconds, window_s):
    recs = []
    for i, c in enumerate(ctxs):
        recs.append(Record(index=i, rid=f"r{i}", prompt_len=c - 1,
                           bucket=c - 1, max_new=2, req=None, submit_t=0.0,
                           times=[0.5, 1.0]))
    trace = {"programs": {"decode_step": {"count": steps,
                                          "seconds": seconds}},
             "window_s": window_s}
    return RunContext(cfg=cfg, mix={}, peaks={"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9},
                      chips=1, t0=0.0, t1=window_s, setup_s=0.0,
                      records=recs, trace=trace)


def test_shares_read_at_most_100_percent_for_an_ideal_time():
    cat = Catalog()
    cfg = config_file()
    ctxs = [300 + 17 * i for i in range(64)]
    flops, byts = mm.decode_steps_cost(cfg, ctxs, 1)
    least, bound = mm.least_time(flops, byts, cat.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.008 < least < 0.011
    roof = cat.reader("mla_moe_decode_roofline")
    assert roof.read(_ctx(cfg, ctxs, 1, least, 2.0)) == pytest.approx(100.0)
    assert roof.read(_ctx(cfg, ctxs, 1, 2 * least, 2.0)) == pytest.approx(
        50.0)
    mfu = cat.reader("mla_moe_mfu")
    work = sum(mm.token_flops(cfg, c) for c in ctxs)
    ideal = work / 197e12
    ctx = _ctx(cfg, ctxs, 1, least, ideal)
    ctx.records = [dataclasses.replace(r, times=[-1.0, ideal / 2])
                   for r in ctx.records]
    assert mfu.read(ctx) == pytest.approx(100.0)
    # nothing to read without a trace
    assert roof.read(dataclasses.replace(ctx, trace=None)) is None
    assert mfu.read(dataclasses.replace(ctx, trace=None)) is None


# --- the reference's route margins ----------------------------------------
PUBLISHED_ROUTER = {"experts_routed": 256, "n_group": 8, "topk_group": 4,
                    "num_experts_per_tok": 8, "expert_offset": 0,
                    "experts_held": 8, "routed_scaling_factor": 2.5}
TOY_ROUTER = {"experts_routed": 16, "n_group": 4, "topk_group": 2,
              "num_experts_per_tok": 4, "expert_offset": 4,
              "experts_held": 4, "routed_scaling_factor": 2.5}


def _reference():
    return Catalog().reference("mla_moe_decoder")


def _selection(c, n, seed):
    """Selection scores as the cell draws them: sigmoid of unit-variance
    router logits plus a bias of 0.1 sd."""
    rng = np.random.default_rng(seed)
    E = c["experts_routed"]
    return (1 / (1 + np.exp(-rng.standard_normal((n, E))))
            + rng.standard_normal(E) * 0.1).astype(np.float32)


@pytest.mark.parametrize("router", [PUBLISHED_ROUTER, TOY_ROUTER],
                         ids=["published", "toy"])
def test_scores_moved_within_the_margin_keep_the_held_experts(router):
    """Moving every selection score by less than half the largest tie
    below a token's route margin leaves its held experts chosen as they
    were, but for a rare second-order case (two group swaps at once): at
    most 1 in 2,000 of the moves tried, random and at the corners."""
    import jax.numpy as jnp

    ref = _reference()
    sel = _selection(router, 2000, 5)
    m = np.asarray(ref.route_margin(router, jnp.asarray(sel)))
    now = np.asarray(ref._held_chosen(router, ref._choose(router, sel)))
    grid = ref.TIE_GRID
    i = np.searchsorted(grid, m) - 1
    safe = np.where(i >= 0, grid[np.maximum(i, 0)], 0.0)[:, None]
    rng = np.random.default_rng(6)
    changed = tried = 0
    for t in range(16):
        d = (rng.uniform(-1, 1, sel.shape) if t % 2
             else rng.choice([-1.0, 1.0], sel.shape))
        moved = (sel + d * safe / 2 * 0.999).astype(np.float32)
        new = np.asarray(ref._held_chosen(router, ref._choose(router, moved)))
        changed += int(np.sum(np.any(new != now, -1) & (safe[:, 0] > 0)))
        tried += sel.shape[0]
    assert changed <= tried / 2000, (changed, tried)


def test_route_margin_finds_a_held_expert_on_the_boundary():
    """A held expert 1e-4 above the first expert left out is tied at the
    first tie above 1e-4; moved clear of every boundary it is not tied
    below 0.1."""
    import jax.numpy as jnp

    ref = _reference()
    c = PUBLISHED_ROUTER
    sel = np.full(256, 0.2, np.float32)
    # groups 0-3 kept (group scores 1.6 and 1.7 against 0.4); chosen:
    # held expert 1, experts 32, 64, 96, 33, 65, 97, and held expert 0
    # the 8th, just above expert 34
    for g in (1, 2, 3):
        sel[32 * g:32 * g + 2] = (0.9, 0.8)
    sel[34] = 0.6
    sel[0] = 0.6001
    sel[1] = 1.0
    chosen = set(np.asarray(ref._choose(c, jnp.asarray(sel))).tolist())
    assert chosen == {1, 32, 64, 96, 33, 65, 97, 0}
    m = float(ref.route_margin(c, jnp.asarray(sel)))
    assert 1e-4 < m < 2e-4
    sel[0] = 0.95
    assert float(ref.route_margin(c, jnp.asarray(sel))) > 0.1


def test_reference_judges_no_token_at_a_tied_position():
    """Positions whose route margin is at most ``route_tie`` give no gap,
    for the served tokens and the control alike; every other position
    does."""
    a = tiny_arch()
    cfg = tiny_cfg(a)
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, a.vocab_size, (2, 48)).astype(np.int32)
    targets = rng.integers(0, a.vocab_size, (2, 48)).astype(np.int32)
    ref = _reference()
    out = {}
    for tie in (0.0, 0.03):
        out[tie] = ref.Reference(dict(cfg, route_tie=tie)).run(
            SEED % 2 ** 31, tokens, targets, control=True)
    margin = out[0.0]["route_margin"]
    assert np.all(np.isfinite(out[0.0]["served"]))
    tied = margin <= 0.03
    assert 0 < tied.sum() < tied.size
    for key in ("served", "control"):
        np.testing.assert_array_equal(np.isnan(out[0.03][key]), tied)
        np.testing.assert_array_equal(out[0.03][key][~tied],
                                      out[0.0][key][~tied])


# --- a whole run of the harness at a test size ----------------------------
TINY = "dsv3-bench-tiny"
SEED = 2 ** 31 + 1203
# test-size limit.  The harness run below serves the toy in float32, where
# the program computes what the reference does and the widest gap reads
# 0.0 over four seeds, while the float8 control reads 0.69-1.27 (CPU).
# In bf16 a rounding at a router near-tie now and then chooses another
# expert for a token, and sound runs read up to 0.44 against the
# control's 0.63: the widest gap does not separate them there (PERF.md §7)
LIMIT = 0.1


def tiny_arch(dtype="bfloat16"):
    from repro.configs import get_arch
    from repro.configs.base import MLAConfig

    base = get_arch(NAME)
    return dataclasses.replace(
        base, name=TINY, dtype=dtype, num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=32, vocab_size=256,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(base.moe, num_experts=16, top_k=4, d_ff=32,
                                n_dense_layers=1, dense_d_ff=96, n_group=4,
                                topk_group=2, experts_held=4,
                                expert_offset=4))


def tiny_cfg(a) -> dict:
    """The cell's configuration file, at the tiny architecture's sizes."""
    cfg = config_file()
    cfg.update(name=TINY, arch=TINY, dtype=a.dtype,
               num_hidden_layers=a.num_layers,
               num_layers=a.num_layers, hidden_size=a.d_model,
               d_model=a.d_model, num_attention_heads=a.num_heads,
               num_heads=a.num_heads, num_key_value_heads=a.num_kv_heads,
               num_kv_heads=a.num_kv_heads, head_dim=a.head_dim,
               d_ff=a.moe.d_ff, moe_intermediate_size=a.moe.d_ff,
               intermediate_size=a.moe.dense_d_ff, vocab_size=a.vocab_size,
               q_lora_rank=a.mla.q_lora_rank,
               kv_lora_rank=a.mla.kv_lora_rank,
               qk_nope_head_dim=a.mla.qk_nope_head_dim,
               qk_rope_head_dim=a.mla.qk_rope_head_dim,
               v_head_dim=a.mla.v_head_dim, n_routed_experts=4,
               experts_routed=16, experts_held=4, expert_offset=4,
               num_experts_per_tok=4, n_group=4, topk_group=2)
    return cfg


def tiny_mix():
    mix = copy.deepcopy(Catalog().traffic("reason"))
    mix["engine"] = {"slots": 4, "page_size": 4, "prompt_buckets": [16, 32],
                     "max_new_tokens": 12}
    mix["prompt_len"] = {"dist": "lognormal", "median": 12, "sigma": 0.6,
                         "min": 4, "max": 32}
    mix["output_len"] = {"dist": "lognormal", "median": 8, "sigma": 0.4,
                         "min": 4, "max": 12}
    mix["correctness"] = {"sample_tokens": 10 ** 6, "max_requests": 4}
    return mix


def test_sound_run_is_correct_and_its_control_is_not(monkeypatch):
    """The whole harness at a toy size of the cell, served in float32 so
    that what decides ``correct`` is the program against the reference and
    not a routing near-tie: the served tokens are within the limit, the
    float8 control's own choices are not, and the run is not correct."""
    import jax

    from bench import run as R
    from repro.configs import registry

    a = tiny_arch("float32")
    monkeypatch.setitem(registry.SHARES, TINY, a)
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=3.0,
                              trace=0, control=1)
    out = R.run_cell(Catalog(), args, cfg=tiny_cfg(a), mix=tiny_mix(),
                     limits={"logit_gap": {"limit": LIMIT}},
                     peaks={"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11},
                     devices=jax.devices()[:1], chips=1)
    c = out["checks"]
    assert c["requests_checked"]["value"] == 4
    assert c["served_gap"]["value"] <= LIMIT, c
    assert c["logit_gap"]["value"] > LIMIT, c
    assert out["correct"] is False and out["failed"] >= 1
    assert set(out["metrics"]) == {"tokens_per_s", "tbt_p95_ms", "setup_s"}


def test_reference_makes_the_programs_weights(monkeypatch):
    import jax

    from repro.configs import registry
    from repro.models import build_model

    a = tiny_arch()
    monkeypatch.setitem(registry.SHARES, TINY, a)
    seed = SEED % 2 ** 31
    ref = Catalog().reference("mla_moe_decoder").Reference(
        tiny_cfg(a)).weights(seed)
    prog = jax.jit(lambda s: build_model(a).init(jax.random.PRNGKey(s)))(seed)
    pairs = [(ref["embed"]["embedding"], prog["embed"]["embedding"]),
             (ref["embed"]["lm_head"], prog["embed"]["lm_head"])]
    dense, moe = (s["blocks"][0] for s in prog["segments"])
    layers = [(dense, 0)] + [(moe, i) for i in range(a.num_layers - 1)]
    for w, (seg, i) in zip(ref["layers"], layers):
        for k, v in w["attn"].items():
            pairs.append((v, seg["attn"][k][i]))
        if "ffn" in w:
            for k, v in w["ffn"].items():
                pairs.append((v, seg["mlp"][k][i]))
            continue
        m = w["moe"]
        pairs.append((m["router"], seg["moe"]["router"][i]))
        pairs.append((m["bias"], seg["moe"]["score_bias"][i]))
        for k, v in m["shared"].items():
            pairs.append((v, seg["moe"]["shared"][k][i]))
        for j, e in enumerate(m["experts"]):
            for k, v in e.items():
                pairs.append((v, seg["moe"][k][i, j]))
    for x, y in pairs:
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
