"""DeepSeek-V3's serving path at a small size: MLA with YaRN over a
latent cache, the published sigmoid group-limited router, and one chip's
share of the experts computed dropless.  The program is compared with the
plain float32 reference the benchmark uses (``bench/configs/
mla_moe_decoder.py``) on seeded weights, by logits."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, registry
from repro.configs.base import MLAConfig, MoEConfig
from repro.core import FunkyCL, Monitor, SliceAllocator
from repro.models import build_model
from repro.models.attention import mla_scale
from repro.models.layers import yarn_inv_freq
from repro.models.moe import held_moe_fwd, init_held_moe, route
from repro.scaling.metrics import MetricsRegistry
from repro.serve.engine import ContinuousBatchingEngine, ServeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = get_arch("deepseek-v3-ep32-5l")
NAME = "dsv3-tiny"
SEED = 7
PAGE = 4
BUCKETS = (16, 32)


def _reference_module():
    path = os.path.join(ROOT, "bench", "configs", "mla_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("mla_moe_decoder_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference_module()


def tiny(held=4, offset=4, num_experts=16, dtype="bfloat16", name=NAME):
    """Every mechanism of the published configuration at toy widths: one
    dense and two expert layers, 16 experts in 4 groups of which 2 are
    kept, top-4, 4 held starting at expert ``offset``."""
    return dataclasses.replace(
        BASE, name=name, num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=32, vocab_size=256, dtype=dtype,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(
            BASE.moe, num_experts=num_experts, top_k=4, d_ff=32,
            n_dense_layers=1, dense_d_ff=96, n_group=4, topk_group=2,
            experts_held=held, expert_offset=offset))


def bench_cfg(cfg) -> dict:
    """The reference's configuration (catalog key names) of ``cfg``."""
    m, a, y = cfg.moe, cfg.mla, cfg.yarn
    return {
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": m.n_dense_layers,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "q_lora_rank": a.q_lora_rank, "kv_lora_rank": a.kv_lora_rank,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim, "v_head_dim": a.v_head_dim,
        "intermediate_size": m.dense_d_ff, "moe_intermediate_size": m.d_ff,
        "n_shared_experts": m.num_shared_experts,
        "num_experts_per_tok": m.top_k, "n_group": m.n_group,
        "topk_group": m.topk_group,
        "routed_scaling_factor": m.routed_scaling_factor,
        "scoring_func": "sigmoid", "rms_norm_eps": 1e-6,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position_embeddings,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim},
        "vocab_size": cfg.vocab_size, "experts_routed": m.num_experts,
        "experts_held": m.experts_held, "expert_offset": m.expert_offset,
        "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype,
    }


@pytest.fixture
def tiny_arch(monkeypatch):
    cfg = tiny()
    monkeypatch.setitem(registry.SHARES, NAME, cfg)
    return cfg


def _engine(slots, max_new, buckets=BUCKETS):
    reg = MetricsRegistry()
    mon = Monitor("mla-moe-test", SliceAllocator("n0", 1), telemetry=reg)
    eng = ContinuousBatchingEngine(
        NAME, FunkyCL(mon), slots=slots, prompt_len=max(buckets),
        prompt_buckets=buckets, max_new_tokens=max_new, registry=reg,
        page_size=PAGE, seed=SEED)
    eng.setup()
    return mon, eng, reg


def _serve(slots, prompts, max_new):
    mon, eng, reg = _engine(slots, max_new)
    for i, (p, n) in enumerate(prompts):
        eng.submit(ServeRequest(rid=f"r{i}", prompt=p, max_new_tokens=n))
    eng.run_until_drained()
    mon.vfpga_exit()
    return {rid: list(c.tokens) for rid, c in eng.completed.items()}, eng, reg


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    lens = [12, 30, 7, 16, 25, 9, 32, 20][:n]
    return [(rng.integers(0, 256, k).astype(np.int32), 6) for k in lens]


def _sequences(prompts, served):
    """Padded prompt + served tokens per request, as the verdict builds
    them: tokens and the targets each position should predict."""
    L = max(BUCKETS) + 6
    tokens = np.zeros((len(prompts), L), np.int32)
    targets = np.full((len(prompts), L), -1, np.int32)
    for b, (p, _) in enumerate(prompts):
        bucket = min(x for x in BUCKETS if x >= len(p))
        out = served[f"r{b}"]
        seq = np.concatenate([p, np.zeros(bucket - len(p), np.int32),
                              np.asarray(out[:-1], np.int32)])
        tokens[b, :len(seq)] = seq
        targets[b, bucket - 1:bucket - 1 + len(out)] = out
    return tokens, targets


# Widest gap allowed between the reference's best logit and the logit of
# what the engine served.  The bf16 program moves a logit by ~2% of the
# logits' range at these widths, and where a token's router scores nearly
# tie, that rounding can choose another expert for it; then a served token
# may trail the reference's best by more.  These four requests read 0.0,
# their float8 control 1.21 (CPU); whole harness runs at this size in bf16
# read up to 0.44 on other requests.  The test checks that the control
# exceeds the limit, so it tells bf16 from a step below on these requests.
GAP = 0.4


def test_engine_serves_what_the_reference_predicts(tiny_arch):
    prompts = _prompts(4)
    served, eng, reg = _serve(4, prompts, 6)
    assert sorted(served) == [f"r{i}" for i in range(4)]
    tokens, targets = _sequences(prompts, served)
    out = REF.Reference(bench_cfg(tiny_arch)).run(SEED, tokens, targets,
                                                  control=True)
    assert float(np.nanmax(out["served"])) <= GAP, out["served"]
    assert float(np.nanmax(out["control"])) > GAP
    # the setup gauges of the share and of the latent cache
    snap = reg.snapshot()["gauges"]
    held = [v for k, v in snap.items() if k.startswith("moe_experts_held")]
    routed = [v for k, v in snap.items()
              if k.startswith("moe_experts_routed")]
    lat = [v for k, v in snap.items()
           if k.startswith("kv_latent_bytes_per_token")]
    assert held == [4] and routed == [16]
    assert lat == [3 * (32 + 8) * 2] == [eng.kv_latent_bytes_per_token]


def test_prefill_then_decode_logits_match_reference(tiny_arch):
    """The model's prefill and its decode through the cache give the
    reference's logits at every position.  The program computes in float32
    here, on the bf16 weights it serves, so that only the order of sums
    differs from the reference: 2.5e-5 of the logits' range, measured.
    Tolerance 1e-3 of the range; in bf16 the program reads 2-28% (a
    routing near-tie flips an expert), the float8 control 29%."""
    cfg = dataclasses.replace(tiny_arch, dtype="float32")
    b = build_model(cfg, cache_margin=16)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          build_model(tiny_arch).init(
                              jax.random.PRNGKey(SEED)))
    seq = np.random.default_rng(3).integers(0, 256, (4, 44)).astype(np.int32)
    ref = REF.Reference(bench_cfg(tiny_arch)).logits(SEED, seq)
    got = []
    logits, caches = jax.jit(b.prefill_fn)(params,
                                           {"tokens": jnp.asarray(seq[:, :32])})
    got.append(np.asarray(logits))
    dec = jax.jit(b.decode_fn)
    for i in range(32, 43):
        logits, caches = dec(params, jnp.asarray(seq[:, i]), jnp.int32(i),
                             caches)
        got.append(np.asarray(logits))
    got = np.stack(got, 1)                               # positions 31..42
    want = ref[:, 31:43]
    tol = 1e-3 * float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) <= tol


def test_lane_tokens_do_not_depend_on_neighbours(tiny_arch):
    """Dropless: a lane decodes the same tokens alone as beside 7 others."""
    prompts = _prompts(8)
    together, _, _ = _serve(8, prompts, 6)
    alone, _, _ = _serve(8, prompts[:1], 6)
    assert alone["r0"] == together["r0"]


def test_prefix_does_not_depend_on_the_bucket(tiny_arch):
    """Dropless: a prompt's latent cache at its own positions is the same
    whether it is padded to the small bucket or the large one (a capacity
    that scales with the padded length would drop other pairs)."""
    cfg = tiny_arch
    b = build_model(cfg, cache_margin=0)
    params = b.init(jax.random.PRNGKey(SEED))
    p = np.random.default_rng(2).integers(0, 256, 12).astype(np.int32)
    lats = []
    for bucket in BUCKETS:
        toks = jnp.asarray(np.pad(p, (0, bucket - len(p)))[None])
        _, caches = jax.jit(b.prefill_fn)(params, {"tokens": toks})
        lats.append([np.asarray(seg[0]["lat"][:, :, :12], np.float32)
                     for seg in caches])
    for a, c in zip(*lats):
        np.testing.assert_array_equal(a, c)


def _np_route(x, router, bias, m):
    """The published rule (DeepSeek-V3 ``MoEGate``, noaux_tc) in numpy."""
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    choice = scores + bias
    T, E = choice.shape
    g = choice.reshape(T, m.n_group, E // m.n_group)
    gs = np.sort(g, axis=-1)[..., -2:].sum(-1)
    top_g = np.argsort(-gs, axis=-1, kind="stable")[:, :m.topk_group]
    mask = np.zeros((T, m.n_group), bool)
    np.put_along_axis(mask, top_g, True, axis=-1)
    choice = np.where(np.repeat(mask, E // m.n_group, axis=-1), choice,
                      -np.inf)
    idx = np.argsort(-choice, axis=-1, kind="stable")[:, :m.top_k]
    w = np.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * m.routed_scaling_factor
    return idx, w


def test_router_matches_published_rule():
    cfg = tiny(dtype="float32")
    p = init_held_moe(cfg, jax.random.PRNGKey(3))
    assert float(jnp.max(jnp.abs(p["score_bias"]))) > 0
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (64, 64)))
    e, w = route(cfg, p, jnp.asarray(x))
    ne, nw = _np_route(x.astype(np.float64), np.asarray(p["router"],
                                                        np.float64),
                       np.asarray(p["score_bias"], np.float64), cfg.moe)
    np.testing.assert_array_equal(np.sort(np.asarray(e), -1),
                                  np.sort(ne, -1))
    order = np.argsort(np.asarray(e), -1)
    norder = np.argsort(ne, -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1),
                               np.take_along_axis(nw, norder, -1),
                               rtol=1e-5)
    # the bias moves the choice, not the weights: without it some choices
    # change
    e0, _ = route(cfg, dict(p, score_bias=jnp.zeros_like(p["score_bias"])),
                  jnp.asarray(x))
    assert (np.sort(np.asarray(e0), -1) != np.sort(np.asarray(e), -1)).any()


@pytest.mark.parametrize("fields", [
    dict(n_group=8, topk_group=4), dict(topk_group=4),
    dict(routed_scaling_factor=2.5)])
def test_capacity_path_refuses_the_held_router(fields):
    """The capacity path routes by softmax top-k: a config that gives it
    noaux_tc's groups or scaling is refused, not served another way."""
    base = dict(num_experts=16, top_k=4, d_ff=32)
    with pytest.raises(ValueError):
        MoEConfig(**base, **fields)
    MoEConfig(**base)
    MoEConfig(**base, experts_held=4, **fields)


def test_expert_shares_sum_to_the_uncut_layer():
    """Every chip's share of the routed experts, with the shared expert
    counted once, adds up to the whole layer computed plainly."""
    E = 16
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, 64))
    whole = tiny(held=E, offset=0, num_experts=E, dtype="float32")
    pw = init_held_moe(whole, key)
    shared = held_moe_fwd(dataclasses.replace(
        whole, moe=dataclasses.replace(whole.moe, num_experts=E,
                                       experts_held=E, expert_offset=E)),
        pw, x)                      # no expert held: the shared expert alone
    total = shared
    for off in range(0, E, 4):
        share = tiny(held=4, offset=off, num_experts=E, dtype="float32")
        ps = init_held_moe(share, key)
        np.testing.assert_array_equal(np.asarray(ps["w_up"]),
                                      np.asarray(pw["w_up"][off:off + 4]))
        total = total + held_moe_fwd(share, ps, x) - shared
    # the uncut layer, plainly: every token through its chosen experts
    x2 = np.asarray(x, np.float64).reshape(-1, 64)
    idx, w = _np_route(x2, np.asarray(pw["router"], np.float64),
                       np.asarray(pw["score_bias"], np.float64), whole.moe)

    def ffn(f, h):
        g = h @ np.asarray(f["w_gate"], np.float64)
        return ((g / (1 + np.exp(-g))) * (h @ np.asarray(f["w_up"],
                                                         np.float64))
                ) @ np.asarray(f["w_down"], np.float64)

    want = ffn(pw["shared"], x2)
    for t in range(x2.shape[0]):
        for e, wt in zip(idx[t], w[t]):
            want[t] += wt * ffn({k: pw[k][e] for k in
                                 ("w_gate", "w_up", "w_down")}, x2[t])
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64), want,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_masked_product_matches_the_grouped_one(offset):
    """Decode's masked product over the held experts computes the pairs of
    the grouped product with the same weights: the two agree to float32
    rounding, alone and under the serving fold of vmapped lanes."""
    cfg = tiny(held=4, offset=offset, dtype="float32")
    p = init_held_moe(cfg, jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (6, 1, 1, 64))
    outs = {}
    for masked in (False, True):
        outs[masked, False] = held_moe_fwd(cfg, p, x[:, 0], masked=masked)
        outs[masked, True] = jax.vmap(lambda xi, m=masked: held_moe_fwd(
            cfg, p, xi, fold=True, masked=m))(x)[:, 0]
    assert float(jnp.max(jnp.abs(outs[False, False]))) > 0
    for key, out in outs.items():
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(outs[False, False]),
                                   rtol=1e-5, atol=1e-6, err_msg=str(key))


def test_yarn_frequencies_and_scale_match_published_formula():
    """DeepSeek-V3's rope_scaling at its 64 rope dims: the correction range
    is [10, 23], the slow dims interpolate by 40, and the softmax scale is
    192 ** -0.5 times (0.1 ln 40 + 1) ** 2 = 1.874."""
    y = BASE.yarn
    d, base = 64, 10000.0

    def corr(rot):
        return d * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    extra = base ** (-np.arange(0, d, 2) / d)
    m = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - m) + extra * m
    np.testing.assert_allclose(yarn_inv_freq(d, base, y), want, rtol=1e-6)
    np.testing.assert_allclose(REF.rope_freqs(bench_cfg(BASE)), want,
                               rtol=1e-6)
    mscale2 = (0.1 * math.log(40) + 1) ** 2
    assert round(mscale2, 3) == 1.874
    assert mla_scale(BASE) == pytest.approx(192 ** -0.5 * mscale2, rel=1e-12)
    assert REF.softmax_scale(bench_cfg(BASE)) == pytest.approx(
        192 ** -0.5 * mscale2, rel=1e-12)
