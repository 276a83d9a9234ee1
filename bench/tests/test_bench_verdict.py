"""Whole runs of the harness on the CPU at a test size, past its look for a
chip: a sound run is correct and its control (the reference in float8) is
not; with the timed path broken underneath, ``correct`` comes out false,
once for each fault the cells can have.  Also: the reference makes the same
weights from the seed as the program."""

import argparse
import copy

import numpy as np
import pytest

ARCH = "stablelm-3b-smoke"
SEED = 2 ** 31 + 977
# test-size limit, set from CPU readings at this size over eight seeds:
# sound runs read 0.0-0.020, the control 0.21-0.86
LIMIT = 0.1


def smoke_cfg():
    from bench.run import MODEL_FIELDS
    from repro.configs import get_arch

    a = get_arch(ARCH)
    cfg = {"name": ARCH, "arch": ARCH, "reduced": [],
           "reference": "dense_decoder", "norm_eps": 1e-5}
    for k in MODEL_FIELDS:
        cfg[k] = a.head_dim_ if k == "head_dim" else getattr(a, k)
    return cfg


def smoke_mix(traffic):
    from bench.harness.catalog import Catalog

    mix = copy.deepcopy(Catalog().traffic(traffic))
    mix["engine"] = {"slots": 4, "page_size": 4, "prompt_buckets": [16, 32],
                     "max_new_tokens": 8}
    mix["prompt_len"] = {"dist": "lognormal", "median": 12, "sigma": 0.6,
                         "min": 4, "max": 32}
    mix["output_len"] = {"dist": "lognormal", "median": 5, "sigma": 0.4,
                         "min": 2, "max": 8}
    for m, at in zip(mix["moves"], (0.3, 1.5)):
        m["at_s"] = at
    mix["move_min_serve_s"] = 0.3
    mix["correctness"] = {"sample_tokens": 40, "max_requests": 8}
    return mix


def catalog():
    """The benchmark's catalog, with the move cell that its traffic mix and
    metric readers are ready for (``PERF.md``, Open questions)."""
    from bench.harness.catalog import Catalog

    cat = Catalog()
    b = cat.benchmark = copy.deepcopy(cat.benchmark)
    cell = "stablelm-3b.preempt"
    b["workloads"].append({"name": cell, "config": "stablelm-3b",
                           "traffic": "preempt", "chips": 1})
    b["end_to_end"].append({"name": "move_stall_s", "unit": "s",
                            "workloads": [cell]})
    b["per_layer"].append({"name": "evict_gbps", "unit": "GB/s",
                           "workloads": [cell]})
    return cat


def run(traffic="chat", seed=SEED, control=False, seconds=3.0, trace=0):
    import jax

    from bench import run as R

    args = argparse.Namespace(workload="stablelm-3b." + traffic, seed=seed,
                              seconds=seconds, trace=trace,
                              control=int(control))
    return R.run_cell(catalog(), args, cfg=smoke_cfg(),
                      mix=smoke_mix(traffic),
                      limits={"logit_gap": {"limit": LIMIT}},
                      peaks={"bf16_flops_per_s": 1e12,
                             "hbm_bytes_per_s": 1e11},
                      devices=jax.devices()[:1], chips=1)


def test_sound_run_is_correct_and_its_control_is_not():
    """With ``--control 1`` the control is judged in the program's place:
    what the sound program served stays within the limit, the control's
    own choices on the same sequences do not, and the run is not correct."""
    out = run(control=True)
    c = out["checks"]
    assert c["served_gap"]["value"] <= LIMIT, c
    assert c["tokens_checked"]["value"] >= 12
    assert c["logit_gap"]["value"] > LIMIT, c
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"


def test_traced_run_reports_its_window():
    """The traced run reads the profiler's trace of the window; on the CPU
    no device plane is recorded, so the device readers find nothing."""
    out = run(trace=1)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "host_us_per_token" in out["metrics"]
    assert "decode_roofline" not in out["metrics"]
    assert list(out)[-1] == "checks"


def _alter_tokens(monkeypatch):
    from repro.serve.engine import ContinuousBatchingEngine as E

    orig = E._commit_tokens
    calls = {"n": 0}

    def altered(self, st, tokens, now, **kw):
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            tokens = [(int(t) + 1) % self.cfg.vocab_size for t in tokens]
        return orig(self, st, tokens, now, **kw)

    monkeypatch.setattr(E, "_commit_tokens", altered)


def _step_returns_its_state(monkeypatch):
    from repro.serve.engine import ContinuousBatchingEngine as E

    orig = E._register

    def register(self, cl, name, fn, abstracts, donate_argnums=()):
        if name == "decode_step":
            def fn(params, toks, pos, bt, pool):
                return toks, pos, pool
        return orig(self, cl, name, fn, abstracts, donate_argnums)

    monkeypatch.setattr(E, "_register", register)


def _restore_loses_the_cache(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core.state import BufferTable

    orig = BufferTable.restore_device_state

    def lost(self, device):
        stats = orig(self, device)
        for b in self._buffers.values():    # the KV pages come back zeroed
            if b.paged and b.device_value is not None:
                b.device_value = jax.tree.map(jnp.zeros_like, b.device_value)
        return stats

    monkeypatch.setattr(BufferTable, "restore_device_state", lost)


@pytest.mark.parametrize("fault,traffic", [
    ("token_altered", "chat"),          # a token altered where produced
    ("state_unchanged", "chat"),        # decode returns its state as given
    ("stale_restore", "preempt"),       # a move loses the replica's state
])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, traffic):
    {"token_altered": _alter_tokens,
     "state_unchanged": _step_returns_its_state,
     "stale_restore": _restore_loses_the_cache}[fault](monkeypatch)
    out = run(traffic)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] >= 1


def test_moves_are_measured():
    out = run("preempt")
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["move_stall_s"]["value"] > 0
    assert set(out["metrics"]) == {"move_stall_s", "setup_s"}


def test_reference_makes_the_programs_weights():
    import jax

    from bench.harness.catalog import Catalog
    from repro.configs import get_arch
    from repro.models import build_model

    cfg = smoke_cfg()
    seed = SEED % 2 ** 31
    ref = Catalog().reference("dense_decoder").Reference(cfg).weights(seed)
    prog = jax.jit(lambda s: build_model(get_arch(ARCH)).init(
        jax.random.PRNGKey(s)))(seed)
    seg = prog["segments"][0]["blocks"][0]
    pairs = [(ref["embed"]["embedding"], prog["embed"]["embedding"]),
             (ref["embed"]["lm_head"], prog["embed"]["lm_head"])]
    for i, w in enumerate(ref["layers"]):
        for k in ("wq", "wk", "wv", "wo"):
            pairs.append((w[k], seg["attn"][k][i]))
        for k in ("w_up", "w_down", "w_gate"):
            pairs.append((w[k], seg["mlp"][k][i]))
        pairs.append((w["norm1"]["bias"], seg["norm1"]["bias"][i]))
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
