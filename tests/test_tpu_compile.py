"""Every Pallas kernel compiles for a TPU v5e at the widths of the model that
uses it, and so does a dense prefill that reserves decode headroom; the
paged KV pool's helpers compile without relayouting the pool.  The chip is
described, not attached: the TPU compiler refuses block shapes, primitives
and scatters that the CPU accepts, so these compiles guard the code
without a chip."""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.decode_attention.kernel import decode_attention_fwd
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.rglru_scan.kernel import rglru_scan_fwd
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.models import build_model
from repro.serve.kvcache import (compact_pool, pool_specs_from_lane_cache,
                                 scatter_pages, scatter_prefill,
                                 token_axes_from_lengths)

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (Hq, Hkv, head_dim) of the dense decoders the engine serves
ATTN = {"stablelm-3b": (32, 32, 80), "yi-9b": (32, 4, 128)}
CTX = 2048


def _decode(Hq, Hkv, hd):
    return (functools.partial(decode_attention_fwd, bk=512),
            [((8, 1, Hq, hd), BF16), ((8, CTX, Hkv, hd), BF16),
             ((8, CTX, Hkv, hd), BF16), ((), I32), ((CTX,), I32)])


def _flash(Hq, Hkv, hd):
    return (flash_attention_fwd,
            [((1, CTX, Hq, hd), BF16), ((1, CTX, Hkv, hd), BF16),
             ((1, CTX, Hkv, hd), BF16)])


CASES = {
    **{f"decode_attention-{m}": _decode(*w) for m, w in ATTN.items()},
    **{f"flash_attention-{m}": _flash(*w) for m, w in ATTN.items()},
    # recurrentgemma-9b: lru_width 4096
    "rglru_scan-recurrentgemma-9b": (
        rglru_scan_fwd, [((1, CTX, 4096), F32), ((1, CTX, 4096), F32)]),
    # mamba2-1.3b: 64 SSD heads of 64, state 128, chunk 256
    "ssd_scan-mamba2-1.3b": (
        functools.partial(ssd_scan_fwd, chunk=256),
        [((1, CTX, 64, 64), BF16), ((1, CTX, 64), F32), ((64,), F32),
         ((1, CTX, 128), BF16), ((1, CTX, 128), BF16)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # Mosaic, not XLA


def test_dense_prefill_with_headroom_compiles_for_v5e(one_chip):
    """A prefill whose cache has decode headroom (``cache_margin``) builds
    its ring buffer inside the layer scan; the dense reference decode of
    the serving engine compiles it."""
    bundle = build_model(get_arch("stablelm-3b-smoke"), cache_margin=8)
    on_chip = functools.partial(jax.tree.map, lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip))
    params = on_chip(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 16), I32, sharding=one_chip)
    jax.jit(bundle.prefill_fn).lower(params, {"tokens": tokens}).compile()


# (layers, Hkv, head_dim) of paged k/v pool leaves: the chat cells' models
# and a head_dim of 64; the cells' pool of 576 pages of 8 tokens serves 8
# lanes and prompts of up to 512 tokens.  An MLA model's lane cache is the
# program's own: one dense and four expert layers, each a latent of 576,
# in the reasoning cell's pool of 64 lanes of 320 pages.
POOL = {"stablelm-3b": (32, 32, 80), "yi-9b-24l": (24, 4, 128),
        "hd64": (32, 8, 64), "deepseek-v3-ep32-5l": "mla"}
POOL_PAGES, PAGE, PROMPT, LANES = 576, 8, 512, 8
PAGES_OF = {"deepseek-v3-ep32-5l": 64 * 320}


def _lane_cache(layers, heads, hd, cap):
    """A lane cache as the layer-scanned decoder lays it out."""
    kv = jax.ShapeDtypeStruct((layers, 1, cap, heads, hd), BF16)
    return {"k": kv, "v": kv,
            "kv_pos": jax.ShapeDtypeStruct((layers, cap), I32)}


def _lane_cache_of(model):
    if POOL[model] == "mla":
        from repro.models.transformer import lm_cache_specs

        return functools.partial(lm_cache_specs, get_arch(model), 1)
    return functools.partial(_lane_cache, *POOL[model])


def _stored_leaves(pool):
    """Every pool leaf but the positions: the k/v or latent leaves."""
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        pool)[0] if path[-1].key != "kv_pos"]


def _whole_leaf_copies(hlo: str, leaf) -> list:
    """Top-level copies (or copy fusions) in a compiled program's entry
    computation that move as many elements as a whole pool leaf: a
    relayout of the pool."""
    entry = hlo[hlo.index("\nENTRY"):]
    out = []
    for m in re.finditer(r"%([\w.-]+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                         entry):
        name, dims, op = m.group(1), m.group(3), m.group(4)
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n == math.prod(leaf.shape) and (
                op == "copy" or (op == "fusion" and name.startswith("copy"))):
            out.append(name)
    return out


@pytest.mark.parametrize("model", sorted(POOL))
def test_paged_pool_is_page_major_for_v5e(model, one_chip):
    """The chip's default layout keeps each k/v or latent pool leaf's page
    axis major, so a page is one contiguous block, and the programs that
    write pages (decode's page scatter, compaction, admission) update the
    donated pool in place, with no relayout of a whole leaf."""
    from jax.experimental.layout import Layout

    lane = _lane_cache_of(model)
    axes = token_axes_from_lengths(lane(PROMPT // 4), lane(PROMPT),
                                   PROMPT // 4, PROMPT)
    pages_n = PAGES_OF.get(model, POOL_PAGES)
    pool = pool_specs_from_lane_cache(lane(PROMPT), axes, pages_n, PAGE)
    dev = next(iter(one_chip.device_set))
    stored = _stored_leaves(pool)
    assert stored
    for leaf in stored:
        layout = Layout.from_pjrt_layout(dev.client.get_default_layout(
            leaf.dtype, leaf.shape, dev))
        assert layout.major_to_minor[0] == 0, (leaf.shape, layout)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_arg = jax.tree.map(lambda l: on_chip(l.shape, l.dtype), pool)
    pages = jax.tree.map(lambda l: on_chip((LANES,) + l.shape[1:], l.dtype),
                         pool)
    ids = on_chip((pages_n,), I32)
    programs = {
        "scatter_pages": (scatter_pages, (pool_arg, on_chip((LANES,), I32),
                                          pages)),
        "compact_pool": (compact_pool, (pool_arg, ids, ids)),
        "scatter_prefill": (
            functools.partial(scatter_prefill, token_axes=axes,
                              page_size=PAGE, prompt_len=PROMPT),
            (pool_arg, on_chip((PROMPT // PAGE,), I32),
             jax.tree.map(lambda l: on_chip(l.shape, l.dtype),
                          lane(PROMPT)))),
    }
    for prog, (fn, args) in programs.items():
        hlo = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile().as_text()
        for leaf in stored:
            assert not _whole_leaf_copies(hlo, leaf), (prog, leaf.shape)


class _Programs:
    """A stand-in for the guest's device API that keeps each program."""
    _monitor = None

    def __init__(self):
        self.progs = {}

    def clCreateProgramWithBinary(self, program, abstracts, donate_argnums=()):
        self.progs[program.program_id] = (program.fn, abstracts,
                                          donate_argnums)


# the cells' engines: (slots, prompt buckets, max_new_tokens), page 8
DECODE_CELLS = {"stablelm-3b": (8, (128, 512), 64),
                "yi-9b-24l": (8, (128, 512), 64),
                "deepseek-v3-ep32-5l": (64, (256, 1024), 1536)}
# temporaries of the compiled decode_step that gathered each lane's whole
# cache (CPU compile for v5e, the parent of the paged decode in place)
DENSE_DECODE_TEMP = {"stablelm-3b": 4_097_519_104, "yi-9b-24l": 456_476_160,
                     "deepseek-v3-ep32-5l": 2_491_664_384}


@pytest.mark.parametrize("model", sorted(DECODE_CELLS))
def test_paged_decode_moves_no_lane_cache_for_v5e(model, one_chip,
                                                  monkeypatch):
    """The cells' ``decode_step`` reads the pool in place: its entry
    computation moves a dense lane-cache stack (every lane's cache of one
    leaf at ``max_ctx``) at most once per k/v leaf, the chip's reorder of
    the gathered rows to layer-major, and never through a relayout
    (``reshape``) or a cache-writing fusion, where the step that gathered
    each lane's dense cache made two to four such moves per leaf besides
    the layer scan's copies and cache rewrites.  Its temporaries stay
    below that step's (stablelm-3b 4,097,519,104 B, yi-9b-24l 456,476,160
    B, deepseek-v3-ep32-5l 2,491,664,384 B)."""
    import dataclasses

    from repro.configs import registry
    from repro.scaling.metrics import MetricsRegistry
    from repro.serve.engine import ContinuousBatchingEngine

    if model == "yi-9b-24l":       # one of Yi-9B's two pipeline stages
        monkeypatch.setitem(registry.SHARES, model, dataclasses.replace(
            get_arch("yi-9b"), name=model, num_layers=24))
    slots, buckets, max_new = DECODE_CELLS[model]
    cl = _Programs()
    eng = ContinuousBatchingEngine(
        model, cl, slots=slots, prompt_len=max(buckets),
        prompt_buckets=buckets, max_new_tokens=max_new, page_size=PAGE,
        registry=MetricsRegistry())
    eng.setup(restore=True)
    fn, abstracts, donate = cl.progs["decode_step"]
    args = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one_chip), abstracts)
    compiled = jax.jit(fn, donate_argnums=donate,
                       out_shardings=one_chip).lower(*args).compile()
    lane = jax.eval_shape(eng._prefill_fn(), abstracts[0], jax.ShapeDtypeStruct(
        (1, eng.prompt_len), I32))[1]
    leaves = _stored_leaves(lane)
    stacks = [slots * leaf.size // leaf.shape[axis] * eng.max_ctx
              for leaf, axis in zip(leaves, _stored_leaves(eng._token_axes))]
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    moves = []
    for m in re.finditer(r"%([\w.-]+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                         entry):
        name, dims, op = m.group(1), m.group(3), m.group(4)
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n >= min(stacks) and (
                op in ("copy", "transpose", "reshape") or op == "fusion"
                and re.search(r"copy|transpose|reshape|dynamic-update-slice",
                              name)):
            moves.append((op, name))
    kv_leaves = sum(leaf.ndim > 4 for leaf in leaves)      # not the latent
    assert len(moves) <= kv_leaves, moves
    assert all(op == "copy" for op, _ in moves), moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < DENSE_DECODE_TEMP[model], temp
