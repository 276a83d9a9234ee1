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
# lanes and prompts of up to 512 tokens
POOL = {"stablelm-3b": (32, 32, 80), "yi-9b-24l": (24, 4, 128),
        "hd64": (32, 8, 64)}
POOL_PAGES, PAGE, PROMPT, LANES = 576, 8, 512, 8


def _lane_cache(layers, heads, hd, cap):
    """A lane cache as the layer-scanned decoder lays it out."""
    kv = jax.ShapeDtypeStruct((layers, 1, cap, heads, hd), BF16)
    return {"k": kv, "v": kv,
            "kv_pos": jax.ShapeDtypeStruct((layers, cap), I32)}


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
    """The chip's default layout keeps each k/v pool leaf's page axis
    major, so a page is one contiguous block, and the programs that write
    pages (decode's page scatter, compaction, admission) update the
    donated pool in place, with no relayout of a whole leaf."""
    from jax.experimental.layout import Layout

    L, H, hd = POOL[model]
    lane = functools.partial(_lane_cache, L, H, hd)
    axes = token_axes_from_lengths(lane(PROMPT // 4), lane(PROMPT),
                                   PROMPT // 4, PROMPT)
    pool = pool_specs_from_lane_cache(lane(PROMPT), axes, POOL_PAGES, PAGE)
    dev = next(iter(one_chip.device_set))
    for name in ("k", "v"):
        layout = Layout.from_pjrt_layout(dev.client.get_default_layout(
            pool[name].dtype, pool[name].shape, dev))
        assert layout.major_to_minor[0] == 0, (name, pool[name].shape,
                                               layout)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_arg = jax.tree.map(lambda l: on_chip(l.shape, l.dtype), pool)
    pages = jax.tree.map(lambda l: on_chip((LANES,) + l.shape[1:], l.dtype),
                         pool)
    ids = on_chip((POOL_PAGES,), I32)
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
        assert not _whole_leaf_copies(hlo, pool["k"]), prog
