"""Decode in place on the paged KV pool: the engine's ``decode_step`` and
``decode_multi`` hand the model a paged view of the pool, each layer reads
its own rows and the engine writes back one row per lane.  Served tokens,
the pool they leave and the logits equal, bit for bit, the dense-cache path
the reserved layout, speculative verify and prefix chunks still take: each
lane's cache gathered whole (``gather_lane_cache``), the dense decode, the
written page scattered back."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, registry
from repro.models.attention import _INVALID_POS
from repro.scaling.metrics import MetricsRegistry
from repro.serve.engine import ContinuousBatchingEngine
from repro.serve.kvcache import (_is_pos_leaf, extract_written_page,
                                 gather_lane_cache, paged_lane_cache,
                                 scatter_pages)

PAGE, SLOTS, PROMPT, MAX_NEW, FUSE = 4, 4, 16, 8, 4


def _shapes():
    stablelm, yi = get_arch("stablelm-3b"), get_arch("yi-9b")
    return {
        # stablelm's shape: MHA, head_dim 80, rotary on a quarter of it
        "mha-hd80": dataclasses.replace(
            registry.reduced(stablelm), name="paged-mha-hd80", num_heads=2,
            num_kv_heads=2, head_dim=80),
        # yi's: grouped queries over 4 KV heads
        "gqa-4kv": dataclasses.replace(
            registry.reduced(yi), name="paged-gqa-4kv", num_heads=8,
            num_kv_heads=4),
        # MLA: one latent leaf per run of layers (a dense layer, then
        # three expert layers), each 40 wide a layer, padded to 128 lanes
        "mla": dataclasses.replace(get_arch("deepseek-v3-ep32-5l-smoke"),
                                   name="paged-mla"),
    }


class _Programs:
    """A stand-in for the guest's device API that keeps each program."""
    _monitor = None

    def __init__(self):
        self.fns = {}

    def clCreateProgramWithBinary(self, program, abstracts, donate_argnums=()):
        self.fns[program.program_id] = program.fn


def _block_table(max_blocks):
    """Lane 0 crosses pages; lane 1 walks past its mapped span; lane 2 is
    inactive; lane 3's context has an unmapped page inside it."""
    bt = np.full((SLOTS, max_blocks), -1, np.int32)
    bt[0, :6] = [9, 3, 14, 0, 6, 11]
    bt[1, :3] = [2, 8, 13]
    bt[3, [0, 1, 3, 4]] = [5, 1, 12, 7]
    pos = np.array([9, 10, 0, 14], np.int32)
    return bt, pos


def _fill(pool, lane_abs, bt, pos, seed):
    """Random k/v (or latent) rows, the lanes a pool leaf pads left zero;
    each mapped row below its lane's position holds that position, every
    other row INVALID."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def leaf(path, x, lane):
        if not _is_pos_leaf(path):
            width = lane.size // lane.shape[-2]    # one token, every layer
            vals = jax.random.normal(next(keys), x.shape).astype(x.dtype)
            return vals.at[..., width:].set(0) if x.ndim == 3 else vals
        out = np.full(x.shape, int(_INVALID_POS), np.int32)
        for lane in range(SLOTS):
            for lp, phys in enumerate(bt[lane]):
                for t in range(PAGE):
                    if phys >= 0 and lp * PAGE + t < pos[lane]:
                        out[phys, t] = lp * PAGE + t
        return jnp.asarray(out)

    return jax.tree_util.tree_map_with_path(leaf, pool, lane_abs)


def _dense_lanes(eng, lane_abs, params, toks, pos, bt, pool, step):
    """The dense-cache step: gather each lane's whole cache, decode it,
    scatter back the page holding the new row, under the row rule of the
    engine's paged step (inactive lanes, rows past the block row's end and
    unmapped pages dropped)."""
    ps, nb, axes = PAGE, eng.max_blocks, eng._token_axes
    cap = nb * ps

    def lane(tok, p, bt_row):
        cache = gather_lane_cache(pool, bt_row, lane_abs, axes, page_size=ps)
        logits, cache = eng.bundle.decode_fn(params, tok, p + step, cache)
        s = p % cap + step
        lp = jnp.minimum(s // ps, nb - 1)
        ok = (bt_row[0] >= 0) & (s < cap) & (bt_row[lp] >= 0)
        page = extract_written_page(cache, lp, axes, page_size=ps)
        return (jnp.argmax(logits, -1).astype(jnp.int32), page,
                jnp.where(ok, bt_row[lp], eng.pool_pages))

    nxt, pages, phys = jax.vmap(lane)(toks, pos, bt)
    return nxt, scatter_pages(pool, phys, pages)


def _logits(eng, lane_abs, params, toks, pos, bt, pool, paged):
    view = paged_lane_cache if paged else gather_lane_cache

    def lane(tok, p, bt_row):
        cache = view(pool, bt_row, lane_abs, eng._token_axes,
                     page_size=PAGE)
        return eng.bundle.decode_fn(params, tok, p, cache)[0]

    return jax.vmap(lane)(toks, pos, bt)


def _same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("program", ["decode_step", "decode_multi"])
@pytest.mark.parametrize("shape", ["mha-hd80", "gqa-4kv", "mla"])
def test_paged_decode_equals_dense_path(shape, program, monkeypatch):
    cfg = _shapes()[shape]
    monkeypatch.setitem(registry.SHARES, cfg.name, cfg)
    progs = _Programs()
    fuse = FUSE if program == "decode_multi" else 1
    eng = ContinuousBatchingEngine(
        cfg.name, progs, slots=SLOTS, prompt_len=PROMPT,
        prompt_buckets=(PROMPT // 2, PROMPT), max_new_tokens=MAX_NEW,
        registry=MetricsRegistry(), page_size=PAGE, fuse_steps=fuse)
    eng.setup(restore=True)
    lane_abs = jax.eval_shape(
        eng._prefill_fn(), jax.eval_shape(progs.fns["init_params"], 0),
        jax.ShapeDtypeStruct((1, PROMPT), jnp.int32))[1]
    params = jax.jit(progs.fns["init_params"])(3)
    bt, pos = _block_table(eng.max_blocks)
    pool = _fill(jax.jit(progs.fns["init_paged"])()[2], lane_abs, bt, pos,
                 seed=5)
    assert np.asarray(jax.tree.leaves(pool)[0]).std() > 0
    toks = jnp.asarray(np.arange(SLOTS, dtype=np.int32)[:, None] * 7 + 1)
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    on = np.asarray(bt[:, 0] >= 0)
    dense = jax.jit(lambda *a, step: _dense_lanes(eng, lane_abs, params, *a,
                                                  step=step),
                    static_argnames="step")
    paged_toks, paged_pool = toks, pool
    if program == "decode_step":
        step = jax.jit(progs.fns["decode_step"])
        p_pos, d_pos = pos, pos
        for _ in range(6):
            paged_toks, p_pos, paged_pool = step(params, paged_toks, p_pos,
                                                 bt, paged_pool)
            toks, pool = dense(toks, d_pos, bt, pool, step=0)
            d_pos = jnp.where(on, d_pos + 1, d_pos)
            assert np.array_equal(paged_toks, toks)
            assert np.array_equal(p_pos, d_pos)
            assert _same(paged_pool, pool)
        pos = p_pos
    else:
        lims = np.array([FUSE, FUSE, FUSE, FUSE - 1], np.int32)
        nodelta = np.full((eng._bt_delta_width, 3), -1, np.int32)
        multi = jax.jit(progs.fns["decode_multi"])
        for _ in range(2):
            outs, paged_toks, p_pos, _, paged_pool = multi(
                params, paged_toks, pos, bt, paged_pool, lims, nodelta)
            cur = toks
            for i in range(FUSE):
                nxt, pool = dense(cur, pos, bt, pool, step=i)
                cur = jnp.where((on & (i < lims))[:, None], nxt, cur)
                assert np.array_equal(outs[:, i], cur[:, 0])
            toks = cur
            assert np.array_equal(paged_toks, toks)
            assert _same(paged_pool, pool)
            pos = jnp.where(on, pos + lims, pos)
            assert np.array_equal(p_pos, pos)
    # the pool was read and written: tokens left the start, pages changed
    assert not np.array_equal(paged_toks, jnp.asarray(
        np.arange(SLOTS, dtype=np.int32)[:, None] * 7 + 1))
    got = _logits(eng, lane_abs, params, paged_toks, pos, bt, paged_pool,
                  paged=True)
    want = _logits(eng, lane_abs, params, toks, pos, bt, pool, paged=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))
