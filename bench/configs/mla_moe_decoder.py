"""Plain reference of a latent-attention, mixture-of-experts decoder
(DeepSeek-V3), in float32.

The reference of every configuration whose ``bench/configs/<name>.json``
names ``"reference": "mla_moe_decoder"``.  It imports nothing of the
program under test.  The weights are made here from the run's seed, in the
configuration's ``dtype`` as served, by the same random draws the configuration's initializer
specifies: ``jax.random.PRNGKey(seed)`` split into the embedding key and
one key per run of layers (the leading dense layers, then the expert
layers), each split into a key per layer and that into the attention's,
the dense MLP's and the expert layer's keys; a matrix is a standard normal
scaled by its fan-in, norms start at one.  Expert ``e`` of an expert layer
is drawn from key ``e`` of a split over all ``experts_routed`` experts, so
a chip's share holds the experts the uncut layer would.  The forward pass
is the textbook one, computed in float32 at ``highest`` matmul precision,
one layer at a time:

    x = E[tokens]
    per layer:  h = n1(x)
                c_q = n_q(W_qa h);  q = W_qb c_q = [q_nope ‖ q_pe]
                [c_kv ‖ k_pe] = W_kva h;  c_kv = n_kv(c_kv)
                k_nope = W_kb c_kv;  v = W_vb c_kv
                q_pe, k_pe rotated (YaRN frequencies, half-split)
                x += W_o softmax(scale (q_nope.k_nope + q_pe.k_pe), causal) v
                h = n2(x)
                dense layer:  x += W_down (silu(W_gate h) * W_up h)
                expert layer: x += sum over held experts e of g_e(h) FFN_e(h)
                              + shared FFN(h)
    logits = W_head n(x)

with the softmax scale ``(qk_nope + qk_rope) ** -0.5`` times YaRN's
``(0.1 ln(factor) mscale_all_dim + 1) ** 2``.  The router: scores
``sigmoid(h W_r)``; experts ranked by score plus the
selection bias among the best ``topk_group`` of ``n_group`` groups, a
group scored by the sum of its two best; ``g_e`` is the chosen expert's
unbiased score over the sum of the chosen scores, times
``routed_scaling_factor``, and 0 for an expert not chosen.  Only the
experts held here contribute (``expert_offset`` on), as on the chip.
Attention and the logits are computed in blocks of query rows, so rows of
a few thousand positions fit.

Which held experts a token runs is a discrete choice, and at a near-tie
of the selection scores a bfloat16 rounding in the program may choose
otherwise than the float32 reference: the token then runs other experts,
and its logits differ by far more than rounding.  So the reference gives
each position its route margin, the least of ``TIE_GRID`` by which the
selection scores of some expert layer can be moved so that the held
experts chosen there change (``_tied``), and judges no token at a
position whose margin is at most the configuration's ``route_tie``: there
the reference cannot say which experts a correct engine runs.  Every
other position is judged, the served tokens and the control's alike.

``control=True`` also runs the control: the same forward with both operands
of every product with a weight rounded to float8 e4m3 (per-tensor scale),
the precision a step below bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
ROWS = 256          # query rows per block of attention and of logits
# the ties tried for a position's route margin: 2**-14 ... 2**-1
TIE_GRID = 2.0 ** (np.arange(-28, -1) / 2)


SHAPE_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "n_shared_experts", "num_experts_per_tok", "n_group",
              "topk_group", "routed_scaling_factor", "scoring_func",
              "rms_norm_eps", "rope_theta", "rope_scaling", "vocab_size",
              "experts_routed", "experts_held", "expert_offset",
              "tie_word_embeddings", "dtype")


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape) * scale).astype(dt)


def _ffn(key, d, ff, dt):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_up": _normal(k1, (d, ff), d ** -0.5, dt),
            "w_down": _normal(k2, (ff, d), ff ** -0.5, dt),
            "w_gate": _normal(k3, (d, ff), d ** -0.5, dt)}


def _attn_weights(c: dict, key) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qr, r, rope = c["q_lora_rank"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    dt = c["dtype"]
    ks = jax.random.split(key, 6)
    return {"wq_a": _normal(ks[0], (d, qr), d ** -0.5, dt),
            "q_norm": jnp.ones((qr,), dt),
            "wq_b": _normal(ks[1], (qr, h, nope + rope), qr ** -0.5, dt),
            "wkv_a": _normal(ks[2], (d, r + rope), d ** -0.5, dt),
            "kv_norm": jnp.ones((r,), dt),
            "wk_b": _normal(ks[3], (r, h, nope), r ** -0.5, dt),
            "wv_b": _normal(ks[4], (r, h, vd), r ** -0.5, dt),
            "wo": _normal(ks[5], (h, vd, d), (h * vd) ** -0.5, dt)}


def _expert_weights(c: dict, key) -> dict:
    d, f, E = c["hidden_size"], c["moe_intermediate_size"], c["experts_routed"]
    dt = c["dtype"]
    ks = jax.random.split(key, 5)
    held = jax.random.split(ks[1], E)[
        c["expert_offset"]:c["expert_offset"] + c["experts_held"]]
    experts = []
    for k in held:
        kg, ku, kd = jax.random.split(k, 3)
        experts.append({"w_gate": _normal(kg, (d, f), d ** -0.5, dt),
                        "w_up": _normal(ku, (d, f), d ** -0.5, dt),
                        "w_down": _normal(kd, (f, d), f ** -0.5, dt)})
    return {"router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
            "bias": jax.random.normal(ks[2], (E,)) * 0.1,
            "experts": experts,
            "shared": _ffn(ks[4], d, f * c["n_shared_experts"], dt)}


def _layer_weights(c: dict, dense: bool, key) -> dict:
    (bk,) = jax.random.split(key, 1)
    k1, k2, k3 = jax.random.split(bk, 3)
    d, dt = c["hidden_size"], c["dtype"]
    w = {"attn": _attn_weights(c, k1), "norm1": jnp.ones((d,), dt),
         "norm2": jnp.ones((d,), dt)}
    if dense:
        w["ffn"] = _ffn(k2, d, c["intermediate_size"], dt)
    else:
        w["moe"] = _expert_weights(c, k3)
    return w


def _embed_weights(c: dict, key) -> dict:
    k1, k2 = jax.random.split(key)
    d, V = c["hidden_size"], c["vocab_size"]
    return {"embedding": _normal(k1, (V, d), 0.02, c["dtype"]),
            "lm_head": _normal(k2, (d, V), d ** -0.5, c["dtype"])}


def _runs(c: dict) -> list:
    """(dense, count) of each run of layers."""
    nd, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    return [(True, nd)] * bool(nd) + [(False, L - nd)]


def _keys(c: dict, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(_runs(c)) + 2)
    return keys[0], [jax.random.split(keys[1 + i], n)
                     for i, (_, n) in enumerate(_runs(c))]


# ---------------------------------------------------------------------------
# forward, float32
# ---------------------------------------------------------------------------
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, q):
    w = w.astype(jnp.float32)
    if q:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(c, x, scale):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + c["rms_norm_eps"]) * scale.astype(
        jnp.float32)


def rope_freqs(c: dict) -> np.ndarray:
    """Rotary frequencies of the rope dims, with YaRN's interpolation
    where ``rope_scaling`` says so."""
    d, base = c["qk_rope_head_dim"], c["rope_theta"]
    inv = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = c.get("rope_scaling") or {}
    if rs.get("type") != "yarn" or rs["factor"] <= 1:
        return inv.astype(np.float32)

    def dim_of(rotations):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inv / rs["factor"] * ramp + inv * (1 - ramp)).astype(np.float32)


def softmax_scale(c: dict) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c.get("rope_scaling") or {}
    if rs.get("type") == "yarn" and rs["factor"] > 1 and rs.get(
            "mscale_all_dim"):
        scale *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"])
                  + 1.0) ** 2
    return scale


def _rope(freqs, x, pos):
    """Half-split rotation of the last axis; x (B, S, ..., d)."""
    ang = pos[:, None].astype(jnp.float32) * freqs              # (S, d/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, q, w, x):
    B, S, _ = x.shape
    nope, r = c["qk_nope_head_dim"], c["kv_lora_rank"]
    freqs = jnp.asarray(rope_freqs(c))
    pos = jnp.arange(S)
    h = _rms(c, x, w["norm1"])
    w = w["attn"]
    cq = _rms(c, _mm("bsd,dr->bsr", h, w["wq_a"], q), w["q_norm"])
    qf = _mm("bsr,rhk->bshk", cq, w["wq_b"], q)
    q_nope, q_pe = qf[..., :nope], _rope(freqs, qf[..., nope:], pos)
    kv = _mm("bsd,dr->bsr", h, w["wkv_a"], q)
    ckv = _rms(c, kv[..., :r], w["kv_norm"])
    k_pe = _rope(freqs, kv[..., r:], pos)
    k_nope = _mm("bsr,rhk->bshk", ckv, w["wk_b"], q)
    v = _mm("bsr,rhk->bshk", ckv, w["wv_b"], q)
    scale = softmax_scale(c)
    rows = math.gcd(S, ROWS)
    nb = S // rows

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q_nope, i * rows, rows, 1)
        ps = jax.lax.dynamic_slice_in_dim(q_pe, i * rows, rows, 1)
        s = (jnp.einsum("bqhk,bshk->bhqs", qs, k_nope, precision=HI)
             + jnp.einsum("bqhk,bsk->bhqs", ps, k_pe, precision=HI))
        qpos = i * rows + jnp.arange(rows)
        s = jnp.where(pos[None, :] <= qpos[:, None], s * scale, -jnp.inf)
        return jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(nb))            # (nb, B, rows, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, *o.shape[3:])
    return x + _mm("bshk,hkd->bsd", o, w["wo"], q)


def _swiglu(h, f, q):
    up = _mm("bsd,df->bsf", h, f["w_up"], q)
    gate = _mm("bsd,df->bsf", h, f["w_gate"], q)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, f["w_down"], q)


def _eligible(c: dict, sel: jax.Array, swap: bool = False) -> jax.Array:
    """(…, E) selection scores, -inf for the experts of the groups not
    kept.  ``swap`` keeps the first group left out in place of the last
    one kept."""
    E, G, tg = c["experts_routed"], c["n_group"], c["topk_group"]
    if tg >= G:
        return sel
    grouped = sel.reshape(sel.shape[:-1] + (G, E // G))
    best2 = jax.lax.top_k(grouped, 2)[0].sum(-1)                # (…, G)
    _, keep = jax.lax.top_k(best2, tg + 1)
    keep = (jnp.concatenate([keep[..., :tg - 1], keep[..., tg:]], -1)
            if swap else keep[..., :tg])
    kept = jnp.any(keep[..., :, None] == jnp.arange(G), axis=-2)
    return jnp.where(jnp.repeat(kept, E // G, axis=-1), sel, -jnp.inf)


def _choose(c: dict, sel: jax.Array) -> jax.Array:
    """(…, E) selection scores -> the chosen experts (…, k)."""
    return jax.lax.top_k(_eligible(c, sel), c["num_experts_per_tok"])[1]


def route(c: dict, scores: jax.Array, bias: jax.Array):
    """(…, E) router scores -> (experts (…, k), weights (…, k))."""
    top = _choose(c, scores + bias)
    wt = jnp.take_along_axis(scores, top, -1)
    wt = wt / wt.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    return top, wt


def _held_chosen(c: dict, top: jax.Array) -> jax.Array:
    """(…, k) chosen experts -> (…, held) which held experts are among
    them."""
    held = c["expert_offset"] + jnp.arange(c["experts_held"])
    return jnp.any(top[..., :, None] == held, axis=-2)


def _edge_tied(c: dict, elig: jax.Array, now: jax.Array, tau) -> jax.Array:
    """Whether a held expert lies within ``tau`` of the boundary between
    the k best of ``elig`` (which choose the held experts ``now``) and the
    rest."""
    k, off, n = (c["num_experts_per_tok"], c["expert_offset"],
                 c["experts_held"])
    edge = jax.lax.top_k(elig, k + 1)[0]
    last_in, first_out = edge[..., k - 1:k], edge[..., k:k + 1]
    hs = elig[..., off:off + n]
    return jnp.any(jnp.where(now, hs - first_out < tau,
                             jnp.isfinite(hs) & (last_in - hs < tau)), -1)


def _tied(c: dict, sel: jax.Array, tau) -> jax.Array:
    """(…, E) selection scores -> (…,): whether moving each score by up to
    ``tau / 2`` can change which held experts are chosen.  The ways tried:
    a held expert within ``tau`` of the boundary between the k chosen and
    the rest of the kept groups; every held score raised by ``tau / 2`` and
    every other lowered as much, and the reverse; the same for every score
    of the held experts' groups, which moves those groups across the
    groups' boundary; and, where the scores of the last group kept and the
    first left out (sums of two) lie within ``2 tau``, the two swapped, and
    the boundary of the k chosen tried again there."""
    E, G, tg, k = (c["experts_routed"], c["n_group"], c["topk_group"],
                   c["num_experts_per_tok"])
    off, n = c["expert_offset"], c["experts_held"]
    now = _held_chosen(c, _choose(c, sel))
    e = jnp.arange(E)
    is_held = (e >= off) & (e < off + n)
    in_held_group = (e // (E // G) >= off // (E // G)) & (
        e // (E // G) <= (off + n - 1) // (E // G))
    tied = _edge_tied(c, _eligible(c, sel), now, tau)
    for up in (is_held, in_held_group):
        nudge = jnp.where(up, tau / 2, -tau / 2)
        for d in (nudge, -nudge):
            tied |= jnp.any(_held_chosen(c, _choose(c, sel + d)) != now, -1)
    if tg < G:
        grouped = sel.reshape(sel.shape[:-1] + (G, E // G))
        g = jax.lax.top_k(jax.lax.top_k(grouped, 2)[0].sum(-1), tg + 1)[0]
        swapped = _eligible(c, sel, swap=True)
        moved = _held_chosen(c, jax.lax.top_k(swapped, k)[1])
        tied |= (g[..., tg - 1] - g[..., tg] < 2 * tau) & (
            jnp.any(moved != now, -1) | _edge_tied(c, swapped, now, tau))
    return tied


def route_margin(c: dict, sel: jax.Array) -> jax.Array:
    """(…, E) selection scores -> (…,): the least tie of ``TIE_GRID`` at
    which ``_tied`` holds, ``inf`` where none does."""
    grid = jnp.asarray(TIE_GRID, jnp.float32)
    tied = jax.lax.map(lambda tau: _tied(c, sel, tau), grid)
    grid = grid.reshape((-1,) + (1,) * (sel.ndim - 1))
    return jnp.min(jnp.where(tied, grid, jnp.inf), axis=0)


def _ffn_layer(c, dense, q, w, x):
    """The FFN half of a layer: (x, the route margin of each position)."""
    h = _rms(c, x, w["norm2"])
    if dense:
        return x + _swiglu(h, w["ffn"], q), jnp.full(x.shape[:-1], jnp.inf)
    m = w["moe"]
    logits = _mm("bsd,de->bse", h, m["router"], q)
    scores = jax.nn.sigmoid(logits)
    top, wt = route(c, scores, m["bias"])
    out = _swiglu(h, m["shared"], q)
    for j, f in enumerate(m["experts"]):
        g = jnp.sum(jnp.where(top == c["expert_offset"] + j, wt, 0.0), -1)
        out = out + g[..., None] * _swiglu(h, f, q)
    return x + out, route_margin(c, scores + m["bias"])


def _layer(c, dense, q, w, x):
    return _ffn_layer(c, dense, q, w, _attention(c, q, w, x))


def _final(c, fn, x):
    return _rms(c, x, fn)


def _block_logits(q, h, head):
    return _mm("bsd,dv->bsv", h, head, q)


def _gaps(ref, top, targets):
    """Gap below the best reference logit of ``top`` tokens, NaN where
    ``targets`` < 0."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, jnp.maximum(top, 0)[..., None], -1)[..., 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


def _gap_block(q_ctl, h_ref, h_ctl, head, tgt):
    ref = _block_logits(False, h_ref, head)
    out = [_gaps(ref, tgt, tgt)]
    if q_ctl:
        ctl = _block_logits(True, h_ctl, head)
        out.append(_gaps(ref, jnp.argmax(ctl, -1).astype(jnp.int32), tgt))
    return out


class Reference:
    """Jitted pieces for one configuration, compiled once per shape."""

    def __init__(self, cfg: dict):
        c = {k: cfg[k] for k in SHAPE_KEYS}
        if c["tie_word_embeddings"]:
            raise ValueError("mla_moe_decoder: tied embeddings not modelled")
        if c["scoring_func"] != "sigmoid":
            raise ValueError("mla_moe_decoder: only sigmoid (noaux_tc) "
                             "scoring is modelled")
        self.c = c
        self.tie = float(cfg.get("route_tie", 0.0))
        self.keys = jax.jit(partial(_keys, c))
        self.embed_w = jax.jit(partial(_embed_weights, c))
        self.layer_w = {d: jax.jit(partial(_layer_weights, c, d))
                        for d in (True, False)}
        self.layer = {(d, q): jax.jit(partial(_layer, c, d, q))
                      for d in (True, False) for q in (False, True)}
        self.final = jax.jit(partial(_final, c))
        self.gap_block = {q: jax.jit(partial(_gap_block, q))
                          for q in (False, True)}
        self.embed = jax.jit(lambda e, t: e["embedding"][t].astype(
            jnp.float32))

    def weights(self, seed: int) -> dict:
        """The whole parameter tree (for tests at small sizes)."""
        ek, lks = self.keys(np.int32(seed))
        layers = [self.layer_w[d](k) for (d, _), ks in zip(_runs(self.c), lks)
                  for k in ks]
        return {"embed": self.embed_w(ek), "layers": layers,
                "final_norm": jnp.ones((self.c["hidden_size"],),
                                       self.c["dtype"])}

    def _hidden(self, seed: int, tokens, qs) -> tuple:
        """The embedding weights, the final normed hidden states of the
        reference (and of the control, for ``True`` in ``qs``), and the
        reference's route margin of each position: its least over the
        expert layers."""
        ek, lks = self.keys(np.int32(seed))
        emb = self.embed_w(ek)
        tok = jnp.asarray(tokens, jnp.int32)
        xs = {q: self.embed(emb, tok) for q in qs}
        margin = jnp.full(tok.shape, jnp.inf)
        for (dense, _), ks in zip(_runs(self.c), lks):
            for k in ks:
                w = self.layer_w[dense](k)
                for q in qs:
                    xs[q], m = self.layer[dense, q](w, xs[q])
                    if not q:
                        margin = jnp.minimum(margin, m)
                del w
        fn = jnp.ones((self.c["hidden_size"],), self.c["dtype"])
        return emb, {q: self.final(fn, x) for q, x in xs.items()}, margin

    def logits(self, seed: int, tokens: np.ndarray) -> np.ndarray:
        """The reference's logits, (B, S, vocab) (for tests at small
        sizes)."""
        emb, hs, _ = self._hidden(seed, tokens, (False,))
        return np.asarray(jax.jit(partial(_block_logits, False))(
            hs[False], emb["lm_head"]))

    def run(self, seed: int, tokens: np.ndarray, targets: np.ndarray,
            control: bool = False) -> dict:
        """Gaps below the reference's best logit of the ``targets`` tokens
        (``targets[b, i]`` is the token served after position ``i``, -1
        where none), and with ``control`` those of the control's own
        first choices; NaN at a position whose route margin is at most
        the configuration's ``route_tie``.  Returns host arrays of shape
        (B, S), the route margins among them."""
        qs = (False, True) if control else (False,)
        emb, hs, margin = self._hidden(seed, tokens, qs)
        tgt = jnp.asarray(targets, jnp.int32)
        tgt = jnp.where(margin > self.tie, tgt, -1)
        S = tgt.shape[1]
        rows = math.gcd(S, ROWS)
        served, ctl = [], []
        for i in range(0, S, rows):
            sl = slice(i, i + rows)
            out = self.gap_block[control](
                hs[False][:, sl], hs[qs[-1]][:, sl], emb["lm_head"],
                tgt[:, sl])
            served.append(np.asarray(out[0]))
            if control:
                ctl.append(np.asarray(out[1]))
        res = {"served": np.concatenate(served, 1),
               "route_margin": np.asarray(margin)}
        if control:
            res["control"] = np.concatenate(ctl, 1)
        return res
