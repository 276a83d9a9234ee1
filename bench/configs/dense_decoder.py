"""Plain reference of a dense decoder-only transformer, in float32.

The reference of every configuration whose ``bench/configs/<name>.json``
names ``"reference": "dense_decoder"``.  It imports nothing of the program
under test: the weights are made here from the run's seed, bf16 as served,
by the same random draws the configuration's initializer specifies (a
``jax.random.PRNGKey(seed)`` split into embedding and layer keys, each
matrix a standard normal scaled by its fan-in; norms start at one, biases
at zero).  The forward pass is the textbook one, computed in float32 at
``highest`` matmul precision, one layer at a time so that it fits beside
nothing else on the chip:

    x = E[tokens]
    per layer:  x += W_o . attn(rope(W_q n1(x)), rope(W_k n1(x)), W_v n1(x))
                x += W_down (silu(W_gate n2(x)) * W_up n2(x))
    logits = W_head n(x)

with causal softmax attention at 1/sqrt(head_dim), grouped-query heads
(query head h reads key/value head h // (H / H_kv)), rotary embedding on
the leading ``rope_pct`` of each head in the half-split convention, and a
layer norm (with bias) or RMS norm per the configuration.

``control=True`` also runs the control: the same forward with both operands
of every matrix product rounded to float8 e4m3 (per-tensor scale), the
precision a step below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _key(cfg: dict, names):
    return tuple(cfg[n] for n in names)


SHAPE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "mlp_kind", "norm_kind",
              "norm_eps", "rope_pct", "rope_theta", "tie_embeddings")


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _normal(key, shape, scale):
    return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)


def _norm_weights(c: dict) -> dict:
    d = c["d_model"]
    p = {"scale": jnp.ones((d,), jnp.bfloat16)}
    if c["norm_kind"] == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.bfloat16)
    return p


def _layer_weights(c: dict, key) -> dict:
    d, h, kv, hd, ff = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                        c["head_dim"], c["d_ff"])
    (bk,) = jax.random.split(key, 1)
    k1, k2, _ = jax.random.split(bk, 3)
    a = jax.random.split(k1, 4)
    m = jax.random.split(k2, 3)
    s = d ** -0.5
    w = {"wq": _normal(a[0], (d, h, hd), s),
         "wk": _normal(a[1], (d, kv, hd), s),
         "wv": _normal(a[2], (d, kv, hd), s),
         "wo": _normal(a[3], (h, hd, d), (h * hd) ** -0.5),
         "w_up": _normal(m[0], (d, ff), s),
         "w_down": _normal(m[1], (ff, d), ff ** -0.5),
         "norm1": _norm_weights(c), "norm2": _norm_weights(c)}
    if c["mlp_kind"] in ("silu_glu", "geglu"):
        w["w_gate"] = _normal(m[2], (d, ff), s)
    return w


def _embed_weights(c: dict, key) -> dict:
    k1, k2 = jax.random.split(key)
    d, V = c["d_model"], c["vocab_size"]
    w = {"embedding": _normal(k1, (V, d), 0.02)}
    if not c["tie_embeddings"]:
        w["lm_head"] = _normal(k2, (d, V), d ** -0.5)
    return w


def _keys(c: dict, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return keys[0], jax.random.split(keys[1], c["num_layers"])


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


def _norm(c, p, x):
    if c["norm_kind"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + c["norm_eps"])
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + c["norm_eps"]) * p["scale"].astype(
        jnp.float32)


def _rope(c, x, pos):
    hd = x.shape[-1]
    rot = int(hd * c["rope_pct"])
    rot -= rot % 2
    if rot == 0:
        return x
    freqs = c["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _layer(c, q, w, x):
    B, S, _ = x.shape
    pos = jnp.arange(S)
    h = _norm(c, w["norm1"], x)
    qh = _rope(c, _mm("bsd,dhk->bshk", h, w["wq"], q), pos)
    kh = _rope(c, _mm("bsd,dhk->bshk", h, w["wk"], q), pos)
    vh = _mm("bsd,dhk->bshk", h, w["wv"], q)
    g = c["num_heads"] // c["num_kv_heads"]
    kh = jnp.repeat(kh, g, axis=2)
    vh = jnp.repeat(vh, g, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", qh, kh, precision=HI)
    s = s * c["head_dim"] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), vh,
                   precision=HI)
    x = x + _mm("bshk,hkd->bsd", o, w["wo"], q)
    h = _norm(c, w["norm2"], x)
    up = _mm("bsd,df->bsf", h, w["w_up"], q)
    if c["mlp_kind"] == "silu_glu":
        up = jax.nn.silu(_mm("bsd,df->bsf", h, w["w_gate"], q)) * up
    elif c["mlp_kind"] == "geglu":
        up = jax.nn.gelu(_mm("bsd,df->bsf", h, w["w_gate"], q)) * up
    else:
        up = jax.nn.gelu(up)
    return x + _mm("bsf,fd->bsd", up, w["w_down"], q)


def _logits(c, q, emb, fn, x):
    h = _norm(c, fn, x)
    head = emb["embedding"].T if c["tie_embeddings"] else emb["lm_head"]
    return _mm("bsd,dv->bsv", h, head, q)


def _gaps(ref, top, targets):
    """Gap below the best reference logit of ``top`` (B, S) tokens, NaN
    where ``targets`` < 0."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, jnp.maximum(top, 0)[..., None], -1)[..., 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


class Reference:
    """Jitted pieces for one configuration, compiled once per shape."""

    def __init__(self, cfg: dict):
        c = {k: cfg[k] for k in SHAPE_KEYS}
        self.c = c
        self.keys = jax.jit(partial(_keys, c))
        self.embed_w = jax.jit(partial(_embed_weights, c))
        self.layer_w = jax.jit(partial(_layer_weights, c))
        self.norm_w = jax.jit(partial(_norm_weights, c))
        self.layer = {q: jax.jit(partial(_layer, c, q)) for q in (False, True)}
        self.logits = {q: jax.jit(partial(_logits, c, q))
                       for q in (False, True)}
        self.embed = jax.jit(lambda e, t: e["embedding"][t].astype(
            jnp.float32))
        self.gaps = jax.jit(_gaps)
        self.argmax = jax.jit(lambda z: jnp.argmax(z, -1).astype(jnp.int32))

    def weights(self, seed: int) -> dict:
        """The whole parameter tree (for tests at small sizes)."""
        ek, lks = self.keys(np.int32(seed))
        return {"embed": self.embed_w(ek),
                "layers": [self.layer_w(lks[i])
                           for i in range(self.c["num_layers"])],
                "final_norm": self.norm_w()}

    def run(self, seed: int, tokens: np.ndarray, targets: np.ndarray,
            control: bool = False) -> dict:
        """Gaps below the reference's best logit of the ``targets`` tokens
        (``targets[b, i]`` is the token served after position ``i``, -1
        where none), and with ``control`` those of the control's own
        first choices.  Returns host arrays of shape (B, S)."""
        ek, lks = self.keys(np.int32(seed))
        emb = self.embed_w(ek)
        tok = jnp.asarray(tokens, jnp.int32)
        tgt = jnp.asarray(targets, jnp.int32)
        xs = {q: self.embed(emb, tok) for q in ((False, True) if control
                                                  else (False,))}
        for i in range(self.c["num_layers"]):
            w = self.layer_w(lks[i])
            xs = {q: self.layer[q](w, x) for q, x in xs.items()}
            del w
        fn = self.norm_w()
        ref = self.logits[False](emb, fn, xs[False])
        out = {"served": np.asarray(self.gaps(ref, tgt, tgt))}
        if control:
            ctl = self.logits[True](emb, fn, xs[True])
            out["control"] = np.asarray(self.gaps(ref, self.argmax(ctl),
                                                  tgt))
        return out
