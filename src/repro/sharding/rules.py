"""Sharding rules: param/batch/cache PartitionSpecs for every architecture.

Conventions
-----------
* mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
  multi-pod.  Batch shards over all of ``("pod", "data")``; tensor/expert
  parallelism rides ``"model"``.
* Two policies:
    - ``tp``      — params replicated over data axes (classic DP x TP);
    - ``fsdp_tp`` — additionally shards a non-TP dimension of each large
      matrix over ``"data"`` (ZeRO-3-style; XLA inserts the all-gathers).
  ``fsdp_tp`` is the default: it is the only layout where the biggest
  assigned arch (deepseek-v3-671b + optimizer state) fits v5e HBM.
* A dimension is only sharded when divisible by the axis size; otherwise it
  falls back to replication (e.g. GQA kv-heads = 4 < model=16, batch=1 for
  long_500k).

The rules are *name-and-rank* based over the param pytree produced by
``repro.models``; stacked-layer params (under segments/stacks) get a leading
``None`` for the scan dimension.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0 and n >= size


def _axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    s = 1
    for n in names:
        s *= mesh.shape[n]
    return s


class ShardingRules:
    """Three layout policies:

    tp       — params replicated over data; tensor parallel over "model".
               Weights stay device-resident: right for decode/serving.
    fsdp_tp  — tp + ZeRO-3-style sharding of one extra dim over "data".
               Default: the only layout where deepseek-v3 + optimizer fits.
    fsdp     — pure ZeRO/DP: no tensor parallelism at all; batch shards over
               *every* mesh axis and weights shard over ("data","model").
               Removes the per-layer TP activation all-reduces entirely —
               the §Perf beyond-paper layout for dense training.
    """

    def __init__(self, cfg: ModelConfig, mesh, policy: str = "fsdp_tp"):
        assert policy in ("tp", "fsdp_tp", "fsdp"), policy
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy
        self.tp = "model"
        self.tp_size = mesh.shape["model"]
        self.dp = dp_axes_of(mesh)
        self.dp_size = _axis_size(mesh, self.dp)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """Axes the batch dimension shards over (model too under pure fsdp,
        except for MoE archs whose expert-parallel region needs 'model')."""
        if self.policy == "fsdp" and not self.cfg.moe.enabled:
            return self.dp + ("model",)
        return self.dp

    # -- helpers -------------------------------------------------------------
    def _fsdp(self, dim: int) -> Any:
        """Axis/axes for the ZeRO-sharded dimension of a weight."""
        if self.policy == "fsdp":
            axes = ("data", "model")
            if _div(dim, _axis_size(self.mesh, axes)):
                return axes
            if _div(dim, self.mesh.shape.get("data", 1)):
                return "data"
            return None
        if self.policy == "fsdp_tp" and _div(dim, self.mesh.shape.get("data", 1)):
            return "data"
        return None

    def _tp(self, dim: int) -> Any:
        if self.policy == "fsdp":
            return None
        return self.tp if _div(dim, self.tp_size) else None

    # -- per-leaf rule --------------------------------------------------------
    def spec_for(self, name: str, shape: tuple[int, ...], stacked: bool) -> P:
        base_shape = shape[1:] if stacked else shape
        spec = self._base_spec(name, base_shape)
        if stacked:
            spec = P(None, *spec)
        return spec

    def _base_spec(self, name: str, s: tuple[int, ...]) -> P:
        tp, fsdp = self._tp, self._fsdp
        if name == "embedding":                      # (V, D)
            if self.policy == "fsdp":
                # shard the vocab (non-contraction) dim: lookup is a masked
                # gather + small psum; sharding D would force full-D gathers
                return P(fsdp(s[0]), None)
            return P(tp(s[0]), fsdp(s[1]))
        if name == "lm_head":                        # (D, V)
            if self.policy == "fsdp":
                # vocab-sharded output: logits stay sharded through the CE
                # (no logits all-reduce, no weight gather)
                return P(None, fsdp(s[1]))
            return P(fsdp(s[0]), tp(s[1]))
        if name in ("wq", "wk", "wv"):               # (D, H, hd)
            return P(fsdp(s[0]), tp(s[1]), None)
        if name == "wo":                             # (H, hd, D)
            return P(tp(s[0]), None, fsdp(s[2]))
        if name in ("wq_a", "wkv_a"):                # (D, r)
            return P(fsdp(s[0]), None)
        if name in ("wq_b", "wk_b", "wv_b"):         # (r, H, hd)
            return P(None, tp(s[1]), None)
        if name in ("w_up", "w_gate") and len(s) == 2:   # mlp (D, F)
            return P(fsdp(s[0]), tp(s[1]))
        if name == "w_down" and len(s) == 2:             # mlp (F, D)
            return P(tp(s[0]), fsdp(s[1]))
        if name in ("w_up", "w_gate", "w_down") and len(s) == 3:
            # moe expert stacks (E, D, F) / (E, F, D)
            if self.cfg.moe_dispatch == "a2a":
                axes = ("data", "model")
                if _div(s[0], _axis_size(self.mesh, axes)):
                    return P(axes, None, None)   # resident 2D EP
            if name == "w_down":
                return P(tp(s[0]), None, fsdp(s[2]))
            return P(tp(s[0]), fsdp(s[1]), None)
        if name == "router":                         # (D, E) - small, replicated
            return P(None, None)
        if name in ("w_z", "w_x", "w_dt") and len(s) == 2:  # ssm (D, di|H)
            return P(fsdp(s[0]), tp(s[1]))
        if name in ("w_B", "w_C"):                   # ssm (D, N) - N small
            return P(fsdp(s[0]), None)
        if name == "conv_x":                         # (K, di)
            return P(None, tp(s[1]))
        if name in ("conv_B", "conv_C"):             # (K, N)
            return P(None, None)
        if name in ("dt_bias", "A_log", "D_skip"):   # (H,)
            return P(tp(s[0]))
        if name == "norm_scale":                     # (di,)
            return P(tp(s[0]))
        if name in ("w_in",):                        # rec (D, W)
            return P(fsdp(s[0]), tp(s[1]))
        if name == "conv_w":                         # rec (K, W)
            return P(None, tp(s[1]))
        if name in ("w_a", "w_x") and len(s) == 3:   # rec block-diag (nb, bs, bs)
            return P(tp(s[0]), None, None)
        if name in ("b_a", "b_x", "lambda_p"):       # (W,)
            return P(tp(s[0]))
        if name == "w_out":                          # rec (W, D)
            return P(tp(s[0]), fsdp(s[1]))
        # norms, scalars, q_norm/k_norm, everything else: replicated
        return P(*([None] * len(s)))

    # -- whole-tree specs -----------------------------------------------------
    def param_specs(self, abstract_params) -> Any:
        stacked_markers = ("segments", "enc_stack", "dec_stack")

        def rule(path, leaf):
            names = [k.key for k in path if hasattr(k, "key")]
            stacked = any(n in stacked_markers for n in names)
            return self.spec_for(names[-1] if names else "", leaf.shape, stacked)

        return jax.tree_util.tree_map_with_path(rule, abstract_params)

    def param_shardings(self, abstract_params) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_specs(abstract_params))

    # -- batches ---------------------------------------------------------------
    def _batch_dim(self, b: int) -> Any:
        """Shard batch over the policy's batch axes when divisible."""
        axes = self.batch_axes
        if _div(b, _axis_size(self.mesh, axes)):
            return axes
        if _div(b, self.dp_size):
            return self.dp
        return None

    def batch_specs(self, abstract_batch) -> Any:
        def rule(path, leaf):
            bdim = self._batch_dim(leaf.shape[0]) if leaf.ndim else None
            rest = [None] * (leaf.ndim - 1)
            if leaf.ndim == 0:
                return P()
            return P(bdim, *rest)

        return jax.tree_util.tree_map_with_path(rule, abstract_batch)

    def cache_specs(self, abstract_caches) -> Any:
        """Decode caches: stacked (L, B, ...) -> shard batch + head dims."""
        def rule(path, leaf):
            names = [k.key for k in path if hasattr(k, "key")]
            name = names[-1] if names else ""
            if name == "kv_pos":
                return P(*([None] * leaf.ndim))
            # leading layer-stack dim, then batch
            if leaf.ndim >= 2:
                bdim = self._batch_dim(leaf.shape[1])
                rest = [None] * (leaf.ndim - 2)
                # shard kv-head / head dims over model where they exist
                if name in ("k", "v", "cross_k", "cross_v") and leaf.ndim == 5:
                    # (L, B, cap, Hkv, hd): prefer kv-head sharding; when the
                    # heads don't divide the model axis, shard the sequence
                    # dim instead (context-parallel cache) so a 32k x 128
                    # cache never replicates 16x.
                    tp_h = self._tp(leaf.shape[3])
                    tp_s = self._tp(leaf.shape[2]) if tp_h is None else None
                    rest = [tp_s, tp_h, None]
                elif name == "lat" and leaf.ndim == 4 and bdim is None:
                    # MLA latent cache (L, B, cap, r) at batch=1: shard cap
                    rest = [self._tp(leaf.shape[2]), None]
                elif name == "ssm_state" and leaf.ndim == 5:
                    # (L, B, H, P, N)
                    rest = [self._tp(leaf.shape[2]), None, None]
                elif name == "h" and leaf.ndim == 3:
                    # (L, B, W)
                    rest = [self._tp(leaf.shape[2])]
                elif name in ("x",) and leaf.ndim == 4:
                    # conv state (L, B, K-1, di)
                    rest = [None, self._tp(leaf.shape[3])]
                elif name == "conv_state" and leaf.ndim == 4:
                    rest = [None, self._tp(leaf.shape[3])]
                elif name == "lat" and leaf.ndim == 4:
                    # (L, B, cap, r): replicate r
                    rest = [None, None]
                return P(None, bdim, *rest)
            return P(*([None] * leaf.ndim))

        return jax.tree_util.tree_map_with_path(rule, abstract_caches)

    def shardings_for(self, tree, kind: str) -> Any:
        specs = {"params": self.param_specs, "batch": self.batch_specs,
                 "cache": self.cache_specs}[kind](tree)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)


def opt_state_specs(rules: ShardingRules, param_specs) -> Any:
    """Adam moments share the param layout; counters replicated."""
    return {
        "m": param_specs,
        "v": param_specs,
        "count": P(),
    }
