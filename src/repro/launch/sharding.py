"""Sharding rules: params, caches and batch inputs → PartitionSpecs.

Megatron-style tensor parallelism on the "model" axis plus optional
FSDP-style weight sharding on the data axes (big_model archs):

* column-parallel weights (output-feature sharded): wq/wk/wv, w1/w3,
  expert up-projections, rwkv r/k/v/g projections, rg-lru in-projections —
  P(..., fsdp, "model")
* row-parallel weights (input-feature sharded): wo, w2, expert down-
  projections — P(..., "model", fsdp)
* expert stacks additionally shard the expert axis on "model" is NOT done
  here — experts live in the (K, N) dims per expert with the expert axis
  treated as a stack dim; expert parallelism is the §Perf all-to-all
  variant (launch/expert_parallel.py)
* embeddings: vocab on "model" when divisible, else replicated
* q/k/v biases: features on "model" as their projection's columns
* KV caches: batch on data axes; heads on "model" when divisible
  (they rarely are at 16-way TP with GQA), else **sequence-parallel** —
  the flash-decoding-across-chips layout from DESIGN.md §6
* quantized PackedWeight leaves shard their tile grid (Kt, Nt) exactly as
  the logical (K, N) would be — tile-major packing keeps every named
  dimension intact, which is what makes the offline layout pjit-friendly.

All rules are name/shape driven over the params pytree — no per-arch
special cases beyond cfg.big_model.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.packing import PackedWeight
from repro.configs.base import ModelConfig

from .mesh import axis_size, data_axes

# weight name → parallel style
_COLUMN = ("wq", "wk", "wv", "w1", "w3", "ws1", "ws3", "we1", "we3",
           "ck", "wr", "wg", "wx", "wy", "wa", "wi", "xwq", "xwk", "xwv",
           "cr", "lm_head")
_ROW = ("wo", "w2", "ws2", "we2", "cv", "xwo")
_REPLICATED = ("router", "w_A", "w_B", "img_proj")   # small / odd shapes
#: q/k/v bias → the projection whose output columns it is added to
_BIAS_OF = {"bq": "wq", "bk": "wk", "bv": "wv"}


def _name_of(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "name", k))))
    return parts[-1] if parts else ""


def _style(name: str) -> str:
    if name in _ROW:
        return "row"
    if name in _COLUMN:
        return "column"
    if name in _REPLICATED:
        return "replicated"
    return "other"


def _div(n: int, by: int) -> bool:
    return by > 0 and n % by == 0 and n >= by


class ShardingRules:
    def __init__(self, mesh: Mesh, cfg: ModelConfig, fsdp: bool = True):
        self.mesh = mesh
        self.cfg = cfg
        self.model = "model"
        self.model_size = axis_size(mesh, "model")
        self.data = data_axes(mesh)                  # ("data",) or ("pod","data")
        self.data_size = axis_size(mesh, self.data)
        # FSDP spreads big-model weights over the data axes.  For decode
        # serving this re-gathers every weight every step (§Perf hillclimb
        # 3 measured it as the dominant collective term) — pass fsdp=False
        # there; w4 weights fit model-sharded.
        self.fsdp: Optional[Tuple[str, ...]] = \
            self.data if (cfg.big_model and fsdp) else None

    def ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- parameters -------------------------------------------------------

    def _expert_axis(self, name: str, leaf) -> Optional[int]:
        """Expert stacks (we1/we2/we3): (L, E, ...) — E at axis 1."""
        if name in ("we1", "we2", "we3") and leaf.ndim >= 3:
            return 1
        return None

    # attention projections: TP must land WHOLE heads per device, or the
    # QK/PV contractions see a split head_dim and GSPMD all-reduces every
    # score tile (measured: 90% of arctic-prefill collective bytes).
    _Q_HEADS = ("wq", "xwq")
    _KV_HEADS = ("wk", "wv", "xwk", "xwv")
    _O_HEADS = ("wo", "xwo")

    def _heads_ok(self, name: str) -> bool:
        if self.cfg.family == "ssm":
            # rwkv reuses the wk/wv/wo names for full (d, d) projections
            # feeding per-head (rwkv_head_dim-wide) recurrences — the
            # alignment unit is d/rwkv_head_dim heads, not GQA heads.
            heads = self.cfg.d_model // self.cfg.rwkv_head_dim
            return _div(heads, self.model_size)
        if name in self._Q_HEADS or name in self._O_HEADS:
            return _div(self.cfg.n_heads, self.model_size)
        if name in self._KV_HEADS:
            return _div(self.cfg.n_kv_heads, self.model_size)
        return True

    def _matrix_spec(self, name: str, shape, K_ax: int, N_ax: int,
                     expert_ax: Optional[int] = None) -> P:
        """Spec for a (.., K, N) weight given its parallel style.

        The style's NATURAL dim only goes on "model" (column → N,
        row → K); when it doesn't divide — tile-granular packed weights
        often don't — the weight replicates over "model" rather than
        swapping to the other dim: swapped sharding puts contractions on
        a split axis and GSPMD inserts per-tile partial-sum all-reduces
        (§Perf hillclimb 2, confirmed pathological).  Expert stacks shard
        E on "model" (expert parallelism); FSDP spreads the off dim over
        the data axes for big_model archs.
        """
        style = _style(name)
        spec = [None] * len(shape)
        model_used = False
        if expert_ax is not None and _div(shape[expert_ax], self.model_size):
            spec[expert_ax] = self.model
            model_used = True
        natural = N_ax if style == "column" else K_ax
        if (not model_used and style in ("column", "row")
                and self._heads_ok(name)
                and _div(shape[natural], self.model_size)):
            # rwkv wk/wv are (d, d) projections feeding per-head (64-wide)
            # recurrences — head-alignment there means d/64 heads, always
            # divisible in this pool, so the generic check suffices.
            spec[natural] = self.model
            model_used = True
        if self.fsdp:
            # multi-pod: if a dim doesn't divide the combined ("pod",
            # "data") size, fall back to the innermost data axis alone —
            # replicating over "pod" only (arctic's Kt=112 divides 16 but
            # not 32; without this the experts replicate entirely: 21 GB
            # per device, over HBM budget).
            candidates = [self.fsdp]
            if len(self.fsdp) > 1:
                candidates.append((self.fsdp[-1],))
            done = False
            for ax in ((K_ax, N_ax) if style == "column" else (N_ax, K_ax)):
                for cand in candidates:
                    if spec[ax] is None and _div(shape[ax],
                                                 axis_size(self.mesh, cand)):
                        spec[ax] = cand
                        done = True
                        break
                if done:
                    break
        return P(*spec)

    def param_spec(self, path, leaf) -> P:
        name = _name_of([k for k in path
                         if not str(getattr(k, "name", "")) in
                         ("data", "scales")])
        # PackedWeight fields arrive as separate leaves (.data / .scales)
        field = str(getattr(path[-1], "name", getattr(path[-1], "key", "")))
        # find the weight's own name = last dict key in the path
        wname = ""
        for k in path:
            kk = getattr(k, "key", None)
            if kk is not None:
                wname = str(kk)
        if wname == "embed":
            V = leaf.shape[0]
            return P(self.model if _div(V, self.model_size) else None)
        if wname in _BIAS_OF:
            # (..., N): features on "model" where its projection's
            # columns go there (whole heads per device), else replicated
            spec = [None] * leaf.ndim
            if self._heads_ok(_BIAS_OF[wname]) and \
                    _div(leaf.shape[-1], self.model_size):
                spec[-1] = self.model
            return P(*spec)
        if wname == "dec_pos" or leaf.ndim <= 1:
            return P()
        style = _style(wname)
        if style == "replicated":
            return P(*([None] * leaf.ndim))
        if field == "data" and leaf.ndim >= 4:
            # PackedWeight.data: (..., Kt, Nt, bk_store, bn) — the tile
            # grid shards exactly as the logical (K, N) would.
            return self._matrix_spec(wname, leaf.shape,
                                     leaf.ndim - 4, leaf.ndim - 3,
                                     expert_ax=self._expert_axis(wname, leaf))
        if field == "scales" and leaf.ndim >= 2:
            # PackedWeight.scales: (..., G, N) — G co-shards with Kt
            # (bk is a multiple of the quant group), N with Nt.  No FSDP
            # on scales (small).
            G_ax, N_ax = leaf.ndim - 2, leaf.ndim - 1
            shape = leaf.shape
            spec = [None] * leaf.ndim
            eax = self._expert_axis(wname, leaf)
            if eax is not None and _div(shape[eax], self.model_size):
                spec[eax] = self.model
                return P(*spec)
            style = _style(wname)
            natural = N_ax if style == "column" else G_ax
            if self._heads_ok(wname) and _div(shape[natural],
                                              self.model_size):
                spec[natural] = self.model
            return P(*spec)
        if leaf.ndim >= 2:
            return self._matrix_spec(wname, leaf.shape,
                                     leaf.ndim - 2, leaf.ndim - 1,
                                     expert_ax=self._expert_axis(wname, leaf))
        return P()

    def params(self, params_tree) -> Any:
        """Pytree of NamedShardings matching ``params_tree``."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        return treedef.unflatten(
            [self.ns(self.param_spec(p, l)) for p, l in flat])

    def opt_state(self, params_tree, opt_state_tree) -> Any:
        """Optimizer moments inherit the param sharding; scalars replicate.

        Works for adamw ({mu, nu, step}) and adafactor (factored leaves are
        reduced copies of the param dims — sharded where shapes allow)."""
        pflat, _ = jax.tree_util.tree_flatten_with_path(params_tree)
        by_shape = {}
        for path, leaf in pflat:
            by_shape.setdefault(leaf.shape, []).append(
                self.param_spec(path, leaf))

        def per(leaf):
            if leaf.ndim == 0:
                return self.ns(P())
            specs = by_shape.get(leaf.shape)
            if specs:
                return self.ns(specs[0])
            # factored adafactor state: match the param spec's prefix where
            # the trailing dim was reduced away — conservative: replicate.
            return self.ns(P(*([None] * leaf.ndim)))

        return jax.tree.map(per, opt_state_tree)

    # -- caches -----------------------------------------------------------

    def _kv_spec(self, leaf) -> P:
        """(L, B, S, H, D)-family cache leaf."""
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2 and _div(leaf.shape[1], self.data_size):
            spec[1] = self.data
        if leaf.ndim >= 4:
            H = leaf.shape[3]
            S = leaf.shape[2]
            if _div(H, self.model_size):
                spec[3] = self.model
            elif _div(S, self.model_size):
                spec[2] = self.model          # sequence-parallel KV
        return P(*spec)

    def cache(self, cache_tree) -> Any:
        def per_path(path, leaf):
            field = ""
            for k in path:
                n = getattr(k, "name", None)
                if n is not None:
                    field = str(n)
            spec = [None] * leaf.ndim
            if field in ("k", "v", "k_scale", "v_scale"):
                return self.ns(self._kv_spec(leaf))
            # generic state leaf: (stack, B, ...rest) — batch on data, the
            # last axis on model when divisible (wkv heads / lru width)
            if leaf.ndim >= 2 and _div(leaf.shape[1], self.data_size):
                spec[1] = self.data
            if field == "wkv" and leaf.ndim >= 3 and \
                    _div(leaf.shape[2], self.model_size):
                spec[2] = self.model          # rwkv heads
            elif leaf.ndim >= 3 and _div(leaf.shape[-1], self.model_size):
                spec[-1] = self.model         # lru width / hidden dim
            return self.ns(P(*spec))

        flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
        return treedef.unflatten([per_path(p, l) for p, l in flat])

    # -- batch inputs -------------------------------------------------------

    def tokens(self, shape) -> NamedSharding:
        B = shape[0]
        return self.ns(P(self.data if _div(B, self.data_size) else None))

    def extra(self, extra_specs: dict) -> dict:
        out = {}
        for k, s in extra_specs.items():
            spec = [None] * len(s.shape)
            if _div(s.shape[0], self.data_size):
                spec[0] = self.data
            out[k] = self.ns(P(*spec))
        return out

    def replicated(self) -> NamedSharding:
        return self.ns(P())
