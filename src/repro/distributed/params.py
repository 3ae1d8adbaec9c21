"""Parameter / optimizer / cache PartitionSpecs for the production mesh.

Strategy (TP on ``model``, ZeRO/FSDP on ``data``, DP across ``pod``):

* attention / MLP projections: input dim on ``data`` (FSDP), output dim on
  ``model`` (Megatron column-parallel); down/out projections transposed
  (row-parallel).
* MoE expert weights: experts on ``model`` (EP), input dim on ``data``.
* embeddings / lm_head: vocab on ``model``, embed dim on ``data``.
* RG-LRU / RWKV channel dims on ``model``; norms and scalar gains replicated.
* KV caches: batch on ``data``, sequence on ``model`` (flash-decoding style
  split -- GQA head counts rarely divide 16, sequence always does).
* optimizer moments: identical specs to their parameters.

Any dimension that does not divide its mesh axis falls back to replication
(granite-moe's vocab 49155, long_500k's batch 1); the roofline notes where
that costs bytes.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.lm import layer_stacks

# trailing-dims spec by (parent, leaf-name); "." matches any parent
_RULES: dict[tuple[str, str], tuple] = {
    (".", "embed"): ("model", "data"),
    (".", "lm_head"): ("data", "model"),
    (".", "enc_pos"): (None, None),
    (".", "dec_pos"): (None, None),
    # attention
    ("attn", "wq"): ("data", "model"),
    ("attn", "wk"): ("data", "model"),
    ("attn", "wv"): ("data", "model"),
    ("attn", "wo"): ("model", "data"),
    ("attn", "bq"): ("model",),
    ("attn", "bk"): ("model",),
    ("attn", "bv"): ("model",),
    ("attn", "bo"): (None,),
    ("xattn", "wq"): ("data", "model"),
    ("xattn", "wk"): ("data", "model"),
    ("xattn", "wv"): ("data", "model"),
    ("xattn", "wo"): ("model", "data"),
    ("xattn", "bq"): ("model",),
    ("xattn", "bk"): ("model",),
    ("xattn", "bv"): ("model",),
    ("xattn", "bo"): (None,),
    # dense MLP
    ("mlp", "w_gate"): ("data", "model"),
    ("mlp", "w_up"): ("data", "model"),
    ("mlp", "w_down"): ("model", "data"),
    ("mlp", "b_up"): ("model",),
    ("mlp", "b_down"): (None,),
    # MoE
    ("moe", "router"): ("data", None),
    ("moe", "w_gate"): ("model", "data", None),
    ("moe", "w_up"): ("model", "data", None),
    ("moe", "w_down"): ("model", None, "data"),
    # RG-LRU recurrent branch
    ("rec", "w_gate_branch"): ("data", "model"),
    ("rec", "w_rec_branch"): ("data", "model"),
    ("rec", "conv_w"): (None, "model"),
    ("rec", "conv_b"): ("model",),
    ("rec", "wa"): ("data", "model"),
    ("rec", "wx"): ("data", "model"),
    ("rec", "ba"): ("model",),
    ("rec", "bx"): ("model",),
    ("rec", "lam"): ("model",),
    ("rec", "w_out"): ("model", "data"),
    # RWKV time-mix
    ("tm", "wr"): ("data", "model"),
    ("tm", "wk"): ("data", "model"),
    ("tm", "wv"): ("data", "model"),
    ("tm", "wg"): ("data", "model"),
    ("tm", "wo"): ("model", "data"),
    ("tm", "lora_a"): ("data", None),
    ("tm", "lora_b"): (None, None, "data"),
    ("tm", "w_lora_a"): ("data", None),
    ("tm", "w_lora_b"): (None, "data"),
    ("tm", "mu"): (None, None),
    ("tm", "ww"): (None,),
    ("tm", "u"): (None,),
    ("tm", "ln_scale"): (None,),
    # RWKV channel-mix
    ("cm", "wk"): ("data", "model"),
    ("cm", "wv"): ("model", "data"),
    ("cm", "wr"): ("data", "model"),
    ("cm", "mu_k"): (None,),
    ("cm", "mu_r"): (None,),
}


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        out.append(str(name) if name is not None else "")
    return out


def _divisible(dim: int, axes, mesh: Mesh) -> bool:
    if axes is None:
        return True
    axes = axes if isinstance(axes, tuple) else (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return dim % size == 0


def _pad_spec(trailing: tuple, ndim: int, shape, mesh: Mesh) -> P:
    lead = ndim - len(trailing)
    parts = [None] * lead + list(trailing)
    # drop axes the tensor cannot divide (falls back to replication)
    parts = [a if _divisible(shape[i], a, mesh) else None
             for i, a in enumerate(parts)]
    return P(*parts)


def spec_for_param(path, leaf, mesh: Mesh) -> P:
    names = [n for n in _path_names(path) if n]
    leaf_name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else "."
    rule = _RULES.get((parent, leaf_name)) or _RULES.get((".", leaf_name))
    if rule is None:
        # norms (ln1/ln2/...), scalar gains: replicate
        return P(*([None] * leaf.ndim))
    return _pad_spec(rule, leaf.ndim, leaf.shape, mesh)


def _strip_data(spec: P) -> P:
    """ZeRO-1 live params: TP on `model` only, replicated over `data`."""
    return P(*[None if p == "data" else p for p in spec])


def param_specs(abstract_params, mesh: Mesh, *, zero1: bool = False):
    full = jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_param(path, leaf, mesh), abstract_params)
    if zero1:
        return jax.tree.map(_strip_data, full,
                            is_leaf=lambda x: isinstance(x, P))
    return full


def param_shardings(abstract_params, mesh: Mesh, *, zero1: bool = False):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(abstract_params, mesh, zero1=zero1))


def opt_state_specs(abstract_opt, abstract_params, mesh: Mesh, *,
                    zero1: bool = False):
    """Moments (and the fp32 master copy under ZeRO-1) always keep the full
    data+model sharding -- that is what ZeRO-1 shards."""
    pspec = param_specs(abstract_params, mesh)      # full sharding
    out = {
        "mu": pspec,
        "nu": pspec,
        "step": P(),
    }
    if "master" in abstract_opt:
        out["master"] = pspec
    return out


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_specs(abstract_batch, mesh: Mesh):
    """Leading dim = global batch on ("pod", "data")."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        if _divisible(leaf.shape[0], batch_axes, mesh):
            return P(batch_axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(one, abstract_batch)


def cache_specs(abstract_cache, cfg: ModelConfig, mesh: Mesh):
    """KV caches: (L, B, S, KV, D) -> batch on data, seq on model.
    Recurrent states: channel dims on model."""
    prefixes = [prefix for _, prefix, _ in layer_stacks(cfg) if prefix]

    def one(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        for prefix in prefixes:       # each stack's leaves shard alike
            name = name.removeprefix(prefix)
        if name in ("k", "v", "cross_k", "cross_v"):
            lead = leaf.ndim - 4                       # stacked layer axes
            parts = [None] * lead + ["data", "model", None, None]
        elif name in ("c_kv", "k_pe"):                 # MLA (L, B, S, C)
            parts = [None, "data", "model", None]
        elif name == "S":                              # rwkv state (L,B,H,N,N)
            parts = [None, "data", "model", None, None]
        elif name in ("x_tm", "x_cm"):                 # (L, B, D)
            parts = [None, "data", "model"]
        elif name in ("h", "tail_h"):                  # (..., B, W)
            parts = [None] * (leaf.ndim - 2) + ["data", "model"]
        elif name in ("conv", "tail_conv"):            # (..., B, cw-1, W)
            parts = [None] * (leaf.ndim - 3) + ["data", None, "model"]
        else:
            parts = [None] * leaf.ndim
        parts = [a if _divisible(leaf.shape[i], a, mesh) else None
                 for i, a in enumerate(parts)]
        return P(*parts)

    return jax.tree_util.tree_map_with_path(one, abstract_cache)
