"""Derive PartitionSpecs for every params/opt-state/cache/batch leaf from a
ModelPlan — the realized form of the searched strategy that ``jax.jit``'s
``in_shardings``/``out_shardings`` consume.

Parameter rule table: each (sublayer, param) pair maps its array dims to
logical dims; the sublayer's LayerConfig supplies the mesh axes.  Stacked
(`stack.*`) leaves get a leading ``None`` for the unit dim.  When a plan has
several segments, parameters follow the *dominant* (most units) segment's
configs — `with_sharding_constraint` inside each scanned segment re-lays
activations out per segment, and XLA reshards the few boundary parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import LayerConfig, zero1_axes
from repro.core.sharding import pspec
from repro.models.arch import ArchConfig
from repro.models.plan import ModelPlan, UnitPlan

R = LayerConfig.REPLICATED

# (sublayer key, param name) -> (cfg key, logical dims per array axis)
_RULES: dict[tuple[str, str], tuple[str, tuple]] = {
    ("attn", "wq"): ("attn", (None, "heads", None)),
    ("attn", "wk"): ("attn", (None, "heads", None)),
    ("attn", "wv"): ("attn", (None, "heads", None)),
    ("attn", "bq"): ("attn", ("heads", None)),
    ("attn", "bk"): ("attn", ("heads", None)),
    ("attn", "bv"): ("attn", ("heads", None)),
    ("attn", "q_norm"): ("attn", (None,)),
    ("attn", "k_norm"): ("attn", (None,)),
    ("attn", "wo"): ("attn_out", (None, None, "d_model")),
    ("xattn", "wq"): ("xattn", (None, "heads", None)),
    ("xattn", "wk"): ("xattn", (None, "heads", None)),
    ("xattn", "wv"): ("xattn", (None, "heads", None)),
    ("xattn", "bq"): ("xattn", ("heads", None)),
    ("xattn", "bk"): ("xattn", ("heads", None)),
    ("xattn", "bv"): ("xattn", ("heads", None)),
    ("xattn", "q_norm"): ("xattn", (None,)),
    ("xattn", "k_norm"): ("xattn", (None,)),
    ("xattn", "wo"): ("xattn_out", (None, None, "d_model")),
    ("mlp", "wi"): ("mlp_in", (None, "d_ff")),
    ("mlp", "wg"): ("mlp_in", (None, "d_ff")),
    ("mlp", "wo"): ("mlp_out", (None, "d_model")),
    ("moe", "router"): ("moe", (None, "expert")),
    ("moe", "wi"): ("moe", ("expert", None, "d_ff")),
    ("moe", "wg"): ("moe", ("expert", None, "d_ff")),
    ("moe", "wo"): ("moe", ("expert", "d_ff", None)),
    ("tmix", "wr"): ("tmix", (None, "d_model")),
    ("tmix", "wk"): ("tmix", (None, "d_model")),
    ("tmix", "wv"): ("tmix", (None, "d_model")),
    ("tmix", "wg"): ("tmix", (None, "d_model")),
    ("tmix", "wo"): ("tmix", ("d_model", None)),
    ("tmix", "w0"): ("tmix", ("d_model",)),
    ("tmix", "mu"): ("tmix", (None, None)),
    ("tmix", "w_lora_a"): ("tmix", (None, None)),
    ("tmix", "w_lora_b"): ("tmix", (None, "d_model")),
    ("tmix", "u"): ("tmix", (None, None)),
    ("tmix", "ln_x"): ("tmix", ("d_model",)),
    ("cmix", "wk"): ("cmix", (None, "d_ff")),
    ("cmix", "wv"): ("cmix", ("d_ff", None)),
    ("cmix", "wr"): ("cmix", (None, None)),
    ("cmix", "mu"): ("cmix", (None, None)),
    ("ssm", "in_proj"): ("ssm", (None, "d_model")),
    ("ssm", "conv_w"): ("ssm", (None, "d_model")),
    ("ssm", "conv_b"): ("ssm", ("d_model",)),
    ("ssm", "x_proj"): ("ssm", ("d_model", None)),
    ("ssm", "dt_proj"): ("ssm", (None, "d_model")),
    ("ssm", "dt_bias"): ("ssm", ("d_model",)),
    ("ssm", "A_log"): ("ssm", ("d_model", None)),
    ("ssm", "D"): ("ssm", ("d_model",)),
    ("ssm", "out_proj"): ("ssm", ("d_model", None)),
}


def dominant_unit_plan(segments) -> UnitPlan | None:
    if not segments:
        return None
    return max(segments, key=lambda s: s.n_units).plan


def spec_axes(spec: P) -> set[str]:
    """The mesh axes ``spec`` splits over."""
    return {a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}


def split_free_dim(spec: P, shape, axes, axis_sizes) -> P | None:
    """``spec`` with ``axes`` added, as one entry, on the largest
    dimension of ``shape`` that ``spec`` leaves whole and that their
    degree divides; None where no dimension does.  FSDP stores a
    parameter so (:func:`param_pspecs`), ZeRO-1 its moments
    (:func:`adamw_shardings`)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    deg = math.prod(axis_sizes[a] for a in axes)
    free = [(shape[i], i) for i, e in enumerate(entries)
            if e is None and shape[i] % deg == 0]
    if not free:
        return None
    entries[max(free)[1]] = axes if len(axes) > 1 else axes[0]
    return P(*entries)


def param_pspecs(params, arch: ArchConfig, plan: ModelPlan, *,
                 stages=None):
    """Pytree of PartitionSpec mirroring ``params``.

    ``stages`` (a :class:`~repro.core.stages.StageAssignment` with
    ``num_stages > 1``) places the stacked decoder parameters by pipeline
    stage: the leading unit dim of every ``stack.*`` leaf is sharded over
    the stage mesh axis, so each stage's device group holds exactly its
    contiguous unit range — the stage sub-mesh placement the staged
    search priced.  (Contiguous stages over homogeneous units map to
    equal leading-dim slices, which is what a named-axis shard is.)
    """
    dec_plan = dominant_unit_plan(plan.segments)
    enc_plan = dominant_unit_plan(plan.enc_segments)
    stage_axis = None
    if stages is not None and stages.num_stages > 1:
        stage_axis = stages.mesh_axis

    def add_fsdp_axes(spec: P, shape, cfg: LayerConfig,
                      mesh_axis_sizes) -> P:
        """FSDP realization: distribute the replicating (pod/data/model)
        axes onto the largest free divisible dim of the stored param."""
        if not cfg.fsdp:
            return spec
        used = spec_axes(spec)
        # FSDP shards over every axis not already sharding this param —
        # including the batch axes (that is what makes it ZeRO-3).
        axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh_axis_sizes and a not in used)
        while axes:
            split = split_free_dim(spec, shape, axes, mesh_axis_sizes)
            if split is not None:
                return split
            axes = axes[:-1]
        return spec

    # mesh axis sizes are resolved lazily in to_shardings; here we use the
    # production superset (pod/data/model all present is fine — extra axes
    # are dropped downstream).
    from repro.core.device import multi_pod_mesh_spec
    _ms = multi_pod_mesh_spec()
    axis_sizes = {a.name: a.size for a in _ms.axes}

    def leaf_spec(path, leaf) -> P:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        top = keys[0]
        if top == "embed":
            spec = pspec(plan.embed, ("vocab", "d_model"))
            return add_fsdp_axes(spec, leaf.shape, plan.embed, axis_sizes)
        if top == "lm_head":
            spec = pspec(plan.lm_head, (None, "vocab"))
            return add_fsdp_axes(spec, leaf.shape, plan.lm_head, axis_sizes)
        if top == "enc_in":
            return pspec(plan.enc_embed, (None, "d_model"))
        if top in ("final_norm", "enc_norm"):
            return P(*([None] * leaf.ndim))
        if top in ("stack", "enc_stack"):
            unit_plan = dec_plan if top == "stack" else enc_plan
            lkey = keys[1]            # "l{j}"
            j = int(lkey[1:])
            sub = unit_plan[j] if unit_plan else {}
            sublayer, pname = keys[2], keys[3]
            lead = stage_axis if top == "stack" else None
            if sublayer in ("ln1", "ln2", "ln_x"):
                return P(*((lead,) + (None,) * (leaf.ndim - 1)))
            rule = _RULES.get((sublayer, pname))
            if rule is None:
                return P(*((lead,) + (None,) * (leaf.ndim - 1)))
            cfg_key, dims = rule
            cfg = sub.get(cfg_key, R)
            spec = pspec(cfg, dims)
            spec = add_fsdp_axes(spec, leaf.shape[1:], cfg, axis_sizes)
            # leading unit dim: stage-sharded when pipelined (decoder
            # stack only — encdec graphs are not stageable)
            return P(*((lead,) + tuple(spec)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def batch_pspecs(batch, plan: ModelPlan):
    """Input batch: shard the batch dim by the embed config's batch axes."""
    baxes = plan.embed.axes_for("batch")
    entry = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    def one(path, leaf):
        return P(*((entry,) + (None,) * (leaf.ndim - 1)))

    return jax.tree_util.tree_map_with_path(one, batch)


def cache_pspecs(cache, arch: ArchConfig, plan: ModelPlan, *,
                 paged: bool = False):
    """KV/state cache: batch by embed batch axes; KV heads / channels by the
    dominant plan's mixer config.

    With ``paged=True`` the KV leaves are the serve engine's block pool
    ``(units, num_blocks, block_size, KH, hd)``: the block and in-block
    token axes stay replicated (any slot's table can point at any block,
    so there is no batch/seq meaning to shard over) while heads follow
    the searched decode-phase config exactly as in the dense layout.
    """
    dec_plan = dominant_unit_plan(plan.segments)

    def leaf_spec(path, leaf) -> P:
        keys = [getattr(k, "key", None) for k in path]
        # paths like ("dec")? -> ("l{j}", "kv", "k") or ("l{j}", ...)
        flat = [k for k in keys if isinstance(k, str)]
        lkey = next((k for k in flat if k.startswith("l") and k[1:].isdigit()),
                    None)
        if lkey is None:  # e.g. encdec "memory"
            cfg = plan.embed
            return pspec(cfg, ("batch",) + (None,) * (leaf.ndim - 1))
        j = int(lkey[1:])
        sub = dec_plan[j] if dec_plan else {}
        if "kv" in flat:
            cfg = sub.get("attn", R)
            if paged:
                if leaf.ndim == 4:
                    # int8 pool scales: (units, num_blocks, block_size, KH)
                    return pspec(cfg, (None, None, None, "heads"))
                # (units, num_blocks, block_size, KH, hd)
                return pspec(cfg, (None, None, None, "heads", None))
            # (units, B, S, KH, hd)
            return pspec(cfg, (None, "batch", "seq", "heads", None))
        if "ssm_state" in flat:
            cfg = sub.get("ssm", R)
            dims = {"conv": (None, "batch", None, "d_model"),
                    "ssm": (None, "batch", "d_model", None)}
            return pspec(cfg, dims.get(flat[-1],
                                       (None, "batch") + (None,) * (leaf.ndim - 2)))
        if "tmix_state" in flat or "cmix_state" in flat:
            cfg = sub.get("tmix", R)
            if flat[-1] == "shift":
                return pspec(cfg, (None, "batch", "d_model"))
            return pspec(cfg, (None, "batch") + (None,) * (leaf.ndim - 2))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


class LeafSharding(NamedSharding):
    """A NamedSharding that keeps the shape of the leaf it lays out, so
    that a layout derived from it (the AdamW moments',
    :func:`adamw_shardings`) can pick dimensions by size."""

    def __init__(self, mesh: Mesh, spec: P, leaf_shape):
        super().__init__(mesh, spec)
        self.leaf_shape = tuple(leaf_shape)


def to_shardings(pspecs, mesh: Mesh, like=None):
    """PartitionSpec pytree -> NamedSharding pytree.

    Drops axes not present in ``mesh``; when ``like`` (a matching pytree of
    arrays / ShapeDtypeStructs) is given, also drops entries whose shard
    count exceeds the dim size (8 KV heads on a 16-way axis -> replicated),
    and returns :class:`LeafSharding` s, which keep their leaf's shape.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def clean(spec: P, leaf=None) -> NamedSharding:
        entries = []
        for i, e in enumerate(spec):
            if e is None:
                entries.append(None)
                continue
            axes = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                         if a in mesh.axis_names)
            if leaf is not None:
                # drop axes (left-first) until the dim divides evenly
                while axes:
                    deg = 1
                    for a in axes:
                        deg *= sizes[a]
                    if leaf.shape[i] % deg == 0:
                        break
                    axes = axes[1:]
            if not axes:
                entries.append(None)
                continue
            entries.append(axes if len(axes) > 1 else axes[0])
        if leaf is None:
            return NamedSharding(mesh, P(*entries))
        return LeafSharding(mesh, P(*entries), leaf.shape)

    if like is None:
        return jax.tree.map(clean, pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(clean, pspecs, like,
                        is_leaf=lambda x: isinstance(x, P))


def adamw_shardings(param_shardings, mesh: Mesh) -> dict:
    """The AdamW state's layout for parameters laid out as
    ``param_shardings`` (:class:`LeafSharding` s, as :func:`to_shardings`
    gives them with ``like``).

    ``m`` and ``v`` take their parameter's spec, split further (ZeRO-1)
    over the data-parallel axes the parameter is not stored over
    (:func:`repro.core.config.zero1_axes`, the rule the plan search's
    memory count takes) by :func:`split_free_dim`; a leaf with no such
    dimension keeps its parameter's layout.  ``step`` is replicated.
    Where no such axis is larger than 1 (one device) the moments take
    the parameters' shardings themselves.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def moment(sh: NamedSharding) -> NamedSharding:
        axes = tuple(a for a in zero1_axes(spec_axes(sh.spec), mesh.axis_names)
                     if sizes[a] > 1)
        if not axes:
            return sh
        if not isinstance(sh, LeafSharding):
            raise TypeError("adamw_shardings splits moments by their "
                            "leaves' shapes: pass the LeafShardings of "
                            "to_shardings(..., like=params)")
        split = split_free_dim(sh.spec, sh.leaf_shape, axes, sizes)
        return sh if split is None else NamedSharding(mesh, split)

    moments = jax.tree.map(moment, param_shardings)
    return {"m": moments, "v": moments, "step": NamedSharding(mesh, P())}
