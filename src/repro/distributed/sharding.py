"""Logical-axis -> mesh-axis rules and PartitionSpec resolution.

The model code annotates parameters and activations with *logical* axis
names; this module maps them to physical mesh axes for a given mesh.
The batch dims are data parallel (over ``pod`` and ``data`` where the mesh
has them), the ``model`` axis does tensor parallelism: heads where the
config's ``attn_sharding`` is ``"heads"``, the sequence otherwise, and
experts or the expert FFN dim as its ``moe_sharding`` says. Two options:

* ``fsdp``          — shard the ``embed`` parameter dim over the in-pod data
                      axis (FSDP). Off = pure DP replication.
* ``fsdp_over_pod`` — additionally shard parameters over the cross-pod axis
                      (cheap DCN traffic trade-off; off by default).

The residual stream's sequence dims (``act_seq``, ``act_seq_rnn``) are
never sharded.

Every resolved PartitionSpec is validated against the actual tensor shape:
a dim that does not divide evenly by its assigned mesh axes falls back to
replication for that dim (noted in ``notes`` where the caller passes a
list). This is what makes e.g. a batch of 1 lower cleanly.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig

AxisRule = Any   # str | tuple[str, ...] | None


def _mesh_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_rules(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True,
               fsdp_over_pod: bool = False) -> dict[str, AxisRule]:
    """Logical axis -> mesh axes for ``cfg`` on ``mesh``."""
    sizes = _mesh_sizes(mesh)
    model_size = sizes.get("model", 1)
    has_pod = "pod" in sizes

    data_axes = (("pod", "data") if has_pod else ("data",))
    if fsdp:
        fsdp_axis: AxisRule = (("pod", "data") if (fsdp_over_pod and has_pod)
                               else ("data",))
    else:
        fsdp_axis = None

    heads_tp = cfg.attn_sharding == "heads"
    kv_w_shardable = heads_tp and cfg.num_kv_heads % model_size == 0
    ep = cfg.moe_sharding == "expert"

    rules: dict[str, AxisRule] = {
        # data-parallel dims
        "batch": data_axes,
        "kv_batch": data_axes,
        "moe_groups": data_axes,
        # parameter dims
        "layers": None,
        "embed": fsdp_axis,
        "embed_out": None,
        "vocab": "model",
        "heads": "model" if heads_tp else None,
        "kv_heads_w": "model" if kv_w_shardable else None,
        "head_dim": None,
        "ffn": "model",
        "ffn_sharded_w": "model",
        "expert": None,                       # TP-in-expert: experts replicated
        "expert_sharded": "model" if ep else None,
        "moe_ffn": None if ep else "model",   # per-expert ffn weight dim
        "moe_ffn_act": None if ep else "model",
        "rnn_tp": "model",
        "rnn_blocks": "model",
        "xlstm_inner": "model",
        "xlstm_hd": None,
        "xlstm_hd_out": None,
        # activation dims
        "vocab_sharded": "model",
        "heads_sharded": "model" if heads_tp else None,
        "kv_heads_sharded": "model" if heads_tp else None,
        "seq_sharded": "model" if not heads_tp else None,
        "kv_seq_sharded": "model" if not heads_tp else None,
        "ffn_sharded": "model",
        "rnn_sharded": "model",
        "xlstm_inner_sharded": None,
        "xlstm_hd_sharded": None,
        "act_seq": None,
        "act_seq_rnn": None,
    }
    return rules


def _axes_to_names(rule: AxisRule) -> tuple[str, ...]:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


def resolve_spec(shape: Sequence[int], axes: Sequence[str | None],
                 rules: Mapping[str, AxisRule], sizes: Mapping[str, int],
                 notes: list[str] | None = None, name: str = "") -> P:
    """Resolve one tensor's logical axes to a PartitionSpec, dropping any
    assignment that does not divide the dim evenly."""
    parts: list[AxisRule] = []
    for dim, ax in zip(shape, axes):
        rule = rules.get(ax) if ax is not None else None
        names = _axes_to_names(rule)
        if names:
            prod = math.prod(sizes[n] for n in names)
            if dim % prod != 0:
                if notes is not None:
                    notes.append(
                        f"{name}: dim {dim} ∤ axes {names} (size {prod}); "
                        f"replicated instead")
                rule = None
        parts.append(rule if not isinstance(rule, tuple) else tuple(rule))
    return P(*parts)


def _is_axes_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, str) or e is None for e in x)


def tree_partition_specs(shapes_tree: Any, axes_tree: Any,
                         rules: Mapping[str, AxisRule], mesh: Mesh,
                         notes: list[str] | None = None) -> Any:
    """PartitionSpec tree from parallel (shapes, logical axes) trees."""
    sizes = _mesh_sizes(mesh)

    def leaf(shape_leaf, axes_leaf):
        shp = (shape_leaf.shape if hasattr(shape_leaf, "shape")
               else tuple(shape_leaf))
        return resolve_spec(shp, axes_leaf, rules, sizes, notes)

    return jax.tree.map(leaf, shapes_tree, axes_tree,
                        is_leaf=lambda x: _is_axes_leaf(x) or
                        hasattr(x, "shape"))


def tree_named_shardings(shapes_tree: Any, axes_tree: Any,
                         rules: Mapping[str, AxisRule], mesh: Mesh,
                         notes: list[str] | None = None) -> Any:
    specs = tree_partition_specs(shapes_tree, axes_tree, rules, mesh, notes)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
