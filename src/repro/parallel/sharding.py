"""Logical-axis sharding rules -> PartitionSpecs.

Every parameter/activation is annotated with logical dimension names; a rule
table maps them to mesh axes. The production mesh is ('data','model') per pod
plus a 'pod' axis across pods; 'pod' composes with 'data' for batch/FSDP.

Default placement:
  batch   -> ('pod','data')      data parallel across pods
  fsdp    -> ('pod','data')      ZeRO-3 parameter/optimizer sharding; XLA
                                  all-gathers weights per layer inside scan
  vocab/heads/kv_heads/mlp/experts -> 'model'   tensor/expert parallelism
  seq_shard -> 'model'           sequence sharding inside MoE shuffle blocks
                                  and long-context KV caches
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ShardingRules:
    rules: dict

    def spec(self, axes: tuple) -> P:
        """axes: tuple of logical names (or None) per tensor dim."""
        out = []
        for a in axes:
            m = self.rules.get(a) if a is not None else None
            out.append(m)
        return P(*out)

    def with_overrides(self, **kw) -> "ShardingRules":
        r = dict(self.rules)
        r.update(kw)
        return ShardingRules(rules=r)


DEFAULT_RULES = ShardingRules(
    rules={
        "batch": ("pod", "data"),
        "fsdp": ("pod", "data"),
        "seq": None,
        "seq_shard": "model",
        "embed": None,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",
        "expert_mlp": None,
        "layers": None,
        "state": None,
        "dconv": None,
    }
)

SINGLE_POD_RULES = DEFAULT_RULES.with_overrides(batch="data", fsdp="data")


def rules_for_mesh(mesh: Mesh, cfg=None) -> ShardingRules:
    """Rules restricted to the axes this mesh actually has (test meshes may
    lack 'model' or 'pod'; those logical axes fall back to replication).

    cfg.shard_strategy == "dp_sp": weights replicated (no TP), the 'model'
    axis is spent on sequence/context parallelism instead — the right trade
    for small-d_model archs whose per-layer all-reduces dominate (§Perf).
    """
    base = DEFAULT_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES
    names = set(mesh.axis_names)
    rules = dict(base.rules)
    strategy = getattr(cfg, "shard_strategy", "tp") if cfg is not None else "tp"
    if strategy == "dp_sp":
        for ax in ("heads", "kv_heads", "mlp", "vocab", "experts", "expert_mlp"):
            rules[ax] = None
        rules["seq"] = "model"
    elif strategy == "ep_only":
        # replicate the (small) attention/vocab weights, kill their per-layer
        # all-reduces; keep experts sharded — decode-collective trade (§Perf)
        for ax in ("heads", "kv_heads", "mlp", "vocab"):
            rules[ax] = None

    def keep(v):
        if isinstance(v, tuple):
            kept = tuple(a for a in v if a in names)
            return kept or None
        return v if v in names else None

    return ShardingRules(rules={k: keep(v) for k, v in rules.items()})


def logical_to_spec(axes_tree, rules: ShardingRules):
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def shard_params_specs(axes_tree, mesh: Mesh, rules: ShardingRules | None = None):
    """NamedShardings for a params tree from its logical axes tree."""
    rules = rules or rules_for_mesh(mesh)
    specs = logical_to_spec(axes_tree, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
