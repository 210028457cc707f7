"""Sharding rules: logical param axes → mesh PartitionSpecs.

Strategy (DESIGN.md §5):

* **TP/EP** over the ``model`` axis for vocab / q-heads / ffn / experts /
  ssm-inner dims — applied only when the dim is divisible by the axis size,
  otherwise the dim stays replicated (e.g. kv=2 GQA heads, 56-head attention)
  and the compute falls back to sequence/context parallelism via the
  activation constraints below.
* **FSDP** over the ``data`` axis on the ``embed`` (d_model) dim of every
  weight when enabled (params + optimizer state; per-layer all-gathers are
  the visible FSDP cost in the collective roofline term).
* **SP**: residual activations constrained to P(dp, "model", None) between
  layers for large models — bounds remat-saved bytes and gives context
  parallelism to archs whose head counts don't divide the TP axis.
* Caches: attention KV caches shard batch over dp and *sequence* over
  ``model`` (distributed flash-decoding layout); recurrent states shard
  their inner dim over ``model``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.lm import ParamDef, param_defs, _strip_kind, _is_def

#: logical axis → candidate mesh axis for tensor/expert parallelism
TP_RULES: Dict[str, str] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "expert": "model",
    "inner": "model",
}
FSDP_AXES = ("embed", "embed2")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Per-(arch × mesh) distribution plan."""
    mesh: Mesh
    dp_axes: Tuple[str, ...]            # ("data",) or ("pod", "data")
    fsdp: bool = False                  # shard params over data on embed dim
    sp: bool = False                    # sequence-parallel residuals
    remat: bool = True
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))

    @property
    def tp_size(self) -> int:
        return self.mesh.shape["model"]


def make_plan(cfg: ArchConfig, mesh: Mesh, kind: str = "train") -> MeshPlan:
    """Default plan: SP for every training run (bounds the remat-saved
    residuals AND the attention-score working set); FSDP for ≥5B params."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    big = cfg.param_count() >= 5e9
    return MeshPlan(
        mesh=mesh, dp_axes=dp_axes,
        fsdp=big,
        sp=kind == "train" and mesh.shape["model"] > 1,
        remat=kind == "train",
    )


def _spec_for(defn: ParamDef, plan: MeshPlan) -> P:
    spec: list = [None] * len(defn.shape)
    used = set()
    # 1) TP/EP on the first divisible candidate axis
    for i, (dim, ax) in enumerate(zip(defn.shape, defn.axes)):
        rule = TP_RULES.get(ax)
        if rule and rule not in used and dim % plan.mesh.shape[rule] == 0:
            spec[i] = rule
            used.add(rule)
            break
    # 2) FSDP over data on the embed dim
    if plan.fsdp and "data" not in used:
        for i, (dim, ax) in enumerate(zip(defn.shape, defn.axes)):
            if spec[i] is None and ax in FSDP_AXES \
                    and dim % plan.mesh.shape["data"] == 0:
                spec[i] = "data"
                used.add("data")
                break
    return P(*spec)


def make_param_shardings(cfg: ArchConfig, plan: MeshPlan):
    """Pytree of NamedShardings matching ``lm.abstract_params`` structure."""
    defs = _strip_kind(param_defs(cfg))
    return jax.tree.map(
        lambda d: NamedSharding(plan.mesh, _spec_for(d, plan)),
        defs, is_leaf=_is_def)


def opt_state_shardings(param_shardings):
    """Adam m/v mirror the param shardings."""
    return jax.tree.map(lambda s: s, param_shardings)


def shard_batch_spec(plan: MeshPlan, batch: int, rank: int = 2) -> P:
    """Spec for (B, S) token batches — batch over dp when divisible."""
    dp = plan.dp_axes if batch % plan.dp_size == 0 else ()
    lead = dp if dp else None
    return P(lead, *([None] * (rank - 1)))


def constrain_activations(x, plan: MeshPlan, batch_divisible: bool = True):
    """SP residual-stream constraint: P(dp, "model", None)."""
    if not plan.sp:
        return x
    dp = plan.dp_axes if batch_divisible else None
    seq_ax = "model" if x.shape[1] % plan.tp_size == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, P(dp, seq_ax, None)))


# ---------------------------------------------------------------------------
# Cache sharding (serving)
# ---------------------------------------------------------------------------

def _cache_leaf_spec(path_leaf_shape: Tuple[int, ...], plan: MeshPlan,
                     kind: str) -> P:
    m = plan.mesh.shape["model"]
    dp = plan.dp_axes
    B = path_leaf_shape[0]
    b_ax = dp if B % plan.dp_size == 0 else None
    if kind == "attn_kv":                       # (B, T, KV, Dh): seq → model
        t_ax = "model" if path_leaf_shape[1] % m == 0 else None
        return P(b_ax, t_ax, None, None)
    # recurrent states: shard the largest trailing dim divisible by model
    spec = [b_ax] + [None] * (len(path_leaf_shape) - 1)
    order = sorted(range(1, len(path_leaf_shape)),
                   key=lambda i: -path_leaf_shape[i])
    for i in order:
        if path_leaf_shape[i] % m == 0 and path_leaf_shape[i] >= m:
            spec[i] = "model"
            break
    return P(*spec)


def shard_cache(cfg: ArchConfig, plan: MeshPlan, cache_abstract):
    """NamedShardings for an ``lm.init_cache`` pytree (ShapeDtypeStructs)."""
    def leaf_spec(leaf):
        shape = leaf.shape
        kind = "attn_kv" if len(shape) == 4 and shape[2] == cfg.n_kv_heads \
            and shape[3] == cfg.dh else "state"
        return NamedSharding(plan.mesh, _cache_leaf_spec(shape, plan, kind))
    return jax.tree.map(leaf_spec, cache_abstract)
