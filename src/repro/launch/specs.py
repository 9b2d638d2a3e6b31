"""Sharding rules: parameter / optimizer-state / activation / cache
PartitionSpecs for the production meshes.

Strategy (see DESIGN.md): FSDP over "data" (every large weight's first core
dim), TP over "model" (heads / ff / vocab / experts), DP over
("pod","data") for the batch. Optimizer moments mirror the param specs, so
state is fully ZeRO-sharded. Dims that don't divide the mesh axis are left
unsharded (e.g. rwkv6's 40 heads vs the 16-way model axis falls back to
sharding head_dim).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import dp_axes


def _div(n: int, size: int) -> bool:
    return n % size == 0


def _axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def leaf_spec(path_names: list[str], shape: tuple, mesh) -> P:
    """Sharding rule for one parameter leaf."""
    sz = _axis_sizes(mesh)
    dm, dd = sz["model"], sz["data"]
    stacked = "groups" in path_names
    core = shape[1:] if stacked else shape
    name = path_names[-1]

    def build(core_spec: tuple) -> P:
        return P(*((None,) + core_spec if stacked else core_spec))

    if len(core) <= 1:
        # norms / biases / small vectors: shard if cleanly divisible by model
        if len(core) == 1 and core[0] >= 1024 and _div(core[0], dm):
            return build(("model",))
        return build((None,) * len(core))

    if name == "embed":  # (Vp, D): vocab over model only — keeping D
        # unsharded lets GSPMD lower the token gather as a local masked
        # gather + all-reduce instead of a full rematerialization.
        return build(("model" if _div(core[0], dm) else None, None))
    if name in ("w1", "w3") and len(core) == 3:  # MoE (E, D, Fe): EP on model
        return build(
            ("model" if _div(core[0], dm) else None,
             "data" if _div(core[1], dd) else None, None)
        )
    if name == "w2" and len(core) == 3:  # MoE (E, Fe, D)
        return build(
            ("model" if _div(core[0], dm) else None, None,
             "data" if _div(core[2], dd) else None)
        )
    # output projections (X, D): model x data (reduce dim sharded over model)
    if name in ("wo", "w2", "w_out", "cm_v", "lm_head") and len(core) == 2:
        if name == "lm_head":  # (D, Vp): data x model
            return build(
                ("data" if _div(core[0], dd) else None,
                 "model" if _div(core[1], dm) else None)
            )
        return build(
            ("model" if _div(core[0], dm) else None,
             "data" if _div(core[1], dd) else None)
        )
    if len(core) == 2:  # generic input projection (D, X): data x model
        return build(
            ("data" if _div(core[0], dd) else None,
             "model" if _div(core[1], dm) else None)
        )
    return build((None,) * len(core))


def param_specs(abstract_params, mesh):
    """PartitionSpec pytree matching the (abstract) param tree."""
    flat, treedef = jax.tree.flatten_with_path(abstract_params)
    specs = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        specs.append(leaf_spec(names, leaf.shape, mesh))
    return jax.tree.unflatten(treedef, specs)


def opt_specs(abstract_opt_state, pspecs):
    """Optimizer state mirrors params per moment tree ({'m','v',['err']})."""
    return {k: pspecs for k in abstract_opt_state}


def batch_specs(abstract_batch, mesh, multi_pod: bool):
    """Batch-dim data parallel where divisible; replicate otherwise."""
    dp = dp_axes(multi_pod)
    dp_size = 1
    sz = _axis_sizes(mesh)
    for a in dp:
        dp_size *= sz[a]

    def one(leaf):
        b = leaf.shape[0]
        lead = dp if _div(b, dp_size) else None
        return P(*((lead,) + (None,) * (leaf.ndim - 1)))

    return jax.tree.map(one, abstract_batch)


def cache_specs(abstract_cache, mesh, multi_pod: bool):
    """KV-cache / recurrent-state shardings: batch over dp; the kv-head dim
    (or head_dim / state width) over "model" when divisible."""
    dp = dp_axes(multi_pod)
    sz = _axis_sizes(mesh)
    dm = sz["model"]
    dp_size = 1
    for a in dp:
        dp_size *= sz[a]

    def one(path, leaf):
        if leaf.ndim == 0:  # pos scalar
            return P()
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        stacked = "groups" in names
        core = list(leaf.shape[1:] if stacked else leaf.shape)
        spec: list = [None] * len(core)
        b = core[0]
        if _div(b, dp_size):
            spec[0] = dp
        # context-parallel decode: prefer sharding the SEQUENCE dim (dim 1 of
        # (B, S, ...) KV / latent caches) over the model axis — attention
        # scores then stay local per shard and only tiny softmax-stat /
        # context partial-sums cross the ICI, instead of GSPMD all-gathering
        # the whole cache per layer (§Perf iteration 4).
        if len(core) >= 3 and _div(core[1], dm) and core[1] >= dm:
            spec[1] = "model"
        else:
            # fall back: widest trailing dim that divides the model axis
            for d in range(len(core) - 1, 0, -1):
                if _div(core[d], dm) and core[d] >= dm:
                    spec[d] = "model"
                    break
        if stacked:
            spec = [None] + spec
        return P(*spec)

    flat, treedef = jax.tree.flatten_with_path(abstract_cache)
    return jax.tree.unflatten(treedef, [one(p, l) for p, l in flat])


def drop_axis_specs(spec_tree, axis: str = "data"):
    """Remove one mesh axis from every PartitionSpec in a tree (e.g. turn
    FSDP+TP param specs into TP-only for serving / ZeRO-1 gathers)."""

    def drop_entry(e):
        if e == axis:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a != axis)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return e

    def one(spec):
        return P(*(drop_entry(e) for e in spec))

    return jax.tree.map(one, spec_tree, is_leaf=lambda x: isinstance(x, P))


def constrain_tree(tree, spec_tree, mesh):
    """with_sharding_constraint over a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree,
        spec_tree,
    )


def to_shardings(spec_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
