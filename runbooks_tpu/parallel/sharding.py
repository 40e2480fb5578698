"""Logical-axis sharding: params and activations are annotated with *logical*
axis names; a rule table maps logical axes to mesh axes.

This indirection (the standard idiom from the JAX scaling playbook) is what
lets one model definition serve every parallelism layout: switch TP<->FSDP<->SP
by editing the rule table, not the model. Divisibility is checked per-array;
a logical axis whose mesh assignment does not divide the array dimension
degrades to replicated on that dimension instead of erroring, so small debug
models run under any mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalSpec = Tuple[Optional[str], ...]
MeshAssignment = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
DEFAULT_RULES: Dict[str, MeshAssignment] = {
    # Activations
    "batch": ("data", "fsdp"),
    "seq": "sequence",          # context parallelism shards the seq axis
    "kv_seq": "sequence",
    "act_embed": None,
    "act_heads": "tensor",
    "act_mlp": "tensor",
    # Parameters
    "layers": "stage",          # pipeline parallelism: stacked-layer leading
                                # dim shards over stages (dropped on meshes
                                # without a stage axis)
    "embed": "fsdp",            # ZeRO-3 shards the embed axis of every matrix
    "vocab": "tensor",
    "heads": "tensor",          # megatron: split attention over heads
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",            # megatron: split ffn over hidden
    "norm": None,
    "pos": None,
    # MoE
    "experts": "expert",        # expert parallelism: expert leading dim
    "act_experts": "expert",
}


def logical_to_spec(
    logical: LogicalSpec, rules: Optional[Dict[str, MeshAssignment]] = None
) -> P:
    rules = DEFAULT_RULES if rules is None else rules
    out = []
    used: set = set()
    for name in logical:
        if name is None:
            out.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        # A mesh axis may appear at most once in a PartitionSpec.
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def _divisible(dim: int, axes: MeshAssignment, mesh: Mesh) -> bool:
    if axes is None:
        return True
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return dim % size == 0


def spec_for_array(
    shape: Sequence[int],
    logical: LogicalSpec,
    mesh: Mesh,
    rules: Optional[Dict[str, MeshAssignment]] = None,
) -> P:
    """PartitionSpec for a concrete shape: drops mesh axes that are absent
    from the mesh (e.g. "stage"/"expert" on a plain DP/TP mesh) or that
    don't divide the dimension."""
    base = logical_to_spec(logical, rules)
    out = []
    for dim, axes in zip(shape, tuple(base) + (None,) * (len(shape) - len(base))):
        if axes is not None:  # drop mesh axes this mesh doesn't have
            present = tuple(a for a in
                            ((axes,) if isinstance(axes, str) else axes)
                            if a in mesh.shape)
            axes = (present[0] if len(present) == 1
                    else (present or None))
        if axes is not None and not _divisible(dim, axes, mesh):
            # Try dropping trailing axes of a tuple assignment before giving up.
            if isinstance(axes, tuple):
                while axes and not _divisible(dim, axes, mesh):
                    axes = axes[:-1]
                axes = axes if axes else None
                if isinstance(axes, tuple) and len(axes) == 1:
                    axes = axes[0]
            else:
                axes = None
        out.append(axes)
    return P(*out)


def tree_shardings(
    tree_shapes: Any,
    tree_logical: Any,
    mesh: Mesh,
    rules: Optional[Dict[str, MeshAssignment]] = None,
) -> Any:
    """Map a pytree of jax.ShapeDtypeStruct (or arrays) + matching pytree of
    LogicalSpec to a pytree of NamedSharding."""
    def one(shape_like, logical):
        return NamedSharding(
            mesh, spec_for_array(shape_like.shape, logical, mesh, rules)
        )
    return jax.tree.map(one, tree_shapes, tree_logical,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def with_logical_constraint(x: jax.Array, logical: LogicalSpec,
                            mesh: Optional[Mesh] = None,
                            rules: Optional[Dict[str, MeshAssignment]] = None):
    """Sharding constraint by logical axes; no-op outside a mesh context.

    Works under both ``with jax.set_mesh(mesh)`` (abstract mesh context,
    the modern idiom used by create_train_state) and an explicitly passed
    concrete mesh. Divisibility checks only need the mesh *shape*, which
    abstract and concrete meshes both carry.
    """
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = spec_for_array(x.shape, logical, mesh, rules)
    if isinstance(mesh, jax.sharding.AbstractMesh):
        # Inside a set_mesh context a bare PartitionSpec binds to the
        # context mesh.
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh():
    """The innermost ``jax.set_mesh`` context, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh
