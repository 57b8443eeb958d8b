"""Partition rules: pytree path patterns → PartitionSpecs.

The declarative replacement for the reference's per-framework wrapper classes
(DDP/FSDP wrapping in ``train/torch/train_loop_utils.py:91-100``): a model
ships a list of ``(path_regex, spec)`` rules; applying them to a params
pytree yields NamedShardings for ``jax.jit`` in/out shardings. XLA then emits
the all-gathers/reduce-scatters that DDP/FSDP would do by hand.
"""

from __future__ import annotations

import math
import re
from typing import Any, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def axes_size(axes: Union[None, str, Tuple[str, ...]], mesh: Mesh) -> int:
    """Over how many devices one dimension's entry of a spec (None, an axis
    name or a tuple of them) splits that dimension on ``mesh``."""
    names = () if axes is None else (
        axes if isinstance(axes, tuple) else (axes,))
    return math.prod(mesh.shape.get(name, 1) for name in names)


def _divides(spec: P, shape: Sequence[int], mesh: Mesh) -> bool:
    """Whether every dimension of ``shape`` that ``spec`` shards splits
    evenly over the mesh axes it names."""
    return not any(dim % axes_size(axes, mesh)
                   for dim, axes in zip(shape, spec))


class ShardingRules:
    """Ordered (regex, spec) rules; first match wins. A rule's spec is a
    PartitionSpec or a list of them in order of preference: a leaf takes
    the first that splits its shape evenly over the mesh it is placed on
    (the last where none does), so a placement follows what the rules can
    see of the model and the mesh, the sizes, and takes no option."""

    def __init__(self, rules: Sequence[Tuple[str, Union[P, Sequence[P]]]],
                 default: P = P()):
        self._rules = [(re.compile(pat),
                        (spec,) if isinstance(spec, P) else tuple(spec))
                       for pat, spec in rules]
        self._default = default

    def spec_for(self, path_string: str, shape: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None) -> P:
        """The spec of the leaf at ``path_string``. With the leaf's ``shape``
        and the ``mesh``, a rule's alternatives are resolved against them;
        without, a rule answers with its first."""
        for pat, specs in self._rules:
            if pat.search(path_string):
                if shape is None or mesh is None:
                    return specs[0]
                return next((s for s in specs if _divides(s, shape, mesh)),
                            specs[-1])
        return self._default

    def tree_specs(self, tree: Any):
        """A pytree of PartitionSpecs matching ``tree``'s structure."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.spec_for(_path_str(path)), tree)

    def tree_shardings(self, tree: Any, mesh: Mesh):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(mesh, self.spec_for(
                _path_str(path), getattr(leaf, "shape", None), mesh)), tree)


def named_sharding(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def shard_pytree(tree: Any, mesh: Mesh, rules: ShardingRules):
    """Device-put a pytree according to the rules (used at init/restore)."""
    shardings = rules.tree_shardings(tree, mesh)
    return jax.tree.map(jax.device_put, tree, shardings)


# Spec fragments shared by transformer models. Conventions:
#   batch axis   -> ("dp", "fsdp")      [+ "sp" shards sequence]
#   param matrices -> ("fsdp" on one dim, "tp" on the other)
BATCH_AXES = ("dp", "fsdp")


def data_spec(extra_seq_axis: Optional[str] = None) -> P:
    """[batch, seq, ...] inputs: batch over data axes, seq over sp if used."""
    return P(BATCH_AXES, extra_seq_axis)
