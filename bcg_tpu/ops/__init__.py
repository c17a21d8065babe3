"""TPU kernels and collective ops: Pallas attention, ring attention.

This module holds only the ``impl`` markers the kernels' callers pass
around, so reading one never imports Pallas.
"""

from typing import NamedTuple, Optional

from jax.sharding import Mesh


class PallasTP(NamedTuple):
    """Attention ``impl`` marker: the Pallas kernels with every call
    wrapped in ``jax.shard_map`` over ``mesh``'s ``tp`` axis.  A Mosaic
    kernel has no SPMD partitioning rule, so under a mesh the plain
    ``"pallas"`` marker cannot compile; heads are independent, so each
    device runs the kernel on its own H/tp query heads and Hkv/tp kv
    heads.  The engine resolves this marker at boot; the transformer
    passes it through unread."""

    mesh: Mesh


class HybridImpl(NamedTuple):
    """``impl`` marker of a model with two kinds of layer: what its
    full-attention layers run (any marker above, or a plain name) and
    what its gated delta-rule layers run (an ``ops.gated_delta``
    marker).  Resolved by the engine at boot, like the rest."""

    attention: object
    delta: str


def is_pallas(impl) -> bool:
    return impl == "pallas" or isinstance(impl, PallasTP)


def impl_mesh(impl) -> Optional[Mesh]:
    return impl.mesh if isinstance(impl, PallasTP) else None
