"""Varying-mesh-axes (vma) helpers for shard_map code.

JAX >= 0.9 type-checks collectives inside ``shard_map(check_vma=True)``:
``psum`` over an axis requires its input to be *varying* over that axis.
Values built from constants (masks of ones, token-count weights) are
*invariant*, and psumming an invariant value over an axis is exactly the
"every rank contributes the same thing" case — legal mathematically, but it
needs an explicit ``pvary`` cast first. These helpers insert the cast only
for the axes that actually need it, so the same code runs under
``check_vma=True`` (the default we use — it is also what makes autodiff
insert the correct backward collectives for replicated parameters) and in
plain single-rank traces.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
from jax import lax

Axes = Union[str, Sequence[str]]


def _axis_tuple(axis_name: Axes) -> tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def cast_varying(x, axes: tuple[str, ...]):
    """invariant -> varying cast over ``axes``."""
    return lax.pcast(x, axes, to="varying")


def ensure_varying(x, axis_name: Axes):
    """Cast ``x`` to be varying over every axis in ``axis_name`` it is not
    already varying over (no-op outside vma-checked contexts)."""
    axes = _axis_tuple(axis_name)
    try:
        vma = jax.typeof(x).vma
    except Exception:
        return x
    missing = tuple(a for a in axes if a not in vma)
    if not missing:
        return x
    return cast_varying(x, missing)


def psum_all(x, axis_name: Axes):
    """psum that tolerates invariant inputs (each rank contributing an
    identical value): pvary-then-psum, multiplying by the group size for
    the invariant axes — which is precisely the intended sum."""
    return lax.psum(jax.tree.map(
        lambda leaf: ensure_varying(leaf, axis_name), x), axis_name)
