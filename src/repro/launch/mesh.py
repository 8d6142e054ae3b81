"""The mesh constructor and the production meshes built with it.

Functions (not module-level constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor.  Every axis is ``Auto``: the step
    builders place arrays with ``NamedSharding`` and let jit propagate
    the rest, which ``jax.make_mesh``'s default ``Explicit`` axes refuse
    (an embedding gather then raises ``ShardingTypeError``)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, pp: int = 1,
                         cp: int = 1):
    """Production mesh; ``pp``/``cp`` > 1 carve ``pipe``/``seq`` axes out
    of the data axis (same device count), following the
    ``repro.dist.sharding`` axis contract — so the dry-run lowers the same
    PP/CP step the runtime executes on carved section meshes."""
    if pp == 1 and cp == 1:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return make_mesh(shape, axes)
    assert 16 % (pp * cp) == 0, (pp, cp)
    shape = (16 // (pp * cp), pp, cp, 16)
    axes = ("data", "pipe", "seq", "model")
    if multi_pod:
        shape = (2,) + shape
        axes = ("pod",) + axes
    return make_mesh(shape, axes)


def mesh_from_env(var: str = "REPRO_DRYRUN_MESH"):
    """Mesh from a comma-separated dims env var, or None when unset.

    2/3 dims map to the classic ``(pod,)data,model`` axes; 4/5 dims to
    the full section-mesh contract ``(pod,)data,pipe,seq,model`` (the
    PP/CP dry-run cells).  Single source of the env↔axis-name mapping
    for every dry-run CLI."""
    import os
    spec = os.environ.get(var)
    if not spec:
        return None
    dims = tuple(int(x) for x in spec.split(","))
    names = (("pod", "data", "pipe", "seq", "model") if len(dims) > 3
             else ("pod", "data", "model"))
    return make_mesh(dims, names[-len(dims):])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (virtual) devices this host exposes."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))
