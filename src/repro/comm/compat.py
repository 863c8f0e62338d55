"""The one module through which the repo reaches jax's sharding API.

Everything in-repo imports ``shard_map`` / ``use_mesh`` / ``make_mesh`` /
``psum`` from here instead of touching ``jax.*`` directly, so that:

  * a future jax API change is absorbed in one place;
  * every collective posted outside the 1.5D layer is greppable here and
    can be announced to the ``repro.obs.commwatch`` watcher;
  * the AST checks (CA1xx) can flag raw ``jax.shard_map`` use elsewhere.
"""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["shard_map", "use_mesh", "make_mesh", "axis_size",
           "get_abstract_mesh", "psum", "set_collective_watcher"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


#: collective-wrapper watcher (``repro.obs.commwatch``): when set, every
#: collective POSTED through a compat wrapper is announced with its
#: (prim, axis, operand).  Posting happens at trace time — a cached
#: compiled program re-executes without re-posting — so the watcher
#: counts program construction, while runtime execution counts come from
#: the jaxpr walk on the dispatch hook.
_COLLECTIVE_WATCHER = None


def set_collective_watcher(watcher):
    """Install ``watcher`` (or None); returns the previous watcher."""
    global _COLLECTIVE_WATCHER
    prev = _COLLECTIVE_WATCHER
    _COLLECTIVE_WATCHER = watcher
    return prev


def psum(x, axis_name):
    """``lax.psum`` re-export: the blessed spelling outside the collective
    layer (``comm/``, ``core/distributed.py``), so every cross-device
    reduction in model/data code is greppable here."""
    if _COLLECTIVE_WATCHER is not None:
        _COLLECTIVE_WATCHER.on_collective("psum", axis_name, x)
    return lax.psum(x, axis_name)


def axis_size(name):
    """Size of the named mesh axis (static at trace time)."""
    return lax.axis_size(name)


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def get_abstract_mesh():
    """The ambient mesh set by :func:`use_mesh` (``.empty`` when none)."""
    return jax.sharding.get_abstract_mesh()


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types.

    With an explicit ``devices`` sequence the mesh is built directly from
    ``jax.sharding.Mesh`` in the GIVEN order — ``jax.make_mesh`` may
    permute explicit devices for locality, which would silently scramble
    the 1.5D ring's flat-rank numbering (``comm.grid``)."""
    if devices is not None:
        import numpy as np
        devs = np.asarray(devices).reshape(tuple(axis_shapes))
        return jax.sharding.Mesh(devs, tuple(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
