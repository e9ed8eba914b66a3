"""Orbax param checkpointing, sharding-aware.

``save_params`` writes any param pytree; ``restore_params`` restores it,
optionally placing leaves directly onto mesh shardings (so a 70B restore
never materializes unsharded copies on one host).
"""

from __future__ import annotations

import os

import jax
import orbax.checkpoint as ocp


def save_params(path: str | os.PathLike, params) -> None:
    """Write ``params`` to ``path`` (a directory; created if needed). Only
    the ``params`` subtree is replaced — never the whole target directory."""
    import shutil

    root = os.path.abspath(path)
    os.makedirs(root, exist_ok=True)
    target = os.path.join(root, "params")
    if os.path.exists(target):
        shutil.rmtree(target)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(target, params)
        ckptr.wait_until_finished()


def restore_params(path: str | os.PathLike, shardings=None, params_like=None):
    """Restore the pytree written by ``save_params``.

    ``shardings``: optional pytree of ``NamedSharding`` matching the params
    structure — leaves stream from disk straight onto their mesh placement.
    ``params_like``: optional abstract pytree (e.g. from ``jax.eval_shape``)
    declaring dtypes/shapes; required if shardings is given without concrete
    reference arrays.
    """
    path = os.path.join(os.path.abspath(path), "params")
    with ocp.StandardCheckpointer() as ckptr:
        if shardings is None:
            # onto THIS process's default device, named explicitly: a bare
            # restore() rebuilds the sharding recorded at save time, and a
            # checkpoint saved on the CPU ("TFRT_CPU_0") then fails to
            # restore on a TPU host, whose local devices hold no such name
            here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            is_array = lambda m: hasattr(m, "shape") and hasattr(m, "dtype")
            abstract = jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=here),
                ckptr.metadata(path).item_metadata.tree, is_leaf=is_array)
            return ckptr.restore(path, abstract)
        if params_like is None:
            raise ValueError("restore with shardings requires params_like (abstract pytree)")
        abstract = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params_like, shardings,
        )
        return ckptr.restore(path, abstract)
