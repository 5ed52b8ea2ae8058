"""A configuration file, its family, its weights, and the program's view of
them.

The configuration is a JSON file with the keys of the model's published
``config.json`` (see ``bench/configs/``), a ``family`` key that names the
module under ``bench/families/`` that knows its layers, and, where the
deployment spans chips, a ``mesh`` in the program's axis names
(``{"data": 1, "model": 4}``).  A family module defines

    Dims(c)                  the sizes, under short names: at least ``d``,
                             ``vocab``, ``layers``, ``heads``, ``kv``,
                             ``hd``, ``window``, ``dtype`` and
                             ``flops_dims()`` (what ``bench/roofline/``
                             counts a token's work by)
    weight_shapes(m)         {weight name: shape}
    init(name, key, shape, m)  one weight, in the served type
    program_config(c, name)  the program's ``ModelConfig``
    program_params(m, w)     the same leaves under the program's tree
    embed, layer, head       the plain reference's pieces (``reference.py``)

so that a configuration whose layers no module knows yet comes with a new
module, and no file here changes.  The benchmark makes the weights itself,
from the configuration's ``weights_seed``, in one jitted call on the device
and in the type they are served in -- with a mesh, straight into the
shardings the program's rules give them, so that no chip ever holds the
whole model; the reference reads these arrays, never the program's.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax


def seed_key(seed: int):
    """A PRNG key for any whole number, beyond the 32 bits a key holds."""
    k = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(k, (seed // 2**32) % 2**32)


def shardings(family, m, mesh) -> Dict[str, object]:
    """Each weight's ``NamedSharding``: the program's rule
    (``repro.distributed.sharding.params_shardings``) for the leaf the
    weight becomes in the program's tree.  ``m``: the family's ``Dims``."""
    from repro.distributed import sharding as shd
    shapes = family.weight_shapes(m)
    abstract = {n: jax.ShapeDtypeStruct(s, m.dtype)
                for n, s in shapes.items()}
    placed = shd.params_shardings(mesh, family.program_params(m, abstract))
    names = family.program_params(m, {n: n for n in shapes})
    return dict(zip(jax.tree_util.tree_leaves(names),
                    jax.tree_util.tree_leaves(placed)))


def make_weights(family, m, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """Random weights from ``seed``, made in one jitted call: on the
    default device, or with ``mesh`` in their shardings.  Weight i of the
    sorted names is drawn from ``fold_in(key, i)``; the values do not
    depend on the mesh."""
    shapes = family.weight_shapes(m)

    def init(key):
        # drawn in the served type: no float32 copy of the model is ever
        # live, so this call does not set the process's peak
        return {name: family.init(name, jax.random.fold_in(key, i), shape, m)
                for i, (name, shape) in enumerate(sorted(shapes.items()))}

    placed = ({} if mesh is None
              else {"out_shardings": shardings(family, m, mesh)})
    return jax.jit(init, **placed)(seed_key(seed))


def make_mesh(shape: Optional[dict], devices):
    """The program's mesh of a configuration's ``mesh`` ({axis: size}),
    over ``devices`` (Auto axes, ``repro.launch.mesh``); None for a
    configuration without one, which serves on one device."""
    if not shape:
        return None
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(tuple(int(v) for v in shape.values()),
                           tuple(shape))
    if sorted(d.id for d in mesh.devices.flat) != \
            sorted(d.id for d in devices):
        raise RuntimeError(f"bench: the mesh {dict(shape)} spans devices "
                           f"{[d.id for d in mesh.devices.flat]}, not the "
                           f"cell's {[d.id for d in devices]}")
    return mesh
