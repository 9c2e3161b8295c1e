"""Seeded weights, made by the benchmark and not by the program.

One jitted call builds the whole tree on the device, in the dtype it
is served in, from ``--seed``.  The worker entry hands the tree to the
program in place of its own ``init_params`` and the reference builds
the same tree from the same call, so neither takes anything the other
has made.  WHICH tree is the configuration's family's to say
(``families/<family>/weight_specs.py``): its names and layouts are the
program's checkpoint format, the interface of the system under test,
and the worker entry checks them against the shapes of the program's
own ``init_params`` before it builds anything (``tree_differences``).

A spec is ``(path, shape, kind, scale, dtype)``: ``path`` a tuple of
names of any depth; ``kind`` "normal" (``scale`` x N(0, 1)) or
"around_one" (1 + ``scale`` x N(0, 1)); ``dtype`` "served" (the dtype
the program serves in) or "float32".  Leaf ``i`` of the list draws from
``fold_in(key, i)``, so a family that appends a leaf moves no other.
"""

from __future__ import annotations


def split_seed(seed: int):
    """``--seed`` may exceed 31 bits; its low 24 bits and the rest never do."""
    seed = int(seed)
    return seed & 0xFFFFFF, seed >> 24


def tree_differences(specs: list, dtype, theirs) -> list:
    """How the tree of ``specs`` differs from ``theirs``, a tree of
    anything with ``shape`` and ``dtype`` (the program's ``init_params``
    under ``jax.eval_shape``): one line a leaf, none when they agree."""
    import jax
    import numpy as np

    mine = {
        "/".join(path): (tuple(shape), np.dtype(
            np.float32 if leaf_dtype == "float32" else dtype
        ))
        for path, shape, _kind, _scale, leaf_dtype in specs
    }
    found = {
        "/".join(str(getattr(k, "key", k)) for k in path):
            (tuple(leaf.shape), np.dtype(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(theirs)[0]
    }
    out = [f"the program has no leaf {name}" for name in mine
           if name not in found]
    out += [f"the program's leaf {name} {found[name]} is unknown here"
            for name in found if name not in mine]
    out += [f"leaf {name} is {found[name]} in the program, {mine[name]} here"
            for name in mine if name in found and found[name] != mine[name]]
    return out


def make_weights(specs: list, seed: int, dtype):
    """The whole tree in one jitted call; ``seed`` is traced, so every
    seed runs the same compiled program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        tree = {}
        for index, (path, shape, kind, scale, leaf_dtype) in enumerate(specs):
            noise = jax.random.normal(
                jax.random.fold_in(key, index), shape, jnp.float32
            )
            if kind == "around_one":
                leaf = 1.0 + scale * noise
            elif kind == "normal":
                leaf = noise * scale
            else:
                raise ValueError(f"leaf {'/'.join(path)}: kind {kind!r}")
            if leaf_dtype == "served":
                leaf = leaf.astype(dtype)
            elif leaf_dtype != "float32":
                raise ValueError(f"leaf {'/'.join(path)}: dtype {leaf_dtype!r}")
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return tree

    lo, hi = split_seed(seed)
    return jax.jit(build)(np.uint32(lo), np.uint32(hi))
