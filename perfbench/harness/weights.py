"""Seeded weights, made by the benchmark and not by the program.

One jitted call builds the whole tree on the device, in the dtype it
is served in, from ``--seed``.  The worker entry hands the tree to the
program in place of its own ``init_params`` and the reference builds
the same tree from the same call, so neither takes anything the other
has made.  The tree's names and layouts are the program's checkpoint
format (stacked layers, ``x @ W`` orientation); that format is the
interface of the system under test, and the worker entry checks it
against the shapes of the program's own ``init_params`` before it
builds anything (``tree_differences``).
"""

from __future__ import annotations


def split_seed(seed: int):
    """``--seed`` may exceed 31 bits; its low 24 bits and the rest never do."""
    seed = int(seed)
    return seed & 0xFFFFFF, seed >> 24


def weight_specs(model: dict):
    """(path, shape, kind, scale) for every leaf; kind is "normal"
    (N(0, scale^2), served dtype), "norm" (1 + 0.1 N(0,1), served
    dtype) or "router" (N(0, scale^2), float32)."""
    d, n = model["hidden_size"], model["num_hidden_layers"]
    h, kv, hd = (model["num_attention_heads"],
                 model["num_key_value_heads"], model["head_dim"])
    f, v = model["intermediate_size"], model["vocab_size"]
    e = model.get("num_local_experts", 0)
    specs = [
        (("embed",), (v, d), "normal", d ** -0.5),
        (("layers", "attn_norm"), (n, d), "norm", 0.0),
        (("layers", "wq"), (n, d, h * hd), "normal", d ** -0.5),
        (("layers", "wk"), (n, d, kv * hd), "normal", d ** -0.5),
        (("layers", "wv"), (n, d, kv * hd), "normal", d ** -0.5),
        (("layers", "wo"), (n, h * hd, d), "normal", (h * hd) ** -0.5),
        (("layers", "mlp_norm"), (n, d), "norm", 0.0),
        (("final_norm",), (d,), "norm", 0.0),
    ]
    lead = (n, e) if e else (n,)
    if e:
        specs.append((("layers", "router"), (n, d, e), "router", d ** -0.5))
    specs += [
        (("layers", "w_gate"), lead + (d, f), "normal", d ** -0.5),
        (("layers", "w_up"), lead + (d, f), "normal", d ** -0.5),
        (("layers", "w_down"), lead + (f, d), "normal", f ** -0.5),
    ]
    return specs


def tree_differences(model: dict, dtype, theirs) -> list:
    """How the tree this file builds differs from ``theirs``, a tree of
    anything with ``shape`` and ``dtype`` (the program's ``init_params``
    under ``jax.eval_shape``): one line a leaf, none when they agree."""
    import jax
    import numpy as np

    mine = {
        "/".join(path): (tuple(shape), np.dtype(
            np.float32 if kind == "router" else dtype
        ))
        for path, shape, kind, _scale in weight_specs(model)
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


def make_weights(model: dict, seed: int, dtype):
    """The whole tree in one jitted call; ``seed`` is traced, so every
    seed runs the same compiled program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    specs = weight_specs(model)

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        tree = {"layers": {}}
        for index, (path, shape, kind, scale) in enumerate(specs):
            noise = jax.random.normal(
                jax.random.fold_in(key, index), shape, jnp.float32
            )
            if kind == "norm":
                leaf = (1.0 + 0.1 * noise).astype(dtype)
            elif kind == "router":
                leaf = noise * scale
            else:
                leaf = (noise * scale).astype(dtype)
            node = tree
            for name in path[:-1]:
                node = node[name]
            node[path[-1]] = leaf
        return tree

    lo, hi = split_seed(seed)
    return jax.jit(build)(np.uint32(lo), np.uint32(hi))
