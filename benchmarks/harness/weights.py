"""Weights from ``--seed``, made by the benchmark and handed to both sides.

One jitted call makes the whole canonical tree on the device, in the type
the configuration serves or trains in. WHICH tree is the family's
(``families/<family>.py``): ``leaves(dims)`` gives every key with its group
and the shape of one member, ``groups(dims)`` how many members each group
stacks on a leading axis (a model with two kinds of block has two groups),
``init_scale(key, shape)`` the mean and the standard deviation of the key's
normal draw. The program gets the tree re-cut to its own parameter tree by
the configuration's ``layout`` (data: canonical key -> path in the program's
tree); the plain reference makes the same tree again from the same seed once
the program's state is freed. The program's own ``init()`` runs for its
tree's shapes and its state; every weight it made is replaced.

The order of the draws is part of the contract: key number ``i`` of the
family's keys, SORTED, draws from ``fold_in(seed's key, i)``. So the same
seed gives a family the same weights for as long as its set of keys stands;
a key added to a family that a cell runs moves the draws of the keys sorted
after it, and is a new family.
"""

from __future__ import annotations


def make_weights(family, dims: dict, seed: int, dtype: str):
    """The family's canonical tree for ``dims`` from ``seed``: a flat dict,
    a group's keys stacked on a leading axis. One jitted call."""
    import jax
    import jax.numpy as jnp

    counts = family.groups(dims)
    shp = {key: (shape if group is None else (counts[group],) + tuple(shape),
                 family.init_scale(key, tuple(shape)))
           for key, (group, shape) in family.leaves(dims).items()}

    def make(key):
        out = {}
        for i, (name, (shape, (mean, std))) in enumerate(sorted(shp.items())):
            noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
            w = std * noise if mean == 0.0 else mean + std * noise
            out[name] = w.astype(dtype)
        return out

    # seeds run a little past 2**31: fold the high and low words in apart
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def layout_paths(family, dims: dict, layout: dict):
    """``[(canonical key, member index or None, path in the program's tree)]``
    for every leaf of the program's tree. ``layout`` maps an ungrouped key
    to its path ``[layer, ..., param]`` and carries a group's keys under the
    group's name. A path's names are format strings over ``{i}`` (the
    member's index in its group), every size in ``dims``, and the sums the
    layout defines under ``"index"``: ``{"j": ["i", 2]}`` makes ``{j}`` mean
    ``i + 2``."""
    counts = family.groups(dims)

    def path(entry, i=0):
        env = dict(dims, i=i)
        for var, terms in layout.get("index", {}).items():
            env[var] = sum(env[t] if isinstance(t, str) else t for t in terms)
        return tuple(s.format(**env) for s in entry)

    out = []
    for key, (group, _) in sorted(family.leaves(dims).items()):
        if group is None:
            out.append((key, None, path(layout[key])))
        else:
            out.extend((key, i, path(layout[group][key], i))
                       for i in range(counts[group]))
    return out


def program_tree(family, dims: dict, weights: dict, layout: dict) -> dict:
    """The canonical tree re-cut to the program's nested dict in one jitted
    call."""
    import jax

    paths = layout_paths(family, dims, layout)

    def cut(w):
        out: dict = {}
        for key, i, path in paths:
            node = out
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = w[key] if i is None else w[key][i]
        return out

    return jax.jit(cut)(weights)


def install(model, tree: dict) -> None:
    """Hand the program its weights in place of those its own ``init()``
    made, after holding the layout to that tree: the same layers, parameters,
    shapes and types."""
    import jax

    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                  model.init().params)
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)
    if want != got:
        raise ValueError(
            "the configuration's layout does not give the program's "
            f"parameter tree:\n  program: {want}\n  layout:  {got}")
    model.params = tree


def canonical_names(family, dims: dict, tree: dict, layout: dict) -> dict:
    """A program-shaped tree of per-leaf numbers -> ``{"wq.3": x, ...}``."""
    out = {}
    for key, i, path in layout_paths(family, dims, layout):
        node = tree
        for name in path:
            node = node[name]
        out[key if i is None else f"{key}.{i}"] = float(node)
    return out


def stacked_keys(family, dims: dict) -> frozenset:
    """The canonical keys that stack a group's members on a leading axis."""
    return frozenset(key for key, (group, _) in family.leaves(dims).items()
                     if group is not None)


def stacked_names(tree: dict, stacked: frozenset) -> dict:
    """A canonical tree of per-leaf numbers (vectors over the members for
    the stacked keys) -> the same flat names."""
    out = {}
    for key, val in tree.items():
        if key in stacked:
            for i, x in enumerate(val):
                out[f"{key}.{i}"] = float(x)
        else:
            out[key] = float(val)
    return out
