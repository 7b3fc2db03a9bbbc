"""Weights from ``--seed``, made by the benchmark and handed to both sides.

One jitted call makes the whole canonical tree on the device, blocks stacked
on a leading axis, in the type the configuration serves or trains in. The
program gets it re-cut to its own parameter tree by the configuration's
``layout`` (data: canonical key -> [layer name, parameter name]); the plain
reference makes the same tree again from the same seed once the program's
state is freed. The program's own ``init()`` runs for its tree's shapes and
its state; every weight it made is replaced.

Matrices are Xavier-normal as in the zoo models; gains and biases get a small
random part (the zoo's are exactly 1 and 0), so that a dropped bias or gain
shows in the comparison.
"""

from __future__ import annotations

import math

BLOCK_KEYS = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
              "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")
TOP_KEYS = ("tok_emb", "pos_emb", "lnf_g", "lnf_b", "head_w", "head_b")


def shapes(model: dict) -> dict:
    h, f, v = model["hidden"], model["ffn_size"], model["vocab_size"]
    L, t = model["n_layers"], model["max_len"]
    return {
        "tok_emb": (v, h), "pos_emb": (t, h),
        "ln1_g": (L, h), "ln1_b": (L, h),
        "wq": (L, h, h), "wk": (L, h, h), "wv": (L, h, h), "wo": (L, h, h),
        "ln2_g": (L, h), "ln2_b": (L, h),
        "w1": (L, h, f), "b1": (L, f), "w2": (L, f, h), "b2": (L, h),
        "lnf_g": (h,), "lnf_b": (h,), "head_w": (h, v), "head_b": (v,),
    }


def make_weights(model: dict, seed: int, dtype: str):
    """The canonical tree for ``model`` from ``seed``, one jitted call."""
    import jax
    import jax.numpy as jnp

    shp = shapes(model)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "pos_emb":
                w = 0.02 * noise
            elif name.endswith("_g"):
                w = 1.0 + 0.02 * noise
            elif len(shape) == 1 or name in ("ln1_b", "ln2_b", "b1", "b2"):
                w = 0.02 * noise
            else:
                fan_in, fan_out = shape[-2], shape[-1]
                w = math.sqrt(2.0 / (fan_in + fan_out)) * noise
            out[name] = w.astype(dtype)
        return out

    # seeds run a little past 2**31: fold the high and low words in apart
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def layout_paths(layout: dict, n_layers: int):
    """``[(canonical key, block index or None, layer name, param name)]``
    for every leaf of the program's tree. ``layout`` maps the top keys to
    ``[layer, param]`` and carries the block keys under ``"block"``; names may
    use ``{i}`` (block index), ``{j}`` (``i + block_offset``), ``{n}``
    (``n_layers + block_offset``) and ``{m}`` (``n + 1``)."""
    off = int(layout.get("block_offset", 0))
    n = n_layers + off

    def fmt(s, i=0):
        return s.format(i=i, j=i + off, n=n, m=n + 1)

    out = []
    for key in TOP_KEYS:
        layer, param = layout[key]
        out.append((key, None, fmt(layer), param))
    for i in range(n_layers):
        for key in BLOCK_KEYS:
            layer, param = layout["block"][key]
            out.append((key, i, fmt(layer, i), param))
    return out


def program_tree(weights: dict, layout: dict, n_layers: int) -> dict:
    """The canonical tree re-cut to the program's ``{layer: {param: array}}``
    in one jitted call."""
    import jax

    paths = layout_paths(layout, n_layers)

    def cut(w):
        out: dict = {}
        for key, i, layer, param in paths:
            out.setdefault(layer, {})[param] = w[key] if i is None else w[key][i]
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


def canonical_names(tree: dict, layout: dict, n_layers: int) -> dict:
    """A program-shaped tree of per-leaf numbers -> ``{"wq.3": x, ...}``."""
    out = {}
    for key, i, layer, param in layout_paths(layout, n_layers):
        out[key if i is None else f"{key}.{i}"] = float(tree[layer][param])
    return out


def stacked_names(tree: dict) -> dict:
    """A canonical tree of per-leaf numbers (vectors over the blocks for the
    stacked keys) -> the same flat names."""
    out = {}
    for key, val in tree.items():
        if key in BLOCK_KEYS:
            for i, x in enumerate(val):
                out[f"{key}.{i}"] = float(x)
        else:
            out[key] = float(val)
    return out
