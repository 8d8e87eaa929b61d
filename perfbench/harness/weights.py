"""Weights from ``--seed``, made by the benchmark and handed to both sides.

A family module (``perfbench/families/<family>.py``) states the leaves:
``top_spec(cfg)`` and ``layer_spec(cfg)`` map a leaf's path (the flax
path of the program's parameter tree, '/'-joined) to ``(shape, kind)``,
``kind`` one of ``normal`` (N(0, 0.02)) and ``scale`` (1 + N(0, 0.02)).
Every leaf draws from its own key, folded from the seed, a CRC of its
path and (for a layer's leaf) the layer index, so one layer can be made
alone — the plain reference walks a 7.5 GB model layer by layer without
ever holding it — and is bit-identical to that layer's slice of the
stacked tree the program is given.
"""

import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A key for any whole-number seed (the driver's are above 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2**31))
    return jax.random.fold_in(key, seed // (2**31))


def _leaf(key, path, shape, kind, dtype):
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2**31))
    x = STD * jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        x = 1.0 + x
    elif kind != "normal":
        raise ValueError(f"unknown leaf kind {kind!r} at {path}")
    return x.astype(dtype)


def make_group(key, spec, dtype):
    """``{path: array}`` for one spec (the top leaves, or one layer)."""
    return {
        path: _leaf(key, path, shape, kind, dtype)
        for path, (shape, kind) in sorted(spec.items())
    }


def make_top(key, spec, dtype):
    return make_group(key, spec, dtype)


def make_layer(key, layer, spec, dtype):
    return make_group(
        jax.random.fold_in(key, 1_000_003 + layer), spec, dtype
    )


def make_stacked(key, num_layers, spec, dtype):
    """Every layer's leaves stacked on a leading ``[L]`` axis."""
    return jax.vmap(lambda l: make_layer(key, l, spec, dtype))(
        jnp.arange(num_layers)
    )


def nest(flat, prefix=()):
    """``{'a/b': x}`` -> ``{'a': {'b': x}}`` under an optional prefix."""
    out = {}
    for path, x in flat.items():
        node = out
        parts = tuple(prefix) + tuple(path.split("/"))
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def flatten(tree, prefix=""):
    """The inverse of :func:`nest`: nested dicts to ``{'a/b': x}``."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def part_norms(flat, split=None):
    """Euclidean norm of every leaf of ``{path: array}``; a leaf that
    fuses several of the published description's tensors (``split`` maps
    its path to ``(axis, part names)``) gives one norm per part, named
    ``path[part]``, so that a part whose gradient is nought (a key's
    bias under softmax) can be told from its neighbours."""
    out = {}
    for path, x in flat.items():
        sq = jnp.square(x.astype(jnp.float32))
        if split and path in split:
            axis, names = split[path]
            other = tuple(i for i in range(x.ndim) if i != axis)
            parts = jnp.sqrt(jnp.sum(sq, axis=other))
            for i, n in enumerate(names):
                out[f"{path}[{n}]"] = parts[i]
        else:
            out[path] = jnp.sqrt(jnp.sum(sq))
    return out


def merge(a, b):
    """Nested dicts merged; ``b``'s leaves win."""
    out = dict(a)
    for k, v in b.items():
        out[k] = merge(out[k], v) if k in out and isinstance(v, dict) else v
    return out


def program_params(key, fam, cfg):
    """The seed's weights as the program's parameter tree: the family's
    top leaves, and every layer's leaves stacked under ``fam.STACK``.
    Call it under ``jax.jit``: one call, on the device, in the type the
    configuration states."""
    dtype = fam.param_dtype(cfg)
    top = nest(make_top(key, fam.top_spec(cfg), dtype))
    stacked = nest(
        make_stacked(key, fam.num_layers(cfg), fam.layer_spec(cfg), dtype),
        fam.STACK,
    )
    return merge(top, stacked)
