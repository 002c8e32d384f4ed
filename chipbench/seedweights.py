"""Weights from the seed, owned by the benchmark.

Every tensor has a name and a draw id, and its values depend only on
(seed, id, layer) and, for a tensor drawn once per expert, the expert's
published index: ``tensor`` draws them in the dtype they are served in.
Which tensors exist, their shapes and which layer draws which is the
configuration's architecture's (``chipbench/arch/<reference>.py``).  The
program's parameter tree is filled from these tensors in one jitted call
(``program_params``) through the configuration's ``param_map`` (program
leaf path -> tensor); the plain reference draws the same tensors again,
layer by layer (``refmodel``), and so shares nothing the program made.

Draw ids never move, so a seed draws the same weights from one version to the
next: ``GLOBAL`` holds the tensors outside the stack (ids 0-2), and the
first architecture, ``dense_gqa``, gave its nine layer tensors ids 3-11.
An architecture that draws one of those names gives it the same id; a
name it adds takes ``new_id(name)``, at or above 2**31, which none of
them reaches.  ``ids`` refuses an architecture that breaks the rule or
whose new names meet on one id.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

import cellspec

GLOBAL = {"embed": 0, "lm_head": 1, "final_norm": 2}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def new_id(name: str) -> int:
    return 2 ** 31 + zlib.crc32(name.encode()) % 2 ** 31


def ids(arch) -> dict[str, int]:
    """Draw ids of every tensor an architecture draws, the global ones
    included, checked against the rule above."""
    kept = cellspec.arch("dense_gqa").IDS
    out = dict(GLOBAL)
    for n, i in arch.IDS.items():
        if n in GLOBAL or i != kept.get(n, new_id(n)):
            raise ValueError(f"tensor {n!r} has draw id {i}; a name that "
                             f"dense_gqa does not draw takes "
                             f"seedweights.new_id(name)")
        out[n] = i
    if len(set(out.values())) != len(out):
        raise ValueError(f"two tensors share a draw id: {out}")
    return out


def base_key(seed: int):
    """A key from any non-negative integer seed (more than 32 bits too)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def tensor(key, name: str, tid: int, layer, shape, dtype, index=None):
    """The values of one tensor.  Norm gains are float32 around 1;
    matrices are N(0, 1/fan_in) (the embedding N(0, 1)) in ``dtype``.
    ``layer`` is -1 for the tensors outside the stack; it and ``index``
    (an expert's published index, folded in after the layer only when
    given) may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(key, tid), layer + 1)
    if index is not None:
        k = jax.random.fold_in(k, index)
    x = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + 0.1 * x
    scale = 1.0 if name == "embed" else 1.0 / math.sqrt(shape[0])
    return (x * scale).astype(dtype)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def _entry(e, depth: int):
    """(tensor, layers, experts) of one ``param_map`` entry.  A string
    names a global tensor, or with ``layers.`` one stacked over every
    layer; an object ``{"name", "layers": [start, stop, step],
    "experts": [start, stop, step]}`` says which layers a stacked leaf
    holds and, for an expert tensor, which published expert indices (each
    key optional)."""
    if isinstance(e, str):
        if e.startswith("layers."):
            return e.split(".", 1)[1], range(depth), None
        return e, None, None
    rng = lambda k: range(*e[k]) if k in e else None
    return e["name"], rng("layers"), rng("experts")


def program_params(seed: int, c: dict, abstract_tree, shardings=None):
    """The program's parameter tree, every leaf drawn by :func:`tensor`
    through ``c["param_map"]``; one jitted call, on the device."""
    arch = cellspec.arch(c.get("reference"))
    shapes, tids = arch.shapes(c), ids(arch)
    dtype = DTYPES[c["torch_dtype"]]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    pmap = c["param_map"]
    plan = []
    for kp, leaf in leaves:
        p = _path(kp)
        if p not in pmap:
            raise KeyError(f"program parameter {p!r} has no entry in the "
                           f"configuration's param_map")
        plan.append((_entry(pmap[p], c["num_hidden_layers"]),
                     tuple(leaf.shape), leaf.dtype))

    def build(key):
        out = []
        for (n, layers, experts), shape, ldt in plan:
            def draw(layer, n=n, experts=experts):
                one = lambda e=None: tensor(key, n, tids[n], layer, shapes[n],
                                            dtype, e)
                if experts is None:
                    return one()
                return jax.lax.map(one, jnp.arange(experts.start,
                                                   experts.stop, experts.step))
            x = draw(-1) if layers is None else jax.lax.map(
                draw, jnp.arange(layers.start, layers.stop, layers.step))
            out.append(x.reshape(shape).astype(ldt))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(base_key(seed))


def param_bytes(c: dict) -> int:
    """Bytes of every tensor the configuration's layers and head draw."""
    arch = cellspec.arch(c.get("reference"))
    shapes, n_exp = arch.shapes(c), arch.experts(c)
    item = np.dtype(jnp.dtype(DTYPES[c["torch_dtype"]])).itemsize
    size = lambda n: (int(np.prod(shapes[n])) * n_exp.get(n, 1)
                      * (4 if n.endswith("norm") else item))
    return sum(size(n) for n in GLOBAL) + sum(
        size(n) for i in range(c["num_hidden_layers"])
        for n in arch.tensors(c, arch.kind(c, i)))
