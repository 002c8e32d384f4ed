"""Parameter declaration system.

A model module declares its parameters ONCE as a pytree of :class:`ParamDecl`
(shape + logical axis names + initializer).  From that single source of truth
we derive:

  * real initialized parameters           (``init_params``)
  * abstract ``ShapeDtypeStruct`` stand-ins for the multi-pod dry-run
    (``abstract_params`` — no device allocation, ever)
  * logical partition specs → ``jax.sharding.PartitionSpec`` under a given
    set of sharding rules (``logical_to_pspec`` in ``repro.sharding``)

Keeping shapes, axes and init together eliminates the classic bug of a
sharding-spec tree drifting out of sync with the parameter tree.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    """Declaration of a single parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]  # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones | embed | conv | rglru_lambda
    scale: float | None = None  # stddev override for "normal"
    dtype: Any = None  # None -> model default dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"ParamDecl shape {self.shape} and axes {self.axes} rank mismatch"
            )


def is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


def _leaves(decls: PyTree):
    return jax.tree.leaves(decls, is_leaf=is_decl)


def map_decls(fn, decls: PyTree) -> PyTree:
    return jax.tree.map(fn, decls, is_leaf=is_decl)


def stack_decls(decls: PyTree, num: int, axis_name: str | None = "layers") -> PyTree:
    """Add a leading stacked-layer dimension to every decl (for lax.scan)."""

    def stack(d: ParamDecl) -> ParamDecl:
        return dataclasses.replace(
            d, shape=(num,) + d.shape, axes=(axis_name,) + d.axes
        )

    return map_decls(stack, decls)


def _fan_in(shape: tuple[int, ...]) -> int:
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return shape[0]
    return int(np.prod(shape[:-1]))


def _init_leaf(key, d: ParamDecl, default_dtype) -> jax.Array:
    dtype = d.dtype or default_dtype
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "normal":
        scale = d.scale if d.scale is not None else 1.0 / math.sqrt(max(_fan_in(d.shape), 1))
        return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(dtype)
    if d.init == "embed":
        scale = d.scale if d.scale is not None else 1.0
        return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(dtype)
    if d.init == "conv":
        scale = 1.0 / math.sqrt(max(d.shape[-1], 1))
        return (jax.random.uniform(key, d.shape, jnp.float32, -scale, scale)).astype(dtype)
    if d.init == "ssm_a_log":
        # Mamba-2: A ~ U[1, 16], stored as log(A); dA = -exp(A_log) * dt
        a = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(d.dtype or jnp.float32)
    if d.init == "ssm_dt_bias":
        # dt = softplus(raw + bias) in ~[1e-3, 0.1] at init
        dt = jnp.exp(
            jax.random.uniform(key, d.shape, jnp.float32)
            * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)
        )
        inv_softplus = dt + jnp.log(-jnp.expm1(-dt))
        return inv_softplus.astype(d.dtype or jnp.float32)
    if d.init == "rglru_lambda":
        # Griffin RG-LRU Lambda param: a in [0.9, 0.999] via softplus param.
        u = jax.random.uniform(key, d.shape, jnp.float32, 0.9, 0.999)
        c = 8.0
        # log_a = -c * softplus(L)  =>  softplus(L) = -log(a)/c
        sp = -jnp.log(u ** (1.0 / c))
        lam = jnp.log(jnp.expm1(sp))
        return lam.astype(dtype or jnp.float32)
    raise ValueError(f"unknown init {d.init!r}")


def _build_params(key, *, leaves, treedef, default_dtype):
    keys = jax.random.split(key, max(len(leaves), 1))
    out = [_init_leaf(k, d, default_dtype) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


_STATIC = ("leaves", "treedef", "default_dtype")
_build_params_jit = jax.jit(_build_params, static_argnames=_STATIC)


def init_params(key, decls: PyTree, default_dtype=jnp.float32,
                shardings=None) -> PyTree:
    """Initialize real parameters from a decl tree in ONE jitted program.

    Each leaf's float32 draw fuses into its cast to the leaf dtype, so no
    float32 copy of a whole stacked ``[L, ...]`` leaf is ever held.  With
    ``shardings`` (a tree of shardings matching the params) every leaf is
    created in place on its devices, never whole on one device first.
    """
    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_decl)
    fn = (_build_params_jit if shardings is None else
          jax.jit(_build_params, static_argnames=_STATIC,
                  out_shardings=shardings))
    return fn(key, leaves=tuple(leaves), treedef=treedef,
              default_dtype=default_dtype)


def abstract_params(decls: PyTree, default_dtype=jnp.bfloat16) -> PyTree:
    """ShapeDtypeStruct tree — used by the dry-run; allocates nothing."""

    def leaf(d: ParamDecl):
        return jax.ShapeDtypeStruct(d.shape, d.dtype or default_dtype)

    return map_decls(leaf, decls)


def logical_axes(decls: PyTree) -> PyTree:
    """Tree of logical-axis tuples mirroring the param tree."""
    return map_decls(lambda d: d.axes, decls)


def param_count(decls: PyTree) -> int:
    return sum(int(np.prod(d.shape)) for d in _leaves(decls))


def param_bytes(decls: PyTree, default_dtype=jnp.bfloat16) -> int:
    total = 0
    for d in _leaves(decls):
        dt = jnp.dtype(d.dtype or default_dtype)
        total += int(np.prod(d.shape)) * dt.itemsize
    return total
