"""Weights from the seed, owned by the benchmark.

Every tensor of a dense GQA decoder has a canonical name and shape
(``canonical_shapes``) and its values depend only on (seed, name, layer):
``tensor`` draws them in the dtype they are served in.  The program's
parameter tree is filled from these tensors in one jitted call
(``program_params``) through the configuration's ``param_map`` (program
leaf path -> canonical name); the plain reference draws the same tensors
again, layer by layer (``refmodel``), and so shares nothing the program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL = ("embed", "lm_head", "final_norm")
LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
         "w_down")
_IDS = {n: i for i, n in enumerate(GLOBAL + LAYER)}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dims(c: dict) -> dict:
    """Sizes read from a configuration file (Hugging Face key names)."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return dict(d=d, h=h, kh=c["num_key_value_heads"],
                hd=c.get("head_dim") or d // h, ff=c["intermediate_size"],
                v=c["vocab_size"], layers=c["num_hidden_layers"],
                theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def canonical_shapes(c: dict) -> dict[str, tuple[int, ...]]:
    z = dims(c)
    d, h, kh, hd, ff, v = z["d"], z["h"], z["kh"], z["hd"], z["ff"], z["v"]
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
            "attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kh * hd),
            "wv": (d, kh * hd), "wo": (h * hd, d), "mlp_norm": (d,),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def base_key(seed: int):
    """A key from any non-negative integer seed (more than 32 bits too)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def tensor(key, name: str, layer, shape, dtype):
    """The values of one tensor.  Norm gains are float32 around 1;
    matrices are N(0, 1/fan_in) (the embedding N(0, 1)) in ``dtype``.
    ``layer`` is -1 for the tensors outside the stack; it may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(key, _IDS[name]), layer + 1)
    x = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + 0.1 * x
    scale = 1.0 if name == "embed" else 1.0 / math.sqrt(shape[0])
    return (x * scale).astype(dtype)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def program_params(seed: int, c: dict, abstract_tree, shardings=None):
    """The program's parameter tree, every leaf drawn by :func:`tensor`
    through ``c["param_map"]``; one jitted call, on the device."""
    z = dims(c)
    shapes = canonical_shapes(c)
    dtype = DTYPES[c["torch_dtype"]]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    pmap = c["param_map"]
    plan = []
    for kp, leaf in leaves:
        p = _path(kp)
        if p not in pmap:
            raise KeyError(f"program parameter {p!r} has no entry in the "
                           f"configuration's param_map")
        plan.append((pmap[p], tuple(leaf.shape), leaf.dtype))

    def build(key):
        out = []
        for name, shape, ldt in plan:
            if name.startswith("layers."):
                n = name.split(".", 1)[1]
                x = jax.lax.map(
                    lambda l, n=n: tensor(key, n, l, shapes[n], dtype),
                    jnp.arange(z["layers"]))
            else:
                x = tensor(key, name, -1, shapes[name], dtype)
            out.append(x.reshape(shape).astype(ldt))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(base_key(seed))


def param_bytes(c: dict) -> int:
    shapes = canonical_shapes(c)
    z = dims(c)
    item = np.dtype(jnp.dtype(DTYPES[c["torch_dtype"]])).itemsize
    glob = sum(int(np.prod(shapes[n])) * (4 if n.endswith("norm") else item)
               for n in GLOBAL)
    lay = sum(int(np.prod(shapes[n])) * (4 if n.endswith("norm") else item)
              for n in LAYER)
    return glob + z["layers"] * lay
