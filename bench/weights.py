"""Seeded weights and inputs, made by the benchmark itself.

Every weight is drawn from ``--seed`` on the device, in one jitted call, in
the dtype it is served or compressed in, and laid out as the system under
test takes its params (the pytree its checkpoints hold).  The program's
initializer is evaluated only under ``jax.eval_shape``, for that layout: no
value it computes is used.  The plain references regenerate the same arrays
from the same seed with these functions.

Scales keep activations finite at any depth: a dense ``w`` (n, m) and a
factor pair ``v`` (n, k), ``u`` (k, m) both map unit-variance inputs to
unit-variance outputs, and the residual-branch outputs (``wo``, ``down``)
are further scaled by 1/sqrt(2 L), as the program's own initializer does.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

RESIDUAL_OUT = ("wo", "down")


def model_config(entry: dict, **overrides):
    """The program's ``ModelConfig`` for a configuration file's ``model``
    section, with ``overrides`` applied."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**{**entry["model"], **overrides})


def layout(cfg, *, ratio: float = 1.0, rank_multiple: int = 8):
    """Shape/dtype pytree of the program's params for ``cfg``; with
    ``ratio`` < 1 every compressible linear is a factor pair at the ranks
    AA-SVD gives (``ranks.rank_for_ratio``)."""
    from repro.core.factorized import factorize_params
    from repro.models import model as M

    def build():
        p = M.init_params(cfg, jax.random.PRNGKey(0))
        if ratio < 1.0:
            p = factorize_params(p, cfg, ratio=ratio,
                                 rank_multiple=rank_multiple)
        return p
    return jax.eval_shape(build)


def _path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _draw(name: str, shape, key, num_layers: int):
    parts = name.split("/")
    leaf, owner = parts[-1], (parts[-2] if len(parts) > 1 else "")
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf == "scale":
        return 1.0 + 0.1 * normal
    if name == "embed/table":
        return 0.02 * normal
    fan_in = shape[-2]
    out = normal / math.sqrt(fan_in)
    if owner in RESIDUAL_OUT and leaf in ("w", "u"):
        out = out / math.sqrt(2 * num_layers)
    return out


def seed_key(seed: int):
    return jax.random.PRNGKey(seed)


def make_params(cfg, seed: int, *, ratio: float = 1.0,
                rank_multiple: int = 8):
    """Params for ``cfg`` drawn from ``seed``, on the device, one jit."""
    shapes = layout(cfg, ratio=ratio, rank_multiple=rank_multiple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_str(p) for p, _ in flat]
    specs = [(s.shape, s.dtype) for _, s in flat]

    def build(key):
        leaves = []
        for name, (shape, dtype) in zip(names, specs):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            leaves.append(_draw(name, shape, k, cfg.num_layers).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def tokens(seed: int, rows: int, length: int, vocab: int,
           stream: int = 0) -> np.ndarray:
    """(rows, length) int32 token ids, uniform over the vocabulary."""
    rng = np.random.default_rng([seed, stream])
    return rng.integers(0, vocab, size=(rows, length), dtype=np.int32)
