"""Shared utilities: dtypes, pytree helpers, simple rng splitting, formatting.

Everything in this file is dependency-free (jax + numpy only) and safe to import
from any layer of the stack.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
    "int32": jnp.int32,
}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache is ``.jax_cache/`` at the
    checkout root: a fixed path, so that later runs find what earlier ones
    compiled. Entry points call this; tests and library code do not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def dtype_of(name: str | jnp.dtype) -> jnp.dtype:
    if isinstance(name, str):
        return _DTYPES[name]
    return name


def bytes_of_dtype(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * bytes_of_dtype(x.dtype) for x in jax.tree.leaves(tree)
    )


def tree_cast(tree: PyTree, dtype) -> PyTree:
    dtype = dtype_of(dtype)
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def tree_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """Flatten a tree into ('a/b/c', leaf) pairs using dict keys / indices."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                parts.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                parts.append(str(p.idx))
            elif isinstance(p, jax.tree_util.GetAttrKey):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        out.append(("/".join(parts), leaf))
    return out


def tree_map_with_path_str(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    """Like tree.map but fn receives the 'a/b/c' path string."""

    def _fn(path, leaf):
        parts = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                parts.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                parts.append(str(p.idx))
            elif isinstance(p, jax.tree_util.GetAttrKey):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        return fn("/".join(parts), leaf)

    return jax.tree_util.tree_map_with_path(_fn, tree)


def fold_rng(rng: jax.Array, *names: str) -> jax.Array:
    """Deterministically derive a sub-rng from string names (stable across runs)."""
    for name in names:
        data = np.frombuffer(name.encode(), dtype=np.uint8)
        rng = jax.random.fold_in(rng, int(np.sum(data.astype(np.uint32)) % (2**31)))
    return rng


def human_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB", "PB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}EB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def asdict_shallow(dc) -> dict:
    """dataclasses.asdict without deep-copying arrays."""
    return {f.name: getattr(dc, f.name) for f in dataclasses.fields(dc)}


class FifoDict(dict):
    """A dict that evicts its oldest entry (insertion order) at a size cap —
    the ``engine.RecordStore`` eviction pattern as a reusable container.

    Drop-in for the module-level memo caches (``simulator._MATRIX_CACHE``,
    ``proxy.CachedAccuracy``): a full cache sheds one cold entry per insert
    instead of dumping the whole working set, so steady-state hit rates
    survive the cap. Evictions are counted in ``self.evictions``.

    Unlocked, like the plain dicts it replaces — but those caches are
    written from N concurrent searches (``repro.runtime.SearchExecutor``),
    so the evict step tolerates races: a key another thread already evicted
    (KeyError) or an iterator invalidated mid-eviction (RuntimeError) just
    retries against the re-checked size.
    """

    def __init__(self, max_entries: int):
        super().__init__()
        self.max_entries = max_entries
        self.evictions = 0

    def __setitem__(self, key, value) -> None:
        if key not in self:
            while len(self) >= self.max_entries:
                try:
                    super().__delitem__(next(iter(self)))
                    self.evictions += 1
                except (KeyError, RuntimeError, StopIteration):
                    continue  # racing evictor got there first; re-check size
        super().__setitem__(key, value)
