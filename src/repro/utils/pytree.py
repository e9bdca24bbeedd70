"""Pytree utilities used across the framework.

The AsyncFedED protocol operates on whole parameter pytrees: pseudo-gradients,
Euclidean distances between model versions, and scaled AXPY updates. These
helpers are the pure-jnp reference layer; the fused Pallas path lives in
``repro.kernels.fedagg``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import trace

PyTree = Any


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    """a - b, leafwise."""
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: x + y, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y, leafwise (the Eq.(5) server update)."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    """Sum of elementwise products over all leaves, accumulated in f32."""
    leaves = jax.tree.map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b
    )
    return functools.reduce(jnp.add, jax.tree.leaves(leaves), jnp.float32(0.0))


def tree_sq_norm(a: PyTree) -> jax.Array:
    """Squared l2 norm over every leaf, accumulated in f32."""
    leaves = jax.tree.map(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), a)
    return functools.reduce(jnp.add, jax.tree.leaves(leaves), jnp.float32(0.0))


def tree_norm(a: PyTree) -> jax.Array:
    return jnp.sqrt(tree_sq_norm(a))


def tree_sq_dist(a: PyTree, b: PyTree) -> jax.Array:
    """||a - b||^2 without materializing the difference tree leaf-by-leaf twice."""
    leaves = jax.tree.map(
        lambda x, y: jnp.sum(
            jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))
        ),
        a,
        b,
    )
    return functools.reduce(jnp.add, jax.tree.leaves(leaves), jnp.float32(0.0))


def tree_dist(a: PyTree, b: PyTree) -> jax.Array:
    return jnp.sqrt(tree_sq_dist(a, b))


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_zeros_host(a: PyTree) -> PyTree:
    """Host (numpy) zeros shaped like ``a``: rows of a stack assembled on
    the host never need a device copy first."""
    return jax.tree.map(lambda l: np.zeros(l.shape, l.dtype), a)


def tree_size(a: PyTree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(a)))


def tree_bytes(a: PyTree) -> int:
    return int(sum(np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(a)))


def tree_cast(a: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype), a)


def tree_flatten_to_vector(a: PyTree) -> jax.Array:
    """Concatenate all leaves into one flat f32 vector (kernel staging layout)."""
    return jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32) for l in jax.tree.leaves(a)]
    )


def tree_unflatten_from_vector(vec: jax.Array, like: PyTree) -> PyTree:
    """Inverse of :func:`tree_flatten_to_vector` against a template tree."""
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(jnp.reshape(vec[off : off + n], l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


class FlatSpec:
    """Cached flatten/unflatten spec for a fixed pytree structure.

    Flattening a pytree for the fedagg kernels means: ravel every leaf to
    f32, concatenate, and zero-pad to a multiple of ``block`` (the kernel's
    VMEM tile). Doing that naively per server step re-walks the tree and
    re-computes shapes/offsets each time; ``FlatSpec`` captures the treedef,
    leaf shapes/dtypes and the padded length once so both directions are a
    single concat/split with no Python re-derivation.
    """

    __slots__ = ("treedef", "shapes", "dtypes", "sizes", "n", "n_padded",
                 "block")

    def __init__(self, tree: PyTree, block: int = 1):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = tuple(l.shape for l in leaves)
        self.dtypes = tuple(l.dtype for l in leaves)
        self.sizes = tuple(int(np.prod(s)) for s in self.shapes)
        self.n = int(sum(self.sizes))
        self.block = int(block)
        self.n_padded = self.n + (-self.n) % max(self.block, 1)

    def flatten(self, tree: PyTree) -> jax.Array:
        """Pytree (matching this spec) -> padded flat f32 vector."""
        vec = tree_flatten_to_vector(tree)
        if self.n_padded != self.n:
            vec = jnp.pad(vec, (0, self.n_padded - self.n))
        return vec

    def unflatten(self, vec: jax.Array) -> PyTree:
        """Padded flat vector -> pytree with the original shapes/dtypes."""
        out, off = [], 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            out.append(jnp.reshape(vec[off:off + size], shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self.treedef, out)

    def zeros(self) -> jax.Array:
        return jnp.zeros((self.n_padded,), jnp.float32)


class FlatParams:
    """A parameter pytree held as one padded flat f32 array.

    The flat-state server runtime (``AsyncFedEDServer(backend="pallas")``)
    keeps the global model in this form so every Eq.(5-7) step is a kernel
    sweep over one contiguous vector instead of a Python walk over the tree.
    ``tree`` materializes the pytree view lazily and caches it — the cache
    is dropped whenever the vector is replaced.
    """

    __slots__ = ("vec", "spec", "_tree_cache")

    def __init__(self, vec: jax.Array, spec: FlatSpec,
                 tree_cache: Optional[PyTree] = None):
        assert vec.shape == (spec.n_padded,), (vec.shape, spec.n_padded)
        self.vec = vec
        self.spec = spec
        self._tree_cache = tree_cache

    @classmethod
    def from_tree(cls, tree: PyTree, block: int = 1) -> "FlatParams":
        spec = FlatSpec(tree, block=block)
        return cls(spec.flatten(tree), spec, tree_cache=tree)

    @property
    def tree(self) -> PyTree:
        if self._tree_cache is None:
            with trace.span("server.unflatten"):
                self._tree_cache = self.spec.unflatten(self.vec)
        return self._tree_cache

    def replace(self, vec: jax.Array) -> "FlatParams":
        """New FlatParams sharing the spec; invalidates the tree cache."""
        return FlatParams(vec, self.spec)


def tree_map_with_path_names(fn: Callable[[str, jax.Array], Any], tree: PyTree) -> PyTree:
    """Map ``fn(name, leaf)`` where name is a '/'-joined key path string."""

    def _name(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        return "/".join(parts)

    return jax.tree_util.tree_map_with_path(lambda p, l: fn(_name(p), l), tree)
