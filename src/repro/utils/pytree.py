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


def on_host(tree: PyTree) -> bool:
    """True when any leaf of ``tree`` is a NumPy array: staging then joins
    the leaves on the host, reading any device leaf back first, and
    uploads the result once."""
    return any(isinstance(l, np.ndarray) for l in jax.tree.leaves(tree))


def _host_join(leaves, out: np.ndarray) -> np.ndarray:
    """Write the raveled leaves into ``out`` back to back (cast to its
    dtype on assignment)."""
    off = 0
    for l in leaves:
        out[off:off + l.size] = l.reshape(-1)
        off += l.size
    return out


# The staging programs below are module-level jits, so they are cached by
# layout (leaf shapes and dtypes, the padded length, B) and not by FlatSpec
# or server instance: a layout compiled once, by any instance, runs again
# with no compile. NumPy and uncommitted device arguments of one shape share
# an executable, but each new mix of them is traced again: so each program
# is called with one kind of argument per position.

def _zeros_after(rows, n_padded: int) -> jax.Array:
    """The zero padding that takes the last axis of ``rows`` (a list of
    arrays that concatenate along it) to ``n_padded``. Concatenated with
    them, it fills one output buffer; ``jnp.pad`` of their concatenation
    would stage it in a temporary of the same size first."""
    n = sum(r.shape[-1] for r in rows)
    return jnp.zeros((*rows[0].shape[:-1], n_padded - n), rows[0].dtype)


@functools.partial(jax.jit, static_argnums=1)
def _flatten(parts, n_padded: int) -> jax.Array:
    """Leaves (or one host-joined vector) -> padded flat f32 vector."""
    flat = [jnp.ravel(p).astype(jnp.float32) for p in parts]
    return jnp.concatenate(flat + [_zeros_after(flat, n_padded)])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _unflatten(vec: jax.Array, shapes, dtypes) -> list:
    """Padded flat vector -> leaves: static slices, reshapes and casts."""
    out, off = [], 0
    for shape, dtype in zip(shapes, dtypes):
        size = int(np.prod(shape))
        out.append(jnp.reshape(vec[off:off + size], shape).astype(dtype))
        off += size
    return out


@jax.jit
def _join_rows(rows) -> jax.Array:
    """B lists of leaves -> (B, n) f32, each row its leaves joined."""
    return jnp.stack([jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                       for l in row]) for row in rows])


@functools.partial(jax.jit, static_argnums=2)
def stack_rows(stales, rows, n_padded: int):
    """A list of ``B`` padded flat vectors and ``B`` delta rows -> two
    ``(B, n_padded)`` arrays in one compiled program. ``rows`` is a ``(B,
    n)`` array or a list of ``B`` vectors; it keeps its dtype and is
    zero-padded on the right."""
    d = jnp.stack(rows) if isinstance(rows, list) else rows
    return jnp.stack(stales), jnp.concatenate(
        [d, _zeros_after([d], n_padded)], axis=1)


class FlatSpec:
    """Cached flatten/unflatten spec for a fixed pytree structure.

    Flattening a pytree for the fedagg kernels means: ravel every leaf to
    f32, concatenate, and zero-pad to a multiple of ``block`` (the kernel's
    VMEM tile). ``FlatSpec`` captures the treedef, leaf shapes/dtypes and
    the padded length once; each direction is then one compiled program
    (``_flatten``, ``_unflatten``), with the treedef applied on the host.
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
        """Pytree (matching this spec) -> padded flat f32 vector. NumPy
        leaves are joined on the host and uploaded once, unpadded (see
        :func:`on_host`); device leaves go to the program as they are."""
        leaves = jax.tree.leaves(tree)
        if on_host(leaves):
            leaves = [_host_join(jax.device_get(leaves),
                                 np.empty((self.n,), np.float32))]
        return _flatten(leaves, self.n_padded)

    def stack(self, stales, trees):
        """``B`` padded flat vectors and ``B`` pytrees matching this spec ->
        ``(B, n_padded)`` stacks of the vectors and of the flattened trees
        (see :func:`stack_rows`). Trees holding any NumPy leaf are
        staged on the host (:func:`on_host`): all ``B`` are joined into
        one ``(B, n)`` array, any device leaf read back first in one call,
        and uploaded once. Device trees are joined on the device. Either
        way the stacking program of each ``B`` takes the same device
        arguments, so one trace of it serves every source of deltas."""
        leaves = [jax.tree.leaves(t) for t in trees]
        if on_host(leaves):
            rows = np.empty((len(leaves), self.n), np.float32)
            for row, l in zip(rows, jax.device_get(leaves)):
                _host_join(l, row)
            rows = jax.device_put(rows)
        else:
            rows = _join_rows(leaves)
        return stack_rows(list(stales), rows, self.n_padded)

    def unflatten(self, vec: jax.Array) -> PyTree:
        """Padded flat vector -> pytree with the original shapes/dtypes."""
        return jax.tree.unflatten(
            self.treedef, _unflatten(vec, self.shapes, self.dtypes))

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
