"""Logical-axis sharding: rules, mesh context, and constraint helpers.

Model code never names mesh axes.  It names *logical* axes — "batch",
"tp", "fsdp", "expert", ... — and this module resolves them against the
mesh the current run built (or resolves them to nothing on one device).
Resolution applies the **divisibility fallback**: a logical axis binds a
mesh axis only when the tensor dimension divides the mesh-axis size;
otherwise the dimension stays replicated.  A mesh axis is never used for
two dimensions of the same tensor.

The binding between a concrete :class:`jax.sharding.Mesh` and a
:class:`LogicalRules` instance is a dynamic context (:func:`axis_rules`):

    with mesh, axis_rules(mesh, LogicalRules()):
        jitted = jax.jit(step, in_shardings=..., out_shardings=...)
        ...

Inside the context, :func:`logical_constraint` emits
``with_sharding_constraint``; outside any context it is the identity, so
single-device smoke paths trace the exact same model code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# One candidate assignment: a single mesh axis or a tuple of mesh axes that
# shard a dimension jointly (e.g. batch over ("pod", "data")).
Axis = Union[str, Tuple[str, ...]]

# Logical-axis -> mesh-axis candidates, tried in order.  First candidate
# whose axes (a) all exist in the mesh, (b) are not already taken by another
# dimension of the same tensor, and (c) whose combined size divides the
# tensor dimension, wins.  Logical names absent from this table ("embed",
# "seq", "kv_seq", "head_dim", "layers", ...) always replicate.
DEFAULT_RULES: Tuple[Tuple[str, Tuple[Axis, ...]], ...] = (
    ("batch", (("pod", "data"), "data")),
    ("batch2d", (("pod", "data", "model"), ("data", "model"))),
    ("fsdp", (("pod", "data"), "data")),
    ("tp", ("model",)),
    ("tp_seq", ("model",)),
    ("heads", ("model",)),
    ("kv", ("model",)),
    ("expert", ("model",)),
    ("vocab_out", ("model",)),
)


def _axis_sizes(mesh) -> dict:
    # not mesh.shape: sharding-rules tests duck-type the mesh with only
    # ``axis_names`` and ``devices.shape``
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@dataclasses.dataclass(frozen=True)
class LogicalRules:
    """Logical-axis resolution table with divisibility fallback."""

    rules: Tuple[Tuple[str, Tuple[Axis, ...]], ...] = DEFAULT_RULES

    def candidates(self, logical: str) -> Tuple[Axis, ...]:
        for name, cands in self.rules:
            if name == logical:
                return cands
        return ()

    def _resolve(self, logical: Optional[str], dim: int, sizes: dict,
                 taken: set) -> Optional[Axis]:
        if logical is None:
            return None
        for cand in self.candidates(logical):
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if any(a not in sizes or a in taken for a in axes):
                continue
            n = math.prod(sizes[a] for a in axes)
            if n <= 1 or dim % n:
                continue
            taken.update(axes)
            return cand
        return None

    def resolve_dim(self, logical: Optional[str], dim: int, mesh,
                    taken: set) -> Optional[Axis]:
        """Resolve one tensor dimension to a mesh axis (or ``None``).

        ``taken`` is mutated: axes consumed here are unavailable for the
        remaining dimensions of the same tensor.
        """
        return self._resolve(logical, dim, _axis_sizes(mesh), taken)

    def spec(self, logical: Sequence[Optional[str]], shape: Sequence[int],
             mesh) -> P:
        """PartitionSpec for a whole tensor (one shared ``taken`` set)."""
        assert len(logical) == len(shape), (tuple(logical), tuple(shape))
        sizes, taken = _axis_sizes(mesh), set()
        return P(*[self._resolve(name, dim, sizes, taken)
                   for name, dim in zip(logical, shape)])


def _is_axes_leaf(x) -> bool:
    """A logical-axes annotation: None or a tuple of str/None entries."""
    return x is None or (isinstance(x, tuple)
                         and all(e is None or isinstance(e, str) for e in x))


def tree_specs(logical, struct, mesh: Mesh, rules: LogicalRules):
    """Resolve a pytree of logical-axes tuples against ``struct``'s shapes.

    ``logical`` mirrors ``struct`` with each array leaf replaced by its
    logical-axes tuple (see ``models.common.logical_tree``).  Returns the
    same tree of :class:`NamedSharding`.
    """
    return jax.tree.map(
        lambda log, s: NamedSharding(mesh, rules.spec(log, s.shape, mesh)),
        logical, struct, is_leaf=_is_axes_leaf)


# ---------------------------------------------------------------------------
# ZeRO-1 parameter partitioning.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ZeroPartitioner:
    """Padded 1-D layout that shards *any* param tree across N ranks.

    :class:`LogicalRules` can only bind "fsdp" to the data axis when a
    tensor dimension divides the mesh-axis size — everything else stays
    replicated.  ZeRO-1 sidesteps the divisibility gap entirely: the whole
    tree is flattened (leaf order = ``tree_flatten`` order) into one fp32
    vector, zero-padded to a multiple of ``n_shards``, and sharded as equal
    contiguous slices.  Non-divisible leaves, scalars, and leaves smaller
    than the axis all shard, because slice boundaries ignore leaf
    boundaries.

    The layout is the contract between the three ZeRO pieces:

    * ``flatten(grads)`` feeds
      :func:`repro.dist.collectives.dps_reduce_scatter_mean`, whose
      per-rank chunk is exactly ``shard(flatten(x), rank)`` of the mean;
    * the optimizer steps one ``[shard_size]`` slice per rank
      (``SGD.update_shard`` / ``AdamW.update_shard``);
    * :func:`repro.dist.collectives.dps_allgather_params` (or a plain
      ``all_gather``) reassembles the flat vector, and ``unflatten``
      restores shapes and dtypes.

    Padding is always zero: zero gradients and zero parameters produce zero
    optimizer updates, so the pad region stays zero for SGD/AdamW and
    round-trips exactly.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    n_shards: int

    @staticmethod
    def create(tree, n_shards: int) -> "ZeroPartitioner":
        """Build from a concrete or abstract (ShapeDtypeStruct) tree."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("ZeroPartitioner needs a non-empty tree")
        return ZeroPartitioner(
            treedef=treedef,
            shapes=tuple(tuple(l.shape) for l in leaves),
            dtypes=tuple(l.dtype for l in leaves),
            n_shards=int(n_shards))

    @property
    def size(self) -> int:
        """Unpadded element count of the flattened tree."""
        return sum(math.prod(s) for s in self.shapes)

    @property
    def shard_size(self) -> int:
        return -(-self.size // self.n_shards)

    @property
    def padded_size(self) -> int:
        return self.shard_size * self.n_shards

    def flatten(self, tree) -> jax.Array:
        """Tree -> fp32 ``[padded_size]`` (zero-padded, tree_flatten order)."""
        leaves = jax.tree_util.tree_leaves(tree)
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])
        return jnp.pad(flat, (0, self.padded_size - self.size))

    def unflatten(self, flat: jax.Array):
        """``[padded_size]`` (or ``[size]``) -> tree with original
        shapes/dtypes; the pad region is dropped."""
        out, off = [], 0
        for shape, dtype in zip(self.shapes, self.dtypes):
            n = math.prod(shape)
            out.append(flat[off:off + n].reshape(shape).astype(dtype))
            off += n
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def shard(self, flat: jax.Array, index) -> jax.Array:
        """Rank ``index``'s ``[shard_size]`` slice (``index`` may be traced,
        e.g. ``lax.axis_index`` inside ``shard_map``)."""
        return jax.lax.dynamic_slice(
            flat, (index * self.shard_size,), (self.shard_size,))


@dataclasses.dataclass(frozen=True)
class GroupAlignedPartitioner:
    """ZeRO-1 flat layout whose leaf slots are padded to the wire quantum.

    :class:`ZeroPartitioner` packs leaves back to back, so rank-chunk
    boundaries straddle leaves and the flat vector cannot carry per-leaf
    ⟨IL, FL⟩ wire formats — the reason per-layer wire and the overlapped
    bucketed pipeline used to be rejected under ZeRO.  This layout keeps
    the same contract (flatten / shard / optimizer-steps-a-slice /
    unflatten, zero padding everywhere) but reuses
    :class:`repro.dist.collectives.GroupLayout`'s alignment arithmetic:

    * leaves are grouped into ``buckets`` — contiguous runs of leaf
      indices in ``tree_flatten`` order (one run covering every leaf when
      the overlapped pipeline is off);
    * within a bucket every leaf slot is padded up to the bucket's wire
      ``quantum``, and the bucket total is padded so each of the
      ``n_shards`` rank chunks is itself a whole number of quanta
      (``GroupLayout.chunk``).  Chunk boundaries therefore never straddle
      a group, and each aligned tile maps to exactly one leaf
      (``GroupLayout.tile_groups``);
    * a rank's shard is the concatenation of its per-bucket chunks, so
      the sharded half-collectives can run the grouped aligned codec
      bucket-by-bucket in backward-ready order while the optimizer still
      sees one flat ``[shard_size]`` slice.

    Every field is a static Python value, so the partitioner is safe to
    build from abstract trees (``jax.eval_shape``) and to close over in
    jitted code.  Padding is zero and stays zero through SGD/AdamW (zero
    grad + zero param -> zero update), exactly as in
    :class:`ZeroPartitioner`.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    n_shards: int
    backend: str
    buckets: Tuple[Tuple[int, ...], ...]
    layouts: Tuple[Any, ...]   # one collectives.GroupLayout per bucket

    @staticmethod
    def create(tree, n_shards: int, *, backend: str = "auto",
               quantum: Optional[int] = None,
               buckets: Optional[Sequence[Sequence[int]]] = None
               ) -> "GroupAlignedPartitioner":
        """Build from a concrete or abstract tree.

        ``buckets`` is a sequence of contiguous leaf-index runs (any
        order; stored sorted into flatten order) — pass the runs of a
        :class:`repro.dist.overlap.BucketPlan` to align the layout with
        the overlapped pipeline, or leave ``None`` for one bucket over
        the whole tree.  Each bucket resolves its own quantum (same
        derivation as the bucketed collective), unless ``quantum`` pins
        one globally.
        """
        from repro.dist.collectives import (_resolve_backend,
                                            _resolve_quantum, group_layout)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("GroupAlignedPartitioner needs a non-empty tree")
        sizes = [math.prod(tuple(l.shape)) or 1 for l in leaves]
        if buckets is None:
            runs = (tuple(range(len(leaves))),)
        else:
            runs = tuple(tuple(int(i) for i in r) for r in
                         sorted(buckets, key=lambda r: r[0]))
            flat_idx = [i for r in runs for i in r]
            if flat_idx != list(range(len(leaves))):
                raise ValueError(
                    "buckets must partition the leaves into contiguous "
                    f"ascending runs, got {runs}")
        be = _resolve_backend(backend)
        layouts = []
        for run in runs:
            b_sizes = tuple(sizes[i] for i in run)
            q = _resolve_quantum(quantum, sum(b_sizes), len(run), be)
            layouts.append(group_layout(b_sizes, n_chunks=n_shards,
                                        quantum=q))
        return GroupAlignedPartitioner(
            treedef=treedef,
            shapes=tuple(tuple(l.shape) for l in leaves),
            dtypes=tuple(l.dtype for l in leaves),
            n_shards=int(n_shards), backend=be,
            buckets=runs, layouts=tuple(layouts))

    # --- static geometry -------------------------------------------------

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def size(self) -> int:
        """Unpadded element count of the flattened tree."""
        return sum(math.prod(s) or 1 for s in self.shapes)

    @property
    def padded_size(self) -> int:
        """Flat-buffer length: sum of aligned bucket totals."""
        return sum(l.total for l in self.layouts)

    @property
    def shard_size(self) -> int:
        """Per-rank slice length: sum of aligned bucket chunks."""
        return sum(l.chunk for l in self.layouts)

    def bucket_offset(self, b: int) -> int:
        """Flat-buffer offset of bucket ``b``."""
        return sum(l.total for l in self.layouts[:b])

    def shard_offset(self, b: int) -> int:
        """Offset of bucket ``b``'s chunk within a rank's shard."""
        return sum(l.chunk for l in self.layouts[:b])

    def leaf_range(self, b: int) -> Tuple[int, int]:
        """Global leaf-index range ``[lo, hi)`` of bucket ``b`` — the
        slice of a per-leaf ``[G]`` format table this bucket consumes."""
        run = self.buckets[b]
        return run[0], run[-1] + 1

    def leaf_offset(self, g: int) -> int:
        """Flat-buffer offset of leaf ``g``'s aligned slot."""
        for b, run in enumerate(self.buckets):
            if g in run:
                return self.bucket_offset(b) + self.layouts[b].offsets[
                    run.index(g)]
        raise IndexError(g)

    # --- layout transforms ----------------------------------------------

    def flatten(self, tree) -> jax.Array:
        """Tree -> fp32 ``[padded_size]``: each leaf in its aligned slot,
        zeros everywhere else (slot tails and chunk pads)."""
        leaves = jax.tree_util.tree_leaves(tree)
        flat = jnp.zeros((self.padded_size,), jnp.float32)
        for b, run in enumerate(self.buckets):
            off = self.bucket_offset(b)
            lay = self.layouts[b]
            for j, g in enumerate(run):
                leaf = leaves[g].reshape(-1).astype(jnp.float32)
                flat = jax.lax.dynamic_update_slice(
                    flat, leaf, (off + lay.offsets[j],))
        return flat

    def unflatten(self, flat: jax.Array):
        """``[padded_size]`` -> tree with original shapes/dtypes; slot
        tails and chunk pads are dropped."""
        out = []
        for b, run in enumerate(self.buckets):
            off = self.bucket_offset(b)
            lay = self.layouts[b]
            for j, g in enumerate(run):
                n = math.prod(self.shapes[g]) or 1
                o = off + lay.offsets[j]
                out.append(flat[o:o + n].reshape(self.shapes[g])
                           .astype(self.dtypes[g]))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def shard(self, flat: jax.Array, index) -> jax.Array:
        """Rank ``index``'s ``[shard_size]`` slice: the concatenation of
        its per-bucket chunks (``index`` may be traced)."""
        parts = []
        for b, lay in enumerate(self.layouts):
            off = self.bucket_offset(b)
            parts.append(jax.lax.dynamic_slice(
                flat, (off + index * lay.chunk,), (lay.chunk,)))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def assemble(self, gathered: jax.Array) -> jax.Array:
        """``all_gather`` of shards (``[n_shards, shard_size]``) -> the
        flat ``[padded_size]`` buffer (inverse of per-rank :meth:`shard`)."""
        segs = []
        for b, lay in enumerate(self.layouts):
            s = self.shard_offset(b)
            segs.append(gathered[:, s:s + lay.chunk].reshape(
                self.n_shards * lay.chunk))
        return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


# ---------------------------------------------------------------------------
# Mesh + rules context.
# ---------------------------------------------------------------------------

def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """A device mesh whose axes are all ``AxisType.Auto``.

    The models place arrays through logical rules and sharding constraints
    that the compiler propagates (:func:`logical_constraint`), which is the
    Auto axis semantics.  ``jax.make_mesh`` makes Explicit axes by default,
    under which every gather and matmul on a sharded operand would need an
    explicit ``out_sharding``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


class _Ctx(threading.local):
    def __init__(self):
        self.stack = []


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: LogicalRules):
    """Bind ``(mesh, rules)`` for :func:`logical_constraint` et al."""
    _CTX.stack.append((mesh, rules))
    try:
        yield mesh, rules
    finally:
        _CTX.stack.pop()


def current_mesh_rules() -> Tuple[Optional[Mesh], Optional[LogicalRules]]:
    """The innermost ``axis_rules`` binding, or ``(None, None)``."""
    if _CTX.stack:
        return _CTX.stack[-1]
    return None, None


def model_axis_size() -> int:
    """Size of the mesh's "model" axis in the current context (1 outside)."""
    mesh, _ = current_mesh_rules()
    if mesh is None:
        return 1
    return int(_axis_sizes(mesh).get("model", 1))


def logical_constraint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` via logical names; identity off-mesh."""
    mesh, rules = current_mesh_rules()
    if mesh is None:
        return x
    spec = rules.spec(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
