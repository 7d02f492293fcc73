"""Stage partitioning + flat parameter/activation packing.

The reference assigns contiguous cell ranges to ranks
(``mp_pipeline.py:41-83``) and keeps per-rank parameter objects.  The TPU
engine instead runs ONE SPMD program where every device holds its stage's
parameters as a single flat fp32 vector, padded to the max stage size and
sharded over the ``stage`` mesh axis.  Flat stage buffers are what make three
things trivial that cost the reference real machinery:

- heterogeneous stages under ``lax.switch`` (each branch statically unpacks
  its own tree; buffers all have one shape),
- the optimizer (elementwise over one vector; no per-layer loop),
- GEMS mirror exchange (one ppermute of the whole stage's weights — the
  reference builds contiguous flat views by re-pointing every torch parameter,
  train_spatial_master.py:114-138).

Activation boundaries likewise pack to flat vectors (tuple states — AmoebaNet
(x, skip) — flatten transparently) padded to the max boundary size so the
stage handoff is a single uniform ppermute.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpi4dl_tpu.cells import CellModel, split_even
from mpi4dl_tpu.layer_ctx import ApplyCtx

Act = Any


# ---------------------------------------------------------------------------
# Generic pytree <-> flat vector packing (static metadata)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreePack:
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]

    @property
    def total(self) -> int:
        return int(sum(self.sizes))

    @classmethod
    def of(cls, tree) -> "TreePack":
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple(tuple(map(int, l.shape)) for l in leaves)
        dtypes = tuple(l.dtype for l in leaves)
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        return cls(treedef, shapes, dtypes, sizes)

    def pack(self, tree, dtype=jnp.float32) -> jax.Array:
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.zeros((0,), dtype)
        return jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])

    def unpack(self, vec: jax.Array, dtype=None):
        leaves, off = [], 0
        for shape, dt, size in zip(self.shapes, self.dtypes, self.sizes):
            chunk = lax_slice(vec, off, size)
            leaves.append(chunk.reshape(shape).astype(dtype or dt))
            off += size
        return jax.tree.unflatten(self.treedef, leaves)


def lax_slice(vec, off: int, size: int):
    return jax.lax.slice_in_dim(vec, off, off + size)


def stat_leaf_info(tree) -> Tuple[List[int], List[Tuple[int, int]]]:
    """Locate BN running-stat leaves in a params tree.

    Returns (leaf_ids, slots): ``leaf_ids`` are indices into the flattened
    leaf list for every 'mean'/'var' entry of a dict that also carries
    'scale' and 'bias' (the BatchNorm param signature — layers.py); ``slots``
    are the matching (offset, size) ranges in the TreePack flat vector (flatten
    order, offsets = cumulative leaf sizes).  This is what lets the flat-buffer
    engines deposit running-stat updates back into their stage rows."""
    from jax.tree_util import DictKey

    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(tree)
    parents: dict = {}
    for path, _leaf in leaves_with_path:
        if path and isinstance(path[-1], DictKey):
            parents.setdefault(path[:-1], set()).add(path[-1].key)
    bn_parents = {
        p for p, ks in parents.items() if {"scale", "bias", "mean", "var"} <= ks
    }
    leaf_ids: List[int] = []
    slots: List[Tuple[int, int]] = []
    off = 0
    for i, (path, leaf) in enumerate(leaves_with_path):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        if (
            path
            and isinstance(path[-1], DictKey)
            and path[-1].key in ("mean", "var")
            and path[:-1] in bn_parents
        ):
            leaf_ids.append(i)
            slots.append((off, size))
        off += size
    return leaf_ids, slots


def stat_index_array(slots: Sequence[Tuple[int, int]], stat_max: int) -> np.ndarray:
    """[stat_max] int32 flat positions for the slots, padded with -1."""
    idx = np.full((stat_max,), -1, np.int32)
    o = 0
    for off, size in slots:
        idx[o : o + size] = np.arange(off, off + size, dtype=np.int32)
        o += size
    return idx


def pad_to(vec: jax.Array, n: int) -> jax.Array:
    if vec.shape[0] == n:
        return vec
    return jnp.pad(vec, (0, n - vec.shape[0]))


# ---------------------------------------------------------------------------
# Stage partition
# ---------------------------------------------------------------------------


class TiedLeaf(NamedTuple):
    """A parameter leaf that several cells read (``CellModel.tied``): its
    size, and where each use keeps its copy, ``(stage, offset in that
    stage's row)``, the owner's first."""

    size: int
    uses: Tuple[Tuple[int, int], ...]


@dataclasses.dataclass
class StagePartition:
    """Static description of a model split into S pipeline stages."""

    model: CellModel
    ranges: List[Tuple[int, int]]  # cell index ranges per stage
    param_packs: List[TreePack]  # per-stage parameter packing
    act_packs: List[TreePack]  # act_packs[s] = input structure of stage s
    out_pack: TreePack  # output of last stage (logits)
    param_max: int
    act_max: int
    # BN running-stat bookkeeping (see stat_leaf_info): per stage, the leaf
    # indices + (offset, size) slots of mean/var inside the stage packing, and
    # one [S, stat_max] -1-padded position table for the write-back scatter.
    stat_leaf_ids: List[List[int]] = dataclasses.field(default_factory=list)
    stat_slots: List[List[Tuple[int, int]]] = dataclasses.field(default_factory=list)
    stat_max: int = 0
    stat_idx: Optional[np.ndarray] = None  # [S, stat_max] int32
    # Storage dtype of the flat parameter buffers (reference --precision
    # bf_16_all: everything, params included, in bf16 — halves the stage
    # buffers, the GEMS mirror ppermute traffic, and the grad cotangents;
    # update arithmetic stays fp32 inside Optimizer).
    param_dtype: Any = jnp.float32
    # Every leaf that several cells read (``CellModel.tied``, a leaf or each
    # leaf under a tied subtree): each reader keeps a copy of it in its
    # stage's row, and the engine sums the gradients of all its uses over the
    # stage axis before the update (``pipeline.sum_tied_grads``), so the
    # copies stay one value.
    tied_slots: Tuple[TiedLeaf, ...] = ()

    @property
    def num_stages(self) -> int:
        return len(self.ranges)

    @classmethod
    def build(
        cls,
        model: CellModel,
        params_list: Sequence[Any],
        split_size: int,
        microbatch_shape: Any,
        balance: Optional[Sequence[int]] = None,
        compute_dtype=jnp.float32,
        param_dtype=jnp.float32,
        sums_tied_grads: bool = False,
    ) -> "StagePartition":
        """``microbatch_shape`` is either a plain shape tuple or a pytree of
        ``jax.ShapeDtypeStruct`` (tuple activations entering stage 0 — the
        SP→LP junction of sp_pipeline.py hands tail stages AmoebaNet's
        (x, skip) state).  ``sums_tied_grads``: the engine sums a tied
        leaf's gradients over the stage axis (``tied_slots``; the GPipe
        schedule of ``pipeline.py`` does); any other refuses a model with
        such a leaf."""
        if not sums_tied_grads:
            model.refuse_tied("this pipeline engine")
        # one entry a cell as the cell is applied to it: a tied leaf is in
        # its owner's stage row and in its reader's
        params_list = model.per_cell(params_list)
        ranges = split_even(len(model.cells), split_size, balance)
        param_packs = [
            TreePack.of([params_list[i] for i in range(r0, r1)]) for r0, r1 in ranges
        ]
        # Boundary activation structures via eval_shape chain (the reference's
        # two-phase shape probe, mp_pipeline.py:126-168, for free).
        act_structs = []
        if isinstance(microbatch_shape, tuple) and all(
            isinstance(d, int) for d in microbatch_shape
        ):
            x = jax.ShapeDtypeStruct(microbatch_shape, compute_dtype)
        else:
            x = microbatch_shape
        ctx = ApplyCtx(train=True)
        for s, (r0, r1) in enumerate(ranges):
            act_structs.append(x)
            x = jax.eval_shape(
                lambda ps, xx, a=r0, b=r1: _apply_range(model, ps, xx, ctx, a, b),
                [params_list[i] for i in range(r0, r1)],
                x,
            )
        out_struct = x
        act_packs = [TreePack.of_struct(s, compute_dtype) for s in act_structs]
        out_pack = TreePack.of_struct(out_struct, compute_dtype)
        param_max = max(p.total for p in param_packs)
        act_max = max([p.total for p in act_packs] + [out_pack.total])
        stat_leaf_ids, stat_slots = [], []
        for r0, r1 in ranges:
            ids, slots = stat_leaf_info([params_list[i] for i in range(r0, r1)])
            stat_leaf_ids.append(ids)
            stat_slots.append(slots)
        stat_max = max((sum(sz for _, sz in s) for s in stat_slots), default=0)
        stat_idx = (
            np.stack([stat_index_array(s, stat_max) for s in stat_slots])
            if stat_max
            else None
        )
        def slots(cell: int, name: str) -> List[Tuple[int, int, int]]:
            """(stage, offset in its row, size) of each leaf under cell
            ``cell``'s ``name``, in the order the tree flattens."""
            stage = next(s for s, (r0, r1) in enumerate(ranges) if r0 <= cell < r1)
            r0, r1 = ranges[stage]
            found, off = [], 0
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    [params_list[i] for i in range(r0, r1)])[0]:
                if len(path) > 1 and (path[0].idx, getattr(path[1], "key", None)
                                      ) == (cell - r0, name):
                    found.append((stage, off, int(leaf.size)))
                off += int(leaf.size)
            if not found:
                raise KeyError((cell, name))
            return found

        # by the owner's name: the owner's leaves, then each reader's copies
        uses: Dict[Tuple[int, str], List[List[Tuple[int, int, int]]]] = {}
        for owner, reader, name in model.tied:
            uses.setdefault((owner, name), [slots(owner, name)]).append(
                slots(reader, name))
        tied_slots = tuple(
            TiedLeaf(copies[0][2], tuple((s, off) for s, off, _ in copies))
            for per_use in uses.values() for copies in zip(*per_use))
        return cls(
            model, ranges, param_packs, act_packs, out_pack, param_max, act_max,
            stat_leaf_ids, stat_slots, stat_max, stat_idx, param_dtype,
            tied_slots,
        )

    # ---- parameter buffers ----

    def pack_params(self, params_list) -> jax.Array:
        """[S, param_max] buffer in ``param_dtype`` (row s = stage s's flat
        params)."""
        params_list = self.model.per_cell(params_list)
        rows = []
        for (r0, r1), pk in zip(self.ranges, self.param_packs):
            rows.append(
                pad_to(
                    pk.pack(
                        [params_list[i] for i in range(r0, r1)], self.param_dtype
                    ),
                    self.param_max,
                )
            )
        return jnp.stack(rows)

    def unpack_params(self, buf: jax.Array) -> List[Any]:
        """Inverse of pack_params (host-side, for checkpoint/eval)."""
        out: List[Any] = []
        for s, ((r0, r1), pk) in enumerate(zip(self.ranges, self.param_packs)):
            sub = pk.unpack(buf[s, : pk.total])
            out.extend(sub)
        return out

    def stage_apply(self, s: int, flat_params, act, ctx: ApplyCtx):
        """Apply stage s's cell range to an activation pytree."""
        r0, r1 = self.ranges[s]
        pk = self.param_packs[s]
        params = pk.unpack(lax_slice(flat_params, 0, pk.total))
        return _apply_range(self.model, params, act, ctx, r0, r1)


def _apply_range(model: CellModel, sub_params, x, ctx: ApplyCtx, r0: int, r1: int):
    """Run cells [r0, r1) with a stage-local (0-based) params list."""
    for i in range(r0, r1):
        x = model.cells[i].apply(sub_params[i - r0], x, ctx)
    return x


def _treepack_of_struct(struct, dtype) -> TreePack:
    leaves, treedef = jax.tree.flatten(struct)
    shapes = tuple(tuple(map(int, l.shape)) for l in leaves)
    dtypes = tuple(dtype for _ in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    return TreePack(treedef, shapes, dtypes, sizes)


TreePack.of_struct = staticmethod(_treepack_of_struct)
