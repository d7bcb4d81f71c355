"""Tensor-parallel (Megatron) layers.

Parity: reference ``fleet/meta_parallel/parallel_layers/mp_layers.py`` —
VocabParallelEmbedding:30, ColumnParallelLinear:97, RowParallelLinear:170,
ParallelCrossEntropy:249, which issue c_identity/c_concat/mp_allreduce ops.

TPU-native: two composable modes —
 (a) **GSPMD mode** (default): full-size logical weights carry a
     PartitionSpec; inside pjit the partitioner shards the matmul and inserts
     the same collectives the reference codes by hand. No comm code but for
     three things that XLA:TPU would run alone on the chip's line, and that
     are written as ``ppermute`` exchanges inside a ``shard_map`` over 'mp'
     alone, where the mesh being compiled for allows it (``groups_axis``,
     ``reduce_axis``; else GSPMD's collective as before): a fused QKV weight
     onto head boundaries (``linear_on_groups``, PR 36: GSPMD gathered the
     activation); the sum of a row-parallel product's partials
     (``row_parallel``); and the sum of a column-parallel product's partial
     input cotangents (``column_parallel``, ``linear_on_groups``'s backward
     pass) (PR 39: GSPMD's all-reduce is a synchronous instruction on this
     compiler, a collective-permute is not).
 (b) **shard_map mode**: when called inside an explicit shard_map over the
     'mp' axis, per-rank shard weights + explicit psum — bit-for-bit the
     Megatron formulation, used by the hybrid engine's manual path.
"""
from __future__ import annotations

import itertools
import math
from functools import partial
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from ....core.tensor import Tensor
from ....nn import functional as F
from ....nn import initializer as I
from ....nn.layer.layers import Layer
from ....nn.param_attr import ParamAttr
from ... import collective
from ...collective import _c_identity, _c_split, _mp_allreduce, _c_concat, _c_softmax_with_cross_entropy


def _mp_group(mp_group):
    if mp_group is not None:
        return mp_group
    from ..base.fleet_base import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return hcg.get_model_parallel_group() if hcg is not None else None


def _mp_degree(group):
    return group.nranks if group is not None else 1


class VocabParallelEmbedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self.group = _mp_group(mp_group)
        self.world_size = _mp_degree(self.group)
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        # GSPMD: full logical weight, sharded on vocab dim over 'mp'
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=ParamAttr._to_attr(weight_attr),
            default_initializer=I.XavierUniform(),
        )
        self.weight.pspec = PartitionSpec("mp", None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return out


class ColumnParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None, has_bias=None, gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.group = _mp_group(mp_group)
        self.world_size = _mp_degree(self.group)
        self.gather_output = gather_output
        self._name = name
        self.weight = self.create_parameter(
            [in_features, out_features], attr=ParamAttr._to_attr(weight_attr),
            default_initializer=I.XavierUniform(),
        )
        self.weight.pspec = PartitionSpec(None, "mp")  # column sharding
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True, default_initializer=I.Constant(0.0))
            self.bias.pspec = PartitionSpec("mp")
        else:
            self.bias = None

    def forward(self, x):
        if not self.gather_output and reduce_axis(self.weight.shape[1]) is not None:
            return products_of(x, self)[0]
        x = _c_identity(x, self.group)
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            out = _c_concat(out, self.group)
        return out

    def fused_heads(self, x, groups, head_dim):
        """``forward`` for a weight that fuses ``groups`` projections onto
        heads of ``head_dim`` (a QKV), unbound: ``groups`` tensors (B, T,
        heads, head_dim). Under GSPMD shapes are GLOBAL whatever the mesh:
        the product is (B, T, groups x H) and ``heads`` every head; only
        inside a map that holds 'mp' by hand (Megatron's per-rank view) is it
        groups x H / mp and the rank's own heads.

        The weight's contiguous column split is no split on head boundaries
        (with mp 2 the first chip holds Q and half of K), so where the step is
        compiled over a mesh that splits the heads (:func:`groups_axis`), the
        reshape below would gather the activation across the axis in every
        layer; there the product is taken against the weight exchanged onto
        head boundaries (:func:`linear_on_groups`) and leaves the matmul as
        (B, T, groups, H) with H on the axis. The parameters stay as they
        lie."""
        axis = None
        if self.bias is not None:  # the exchange sends bias and weight as one
            axis = groups_axis(self.weight.shape[1] // groups // head_dim, groups)
        if axis is None:
            out = self(x)
        else:
            from ....core.dispatch import eager_call

            out = eager_call(
                "linear_on_groups", linear_on_groups, [x, self.weight, self.bias],
                {"groups": groups, "axis": axis,
                 # read here, not inside: the op is traced once a signature
                 "blocks": MP_REDUCE_CHUNKS if reduce_axis(
                     self.weight.shape[1] // groups) is not None else 0})
        return out.reshape([x.shape[0], x.shape[1], groups, -1, head_dim]).unbind(axis=2)


class RowParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True, input_is_parallel=False, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.group = _mp_group(mp_group)
        self.world_size = _mp_degree(self.group)
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features], attr=ParamAttr._to_attr(weight_attr),
            default_initializer=I.XavierUniform(),
        )
        self.weight.pspec = PartitionSpec("mp", None)  # row sharding
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True, default_initializer=I.Constant(0.0))
            self.bias.pspec = PartitionSpec()
        else:
            self.bias = None

    def forward(self, x):
        axis = reduce_axis(self.weight.shape[0])
        if axis is not None:
            # the sum over 'mp' of the two partial products by exchange, in
            # token chunks under the product itself (``product_summed``)
            from ....core.dispatch import eager_call

            out = eager_call("row_parallel_linear", row_parallel, [x, self.weight],
                             {"where": _where(axis), "blocks": MP_REDUCE_CHUNKS})
        else:
            if not self.input_is_parallel:
                x = _c_split(x, self.group)
            out = F.linear(x, self.weight, None)
            out = _mp_allreduce(out, self.group)
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.group = _mp_group(mp_group)
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return _c_softmax_with_cross_entropy(input, label, self.group, self.ignore_index)


# -- a fused projection split on group boundaries (PR 36) ---------------------
# A fused QKV weight (d, 3h) lies P(None, 'mp'): a CONTIGUOUS split of its 3h
# columns, so with mp 2 chip 0 holds Q and half of K. Attention wants heads on
# 'mp': (d, 3, h) lying P(None, None, 'mp'). GSPMD reaches that layout only by
# gathering the activation (or the whole weight) in every layer; these
# functions move a third of the weight by hand instead, which depends on no
# activation and so runs under whatever the chip computes meanwhile.
MP_EXCHANGE_SCOPE = "mp_exchange"


def groups_axis(heads, groups=3, axis="mp"):
    """``axis`` where the step being traced is compiled over a mesh
    (``distributed.mesh.partitioned_over``) that splits ``heads`` heads over
    more than one chip along it and :func:`split_on_groups` can serve
    ``groups`` fused projections there, else None: no such axis, an enclosing
    map holds it by hand (Megatron's per-rank view: shapes are local there),
    it does not divide the heads, or rank -> (groups x rank + i) % n is no
    permutation of its ranks (n and ``groups`` share a factor)."""
    n = _chips_along(axis)
    if n == 1 or heads % n or math.gcd(groups, n) != 1:
        return None
    return axis


def _chips_along(axis):
    """How many chips the mesh of the step being traced joins along ``axis``
    for GSPMD to split over: 1 with no such mesh or axis, and where an
    enclosing map holds the axis by hand."""
    from ...collective import _axis_bound
    from ...mesh import partitioned_mesh

    mesh = partitioned_mesh()
    if mesh is None or _axis_bound(axis):
        return 1
    return mesh.shape.get(axis, 1)


def _exchange(arrays, rank, groups, n, axis, join):
    """One rank's part of :func:`split_on_groups` (``join``: of its inverse),
    inside a map over ``axis``; ``rank`` is this rank's index as a (1,) array.
    Splitting, the rank holds ``groups`` chunks of c columns; chunk i of rank
    r is chunk m = groups x r + i of the whole, which belongs to rank m % n in
    slot m // n. For each i that is ONE permutation of the ranks, and where it
    is the identity nothing is sent (two of three at n 2). The arrays' chunks
    travel stacked by rows in one transfer: a small transfer sent behind a
    large one waits for it, and XLA schedules its wait as if it were instant
    (PERF.md, PR 30). Rank d then finds slot s in what chunk (s x n + d) %
    groups brought, so it lays the slots by a select on its own index: no
    zero-filled buffer, no scatter."""
    d = rank[0]
    c = arrays[0].shape[-1] // (1 if join else groups)
    rows = [int(np.prod(a.shape[:-2 if join else -1], dtype=np.int64)) for a in arrays]
    inv = pow(n, -1, groups)  # slot s = (i - d) / n  (mod groups)

    def part(a, i):
        if join:
            s = ((i - d) * inv) % groups
            return lax.select_n(s, *(a[..., j, :] for j in range(groups)))
        return a[..., i * c:(i + 1) * c]

    def send(blocks, i):
        perm = [(r, (groups * r + i) % n) for r in range(n)]
        if all(a == b for a, b in perm):
            return blocks
        if join:
            perm = [(b, a) for a, b in perm]
        flat = jnp.concatenate([b.reshape(-1, c) for b in blocks], axis=0)
        flat = lax.ppermute(flat, axis, perm)
        out, at = [], 0
        for b, k in zip(blocks, rows):
            out.append(flat[at:at + k].reshape(b.shape))
            at += k
        return out

    got = [send([part(a, i) for a in arrays], i) for i in range(groups)]
    if join:
        return tuple(jnp.concatenate([g[k] for g in got], axis=-1)
                     for k in range(len(arrays)))
    return tuple(
        jnp.stack([lax.select_n((s * n + d) % groups, *(g[k] for g in got))
                   for s in range(groups)], axis=-2)
        for k in range(len(arrays)))


class _Where(NamedTuple):
    """Where an exchange runs, read while the forward pass is traced (its
    transpose is traced later, outside ``partitioned_over``)."""
    axis: str
    n: int
    mesh: Optional[Mesh]  # None inside a map that took some axes by hand
    # every other axis of the mesh is held by hand or holds one chip: inside a
    # map over ``axis`` a shape is then this chip's own, and a slice of the
    # tokens cuts across no other axis's split of them
    local: bool


def _where(axis) -> _Where:
    from ...collective import _axis_bound
    from ...mesh import partitioned_mesh

    mesh = partitioned_mesh()
    # inside a map that took some axes by hand (the engine's step, manual over
    # 'dp'), the mesh is that map's and may not be named again
    bound = {a for a in mesh.axis_names if _axis_bound(a)}
    return _Where(axis, mesh.shape[axis], None if bound else mesh,
                  all(a == axis or a in bound or mesh.shape[a] == 1
                      for a in mesh.axis_names))


def _over_axis(arrays, groups, where, join):
    from ...mesh import shard_map_compat

    axis, n, mesh, _ = where
    k = 2 if join else 1
    contiguous = tuple(PartitionSpec(*(None,) * (a.ndim - k), axis) for a in arrays)
    on_groups = tuple(PartitionSpec(*(None,) * (a.ndim - k), None, axis) for a in arrays)
    shard_map, check = shard_map_compat()
    fn = shard_map(
        lambda xs, rank: _exchange(xs, rank, groups, n, axis, join),
        in_specs=(on_groups if join else contiguous, PartitionSpec(axis)),
        out_specs=contiguous if join else on_groups,
        axis_names=frozenset({axis}), **({} if mesh is None else {"mesh": mesh}), **check)
    with jax.named_scope(MP_EXCHANGE_SCOPE):
        # ``lax.axis_index`` of an inner axis does not lower in a nested map
        return fn(tuple(arrays), jnp.arange(n, dtype=jnp.int32))


def split_on_groups(x, groups, axis="mp"):
    """``x`` (an array, or a tuple of arrays that travel together) whose LAST
    dimension lies ``P(..., axis)`` contiguously and holds ``groups`` equal
    blocks -> ``(..., groups, last / groups)`` lying ``P(..., None, axis)``:
    every block split over the axis by itself, so that block g's part on a
    chip is that chip's heads of projection g. A collective-permute over
    ``axis`` inside the step being traced (:func:`groups_axis` says where it
    serves); its transpose is the same exchange backwards."""
    return _split(x, groups, _where(axis))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _split(x, groups, where):
    one = not isinstance(x, (tuple, list))
    out = _over_axis((x,) if one else tuple(x), groups, where, join=False)
    return out[0] if one else out


def _split_fwd(x, groups, where):
    return _split(x, groups, where), None


def _split_bwd(groups, where, _, ct):
    one = not isinstance(ct, (tuple, list))
    out = _over_axis((ct,) if one else tuple(ct), groups, where, join=True)
    return (out[0] if one else out,)


_split.defvjp(_split_fwd, _split_bwd)


def linear_on_groups(x, w, b, groups=3, axis="mp", blocks=0):
    """``x @ w + b`` for a fused column-parallel ``w`` (d, groups x h) and
    ``b`` as they lie, ``P(None, axis)`` and ``P(axis)``, as ``(..., groups,
    h)`` with h on ``axis``: the product is taken against
    :func:`split_on_groups` of the two, and no activation crosses the axis.
    The backward pass keeps no split copy: its residuals are the operands
    themselves, and it exchanges the weight again. With ``blocks`` the
    input's cotangent, a sum over h, crosses the axis by exchange in so many
    blocks (:func:`product_summed`; the caller asks :func:`reduce_axis`)."""
    return _linear(x, w, b, groups, _where(axis), blocks)


def _product(x, w, b, groups, where, blocks=0):
    ws, bs = _split((w, b), groups, where)
    return jnp.einsum("...d,dgh->...gh", x, ws) + bs


_linear = jax.custom_vjp(_product, nondiff_argnums=(3, 4, 5))


def _linear_bwd(groups, where, blocks, res, ct):
    # tied to the cotangent, the second exchange cannot be merged with the
    # forward pass's (XLA does merge a plain ``jax.checkpoint``'s), whose
    # result would then be held from there to here: 16 split copies. Not x:
    # through the barrier it has to exist as it was, and XLA then keeps the
    # norm's output from the forward pass where it would have recomputed it
    x, w, b = res
    w, b, ct = lax.optimization_barrier((w, b, ct))
    if not blocks:
        _, vjp = jax.vjp(lambda *a: _product(*a, groups, where), x, w, b)
        return vjp(ct)
    (ws, bs), lay_back = jax.vjp(lambda w, b: _split((w, b), groups, where), w, b)
    _, vjp = jax.vjp(lambda ws, bs: jnp.einsum("...d,dgh->...gh", x, ws) + bs, ws, bs)
    # the input's cotangent is partial over the axis: h is split there
    h = PartitionSpec(*(None,) * (ct.ndim - 1), where.axis)
    dx = product_summed(("...gh,dgh->...d",), (ct, ws),
                        (h, PartitionSpec(None, None, where.axis)), where, blocks)
    return (dx, *lay_back(vjp(ct)))


_linear.defvjp(
    lambda x, w, b, groups, where, blocks: (_product(x, w, b, groups, where), (x, w, b)),
    _linear_bwd)


# -- a sum of partial products over 'mp' by exchange (PR 39) ------------------
# A row-parallel product, and the input cotangent of a column-parallel one,
# contract a dimension that is split over 'mp': each chip holds a partial
# product of the full shape and GSPMD sums them by all-reduce, which XLA:TPU
# runs as a synchronous instruction alone on the chip's line (PERF.md, PR 30).
# Between TWO chips that sum is an exchange of the partials and a local add,
# and a collective-permute does run beside compute: the product is taken in
# chunks over the tokens, each chunk sent as soon as it exists while the next
# is computed. Both chips add the same two values (a + b is b + a, in
# bfloat16 too), so the replicas of every norm and bias stay bit-equal.
MP_REDUCE_SCOPE = "mp_reduce"
MP_REDUCE_CHUNKS = 4


def reduce_axis(*split, axis="mp"):
    """``axis`` where the step being traced is compiled over a mesh that
    splits it over exactly two chips, each of the sizes ``split`` evenly, and
    no enclosing map holds it by hand (Megatron's per-rank view, whose
    ``psum`` stays): where :func:`product_summed` can serve. Else None, and
    the caller takes GSPMD's all-reduce: a ring over more chips (n - 1 hops
    of reduce-scatter and as many of all-gather, by ``ppermute``) is not
    written."""
    if _chips_along(axis) != 2 or any(k % 2 for k in split):
        return None
    return axis


def _cuts(lead, chunks):
    """How many equal parts to cut each of the leading dimensions ``lead``
    into, for ``chunks`` blocks in all or as many as divide them: the first
    dimensions first (whole batch rows, then parts of the sequence; (2, 2048)
    in 4 is 2 x 2). No dimension is merged with another: behind a reshape the
    consumer's fusion takes in neither the add nor the concatenation, and
    each is then a pass over the activation by itself."""
    cuts = []
    for d in lead:
        cuts.append(math.gcd(d, chunks))
        chunks //= cuts[-1]
    return tuple(cuts)


def product_summed(equations, operands, specs, where, blocks):
    """The sum over pairs ``(a, w)`` of ``einsum(equation, a, w)``, each
    equation ``"...<a's own>,<w's>->...<out's>"`` contracting a dimension
    that ``specs`` (a ``PartitionSpec`` an operand, flat as ``operands``: a,
    w, a, w ...) split over ``where.axis``, summed over that axis: whole and
    the same bits on both of its chips. The leading dimensions of every ``a``
    are the tokens, and the product is taken in up to ``blocks`` equal blocks
    of them, a block's partial sent while the next is computed."""
    from ...mesh import shard_map_compat

    axis, _, mesh, local = where
    # the dimensions "..." stands for: all of ``a`` but those the equation names
    lead = operands[0].ndim + 3 - len(equations[0].split(",")[0])
    # blocks only of tokens that are this chip's own: cut through another
    # axis's split of them, a block would live on part of the mesh
    cuts = _cuts(operands[0].shape[:lead], blocks if local else 1)

    def body(*operands):
        arrays, weights = operands[::2], operands[1::2]
        sizes = [d // c for d, c in zip(arrays[0].shape, cuts)]
        places = list(itertools.product(*(range(c) for c in cuts)))
        mine, theirs = [], []
        for at in places:
            block = tuple(slice(i * n, (i + 1) * n) for i, n in zip(at, sizes))
            if len(mine) >= 2:
                # XLA's scheduler, left to itself, computes every block and
                # only then starts the transfers. So block k + 2 is computed
                # once block k has arrived, and the last is sent once the one
                # before it has: the latency-hiding scheduler then lays block
                # k's transfer under block k + 1's product, and only the last
                # stands alone. (The barriers are expanded BEFORE the last
                # passes that touch the schedule, and at three sites in four
                # of the 16-layer step those move the last block's product
                # to the front: there the third transfer, not the fourth, is
                # the one with nothing beside it. PERF.md, PR 39.) The whole
                # operands go through the barrier, not their blocks, which
                # would have to be copied out to exist by themselves
                theirs[-2], arrays = lax.optimization_barrier((theirs[-2], arrays))
            mine.append(sum(jnp.einsum(eq, a[block], w)
                            for eq, a, w in zip(equations, arrays, weights)))
            if len(mine) == len(places) > 1:
                theirs[-1], mine[-1] = lax.optimization_barrier((theirs[-1], mine[-1]))
            theirs.append(lax.ppermute(mine[-1], axis, [(0, 1), (1, 0)]))
        # joined first and added once: the consumer's fusion then reads the
        # blocks where they lie (added block by block, each add stands alone)
        return _joined(mine, cuts) + _joined(theirs, cuts)

    shard_map, check = shard_map_compat()
    fn = shard_map(body, in_specs=tuple(specs), out_specs=PartitionSpec(),
                   axis_names=frozenset({axis}),
                   **({} if mesh is None else {"mesh": mesh}), **check)
    with jax.named_scope(MP_REDUCE_SCOPE):
        return fn(*operands)


def _joined(blocks, cuts):
    """The blocks of :func:`product_summed`, in ``itertools.product`` order
    of their place along each cut dimension, as one array again."""
    for dim in reversed(range(len(cuts))):
        n = cuts[dim]
        if n > 1:
            blocks = [jnp.concatenate(blocks[i:i + n], axis=dim)
                      for i in range(0, len(blocks), n)]
    (whole,) = blocks
    return whole


def row_parallel(x, w, where, blocks):
    """``x @ w`` for a row-parallel ``w`` (k, n) lying ``P(axis, None)`` and
    ``x`` (..., k) with k on ``axis``, summed over the axis by exchange. The
    backward pass needs no collective: the cotangent is whole on both chips,
    and the two products against it are local."""
    return _row(x, w, where, blocks)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _row(x, w, where, blocks):
    k = where.axis
    return product_summed(
        ("...k,kn->...n",), (x, w),
        (PartitionSpec(*(None,) * (x.ndim - 1), k), PartitionSpec(k, None)),
        where, blocks)


def _row_bwd(where, blocks, res, ct):
    return jax.vjp(jnp.matmul, *res)[1](ct)


_row.defvjp(lambda x, w, where, blocks: (_row(x, w, where, blocks), (x, w)), _row_bwd)


def products_of(x, *layers):
    """``[layer(x) for layer in layers]`` for column-parallel layers that
    share the input: their partial input cotangents are added before ONE sum
    over 'mp' (what GSPMD does of itself with the all-reduce; a backward rule
    a layer would send the bytes once a layer)."""
    axis = None
    if not any(l.gather_output for l in layers):
        axis = reduce_axis(*(l.weight.shape[1] for l in layers))
    if axis is None:
        return [l(x) for l in layers]
    from ....core.dispatch import eager_call

    leaves = [t for l in layers for t in (l.weight, l.bias) if t is not None]
    out = eager_call(
        "column_parallel_linear", column_parallel, [x, *leaves],
        {"biased": tuple(l.bias is not None for l in layers), "where": _where(axis),
         "blocks": MP_REDUCE_CHUNKS})
    return list(out)


def column_parallel(x, *leaves, biased, where, blocks):
    """``x @ w + b`` for each column-parallel ``w`` (d, n) and ``b`` of
    ``leaves`` (a weight, then its bias where ``biased``) as they lie,
    ``P(None, axis)`` and ``P(axis)``. Forward is local. Backward, the
    input's cotangent is a sum over n, which is split over the axis: summed by
    exchange in token chunks (:func:`product_summed`), beside which the
    weights' cotangents, that depend on none of it, are free to run."""
    leaves = iter(leaves)
    layers = tuple((next(leaves), next(leaves) if has else None) for has in biased)
    return _columns(x, layers, where, blocks)


def _affine(x, w, b):
    return jnp.matmul(x, w) if b is None else jnp.matmul(x, w) + b


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _columns(x, layers, where, blocks):
    return tuple(_affine(x, w, b) for w, b in layers)


def _columns_bwd(where, blocks, res, cts):
    x, layers = res
    n = PartitionSpec(*(None,) * (x.ndim - 1), where.axis)
    dx = product_summed(
        ("...n,kn->...k",) * len(layers),
        [t for ct, (w, _) in zip(cts, layers) for t in (ct, w)],
        (n, PartitionSpec(None, where.axis)) * len(layers), where, blocks)
    # the leaves' cotangents as autodiff takes them: local products
    dleaves = tuple(
        jax.vjp(lambda w, b: _affine(x, w, b), w, b)[1](ct)
        for ct, (w, b) in zip(cts, layers))
    return dx, dleaves


_columns.defvjp(
    lambda x, layers, where, blocks: (_columns(x, layers, where, blocks), (x, layers)),
    _columns_bwd)
