"""HybridParallelOptimizer + the data-parallel gradient sync of the engine's
step: the ZeRO-1 sharded weight update, and the pairwise exchange.

Parity: reference ``fleet/meta_optimizers/dygraph_optimizer/
hybrid_parallel_optimizer.py:170`` — wraps the user optimizer, fixes grad
clipping across groups, syncs where needed — plus the sharded weight update
of "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336): instead of every replica redundantly running
the full optimizer step after an all-reduce, gradients are reduce-SCATTERED
so each replica updates only its 1/dp shard of params + optimizer moments and
the updated params are all-gathered back. Optimizer-state memory per replica
drops to ~1/dp and the gradient sync moves half the bytes of a ring
all-reduce.

``ShardedWeightUpdate`` is the TPU-native engine for that: it owns a
``BucketPlan`` (fleet/grad_buckets.py — reverse-backward-order, size-capped,
dtype-homogeneous flat buckets) and applies the per-shard update INSIDE a
``shard_map`` over the dp mesh axis, with optional EQuARX-style int8
compression of the gradient reduce-scatter (collective.py quantized prims,
``FLAGS_quantized_allreduce``) and an error-feedback accumulator. The
distributed engine (distributed/engine.py) builds its train step around it
wherever the mesh has one data-parallel axis of size > 1, alone or beside
'mp', when ``FLAGS_shard_weight_update`` is on. Beside 'mp' the leaves are not
flattened (another axis already holds one of their dimensions): each is
exchanged as it lies with ``lax.ppermute``, the one collective this compiler
runs beside compute by default (``ShardedWeightUpdate._exchange_mean``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ....framework import flags as _flags
from ....optimizer import Optimizer
from ...collective import quantized_psum_scatter_mean
from ..grad_buckets import DEFAULT_BUCKET_BYTES, build_bucket_plan


# the scope every data-parallel gradient reduce is traced under: the engine
# finds them by it in the compiled step's text (``dp_reduce_leaves``)
DP_REDUCE_SCOPE = "dp_reduce"


class ShardedWeightUpdate:
    """ZeRO-1 weight-update sharding over one mesh axis.

    The optimizer-state layout is per-bucket FLAT arrays of global shape
    ``(padded,)`` sharded ``P(axis)`` — each replica physically holds
    ``padded/dp`` elements per moment. ``apply`` runs inside a shard_map
    body: bucket grads (reverse-backward order) → reduce-scatter (optionally
    int8-quantized with error feedback) → elementwise rule on the local shard
    → all-gather updated params.

    Only ELEMENTWISE update rules are eligible (``Optimizer._elementwise_rule``
    — LAMB/LARS need full-param norms and fall back to the replicated path).

    ``flat`` says what the mesh holds beside ``axis``. Nothing (True): every
    leaf is whole on every replica, so the buckets are flattened and the
    state lives bucket-flat, 1/dp a replica, as above. 'mp' (False): a leaf's
    dimension is already taken, so a bucket is its leaves as they lie, in the
    same reverse-backward order; each is summed over the replicas by pairwise
    exchange and updated whole, with the optimizer's own per-leaf state.
    """

    def __init__(self, optimizer, params, axis: str, nranks: int, flat=True):
        self.optimizer = optimizer
        self.params = list(params)
        self.axis = axis
        self.nranks = int(nranks)
        self.flat = bool(flat)
        self.quantized = bool(_flags.flag("FLAGS_quantized_allreduce", False))
        self.block = int(_flags.flag("FLAGS_quantized_allreduce_block", 128))
        self.error_feedback = self.quantized and bool(
            _flags.flag("FLAGS_quantized_allreduce_error_feedback", False)
        )

        def plr_of(p):
            if hasattr(p, "optimize_attr"):
                return p.optimize_attr.get("learning_rate", 1.0)
            return 1.0

        self.bucket_bytes = int(
            _flags.flag("FLAGS_dp_bucket_bytes") or DEFAULT_BUCKET_BYTES)
        self.plan = build_bucket_plan(
            self.params,
            nranks=self.nranks,
            bucket_bytes=self.bucket_bytes,
            block=self.block,
            wd_of=optimizer._wd_on,
            plr_of=plr_of,
        )
        # accumulator keys per bucket (probe the rule's state layout)
        self._keys = []
        for b in self.plan.buckets:
            probe = optimizer._init_accums(jnp.zeros((1,), b.dtype))
            self._keys.append(tuple(sorted(probe)))

    # -- enablement --------------------------------------------------------
    @staticmethod
    def maybe_build(optimizer, params, mesh, dp_axes, grad_accumulate=1):
        """Return a ShardedWeightUpdate when the mesh has ONE data-parallel
        axis of size > 1, alone or beside 'mp', and nothing shards a leaf, its
        gradient or its state over that axis; else None (the caller falls
        back to the replicated GSPMD update)."""
        if not _flags.flag("FLAGS_shard_weight_update", True):
            return None
        if grad_accumulate and int(grad_accumulate) > 1:
            return None
        if not params:
            return None
        if not getattr(optimizer, "_elementwise_rule", False):
            return None
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_present = [a for a in dp_axes if sizes.get(a, 1) > 1]
        other = [a for a, s in sizes.items() if a not in tuple(dp_axes) and s > 1]
        if len(dp_present) != 1 or any(a != "mp" for a in other):
            return None
        axis, n = dp_present[0], sizes[dp_present[0]]
        if other and n & (n - 1):
            return None  # the pairwise exchange halves a power of two

        def over_dp(spec):
            # a Megatron pspec names 'mp'; one that names the dp axis itself
            # (ZeRO-2/3 layouts on a mesh without a 'sharding' axis) leaves
            # the reduction to the partitioner
            return spec is not None and any(
                axis in (s if isinstance(s, (tuple, list)) else (s,))
                for s in tuple(spec))

        if any(over_dp(getattr(p, k, None)) for p in params
               for k in ("pspec", "grad_pspec", "opt_state_pspec")):
            return None
        return ShardedWeightUpdate(optimizer, params, axis, n, flat=not other)

    # -- state (global arrays, engine-resident) ----------------------------
    def state_specs(self, state):
        if not self.flat:
            # the optimizer's own per-leaf state, whole on every replica (what
            # 'mp' holds of a leaf is the partitioner's: the specs of the
            # engine's map name its manual axis only)
            return jax.tree_util.tree_map(lambda _: P(), state)
        specs = {
            "t": P(),
            "accums": [
                {k: P(self.axis) for k in keys} for keys in self._keys
            ],
            "ef": [P(self.axis, None) for _ in self.plan.buckets]
            if self.error_feedback else [],
        }
        return specs

    def init_state(self, mesh):
        """Pack the optimizer's per-param accumulators (or cold-start zeros)
        into per-bucket flat arrays placed sharded over the dp axis."""
        from ....core import lazy as _lazy

        opt = self.optimizer
        accums = []
        for bi, b in enumerate(self.plan.buckets):
            have = [bool(opt._accumulators.get(id(self.params[i])))
                    for i in b.indices]
            if any(have):
                # warm/restore: pack per-param state (init missing ones)
                per_key = {}
                for k in self._keys[bi]:
                    parts = []
                    for i in b.indices:
                        p = self.params[i]
                        st = opt._state(p)
                        if not st:
                            st.update(opt._init_accums(_lazy.concrete(p._data)))
                        parts.append(_lazy.concrete(st[k]))
                    per_key[k] = self.plan.flatten(b, parts)
                flats = per_key
            else:
                flats = opt._init_accums(jnp.zeros((b.padded,), b.dtype))
            accums.append({
                k: jax.device_put(v, NamedSharding(mesh, P(self.axis)))
                for k, v in flats.items()
            })
        state = {
            "t": jnp.asarray(float(opt._step_count + 1), jnp.float32),
            "accums": accums,
            "ef": [
                jax.device_put(
                    jnp.zeros((self.nranks, b.padded), jnp.float32),
                    NamedSharding(mesh, P(self.axis, None)),
                )
                for b in self.plan.buckets
            ] if self.error_feedback else [],
        }
        return state

    def sync_back(self, state):
        """Unpack the bucket-flat state into the optimizer's per-param
        accumulators (checkpointing / inspection). The flats are global
        arrays; on a multihost mesh call this only where they are fully
        addressable. Slices are materialized into fresh single-device
        buffers: a lazily-sliced view of the dp-sharded flat keeps a device
        sharding spanning the mesh, and downstream consumers (orbax save,
        donation) must see plain owned arrays."""
        opt = self.optimizer
        for bi, b in enumerate(self.plan.buckets):
            for k, flat in state["accums"][bi].items():
                host = np.asarray(flat)
                for pos, i in enumerate(b.indices):
                    p = self.params[i]
                    off, sz = b.offsets[pos], b.sizes[pos]
                    opt._state(p)[k] = jnp.asarray(
                        host[off:off + sz].reshape(b.shapes[pos])
                    )

    # -- the update (inside shard_map over ``self.axis``) -------------------
    def _exchange_mean(self, g):
        """Mean of ``g`` over the replicas by pairwise exchange: log2(dp)
        rounds of one ``ppermute`` with the partner whose index differs in
        one bit, and a local add. Both partners add the same two arrays, so
        every replica ends with the same bits whatever dp is; at dp 2 it is
        one transfer of the gradient each way, the bytes of an all-reduce.
        XLA:TPU schedules a collective-permute as a start/done pair with the
        rest of the backward pass between them, which it does not do for an
        all-reduce or a reduce-scatter (PERF.md section 6, PR 30)."""
        n = self.nranks
        bit = 1
        while bit < n:
            g = g + lax.ppermute(g, self.axis, [(i, i ^ bit) for i in range(n)])
            bit *= 2
        return g / n

    def _apply_leaves(self, p_arrays, grads, state, lr):
        """Beside 'mp': bucket by bucket in reverse order of the backward
        pass. A bucket that is ONE leaf over the bucket cap (a weight matrix)
        is exchanged as it lies, started behind the matmul that made it. The
        leaves of every other bucket (biases, norms) travel STACKED with the
        model's other leaves of their shape and layout, one transfer a shape
        at the end of the backward pass: the chip's transfers queue, so a
        bias sent behind its layer's matrices is done only when they are, and
        the compiler, which takes a small transfer for instant, waits for it
        there (1 ms for each of 128 leaves: PERF.md section 6, PR 30). Then
        the optimizer's own per-leaf update of step k on step k's gradient."""
        grads = list(grads)
        alone, stacks = [], {}  # stacks: (shape, dtype, layout) -> leaves
        for b in self.plan.buckets:
            matrix = len(b.indices) == 1 and b.size * b.itemsize > self.bucket_bytes
            for i in b.indices:
                if grads[i] is None:
                    continue
                if matrix:
                    alone.append([i])
                else:
                    layout = str(getattr(self.params[i], "pspec", None))
                    stacks.setdefault(
                        (grads[i].shape, str(grads[i].dtype), layout), []).append(i)
        with jax.named_scope(DP_REDUCE_SCOPE):
            for idx in alone + list(stacks.values()):  # one transfer each
                if len(idx) == 1:
                    grads[idx[0]] = self._exchange_mean(grads[idx[0]])
                    continue
                whole = self._exchange_mean(jnp.stack([grads[i] for i in idx]))
                for row, i in enumerate(idx):
                    grads[i] = whole[row]
        return self.optimizer._functional_update(
            p_arrays, grads, state, lr, params=self.params)

    def apply(self, p_arrays, grads, state, lr):
        """(full replicated params, local grads, local state, lr) → (new full
        params, new state). Traced inside shard_map over ``self.axis``;
        collectives are the real exchange / reduce-scatter / all-gather."""
        if not self.flat:
            return self._apply_leaves(p_arrays, grads, state, lr)
        opt = self.optimizer
        axis, n = self.axis, self.nranks
        ridx = lax.axis_index(axis)
        new_params = list(p_arrays)
        new_accums, new_efs = [], []
        t = state["t"]
        for bi, b in enumerate(self.plan.buckets):
            flat = self.plan.flatten(b, [grads[i] for i in b.indices])
            gf = flat.astype(jnp.float32)
            if self.quantized:
                if self.error_feedback:
                    gf = gf + state["ef"][bi].reshape(-1)
                with jax.named_scope(DP_REDUCE_SCOPE):
                    gshard, err = quantized_psum_scatter_mean(
                        gf, axis, n, self.block)
                if self.error_feedback:
                    new_efs.append(err.reshape(1, -1))
            else:
                with jax.named_scope(DP_REDUCE_SCOPE):
                    gshard = lax.psum_scatter(
                        gf, axis, scatter_dimension=0, tiled=True
                    ) / n
            pflat = self.plan.flatten(b, [p_arrays[i] for i in b.indices])
            s = self.plan.shard_size(b)
            pshard = lax.dynamic_slice_in_dim(pflat, ridx * s, s)
            g = opt._regularize_arr(pshard, gshard.astype(pshard.dtype))
            wd = b.wd_scale
            if wd is None:  # mixed decay gates: per-element vector
                wd = lax.dynamic_slice_in_dim(self.plan.wd_vector(b), ridx * s, s)
            new_pshard, new_st = opt._rule(
                pshard, g, state["accums"][bi], lr * b.plr, t, wd
            )
            pnew = lax.all_gather(new_pshard.astype(b.dtype), axis, tiled=True)
            for i, arr in zip(b.indices, self.plan.unflatten(b, pnew)):
                new_params[i] = arr.astype(p_arrays[i].dtype)
            new_accums.append(new_st)
        return new_params, {"t": t + 1.0, "accums": new_accums, "ef": new_efs}

    # -- analytic per-step wire accounting ---------------------------------
    def step_counters(self):
        if not self.flat:
            return {"dp_buckets": len(self.plan)}
        return {
            "dp_sync_bytes": self.plan.sync_bytes("reduce_scatter", self.quantized),
            "dp_gather_bytes": self.plan.gather_bytes(),
            "dp_buckets": len(self.plan),
            "dp_reduce_scatters": len(self.plan),
        }


class HybridParallelOptimizer:
    """Wraps the user optimizer for hybrid-parallel training (reference
    hybrid_parallel_optimizer.py:170). Sharding-stage-1 state specs apply
    when sharding_degree > 1; pure-DP groups get the ZeRO-1 sharded weight
    update automatically when the train step is built by the distributed
    engine (see ShardedWeightUpdate.maybe_build)."""

    def __init__(self, optimizer: Optimizer, hcg=None, strategy=None):
        self._inner_opt = optimizer
        self._hcg = hcg
        self._strategy = strategy
        # apply sharding-stage1 state specs when sharding_degree > 1
        if hcg is not None and hcg.get_sharding_parallel_world_size() > 1:
            from ..meta_parallel.sharding import ShardingOptimizerStage1

            self._inner_opt = ShardingOptimizerStage1(optimizer, hcg=hcg)

    def __getattr__(self, item):
        return getattr(self._inner_opt, item)

    def step(self):
        self._inner_opt.step()

    def clear_grad(self, *a, **k):
        self._inner_opt.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        return self._inner_opt.minimize(loss)

    def state_dict(self):
        return self._inner_opt.state_dict()

    def set_state_dict(self, sd):
        return self._inner_opt.set_state_dict(sd)
