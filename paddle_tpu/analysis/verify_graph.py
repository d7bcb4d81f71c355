"""Lazy-graph IR verifier (``FLAGS_lazy_verify``).

A structural pass over the pending ``_Graph`` in ``core/lazy.py`` run
immediately before dispatch. The graph's wiring descriptors, leaf table and
cache-signature parts are built INCREMENTALLY at record time (PR 6) — fast,
but a single bookkeeping slip there turns into a wrong executable served
from the flush cache or a donated-and-still-referenced buffer, i.e. silent
corruption or a nondeterministic crash far from the bug. This pass
re-derives every incremental structure from ground truth (the nodes and
their live input objects) and cross-checks:

* **acyclicity / topological wiring** — every ``("n", gix, out_ix)``
  descriptor references a STRICTLY EARLIER node (the graph is append-only;
  a forward or self reference is a cycle) and ``out_ix < nodes[gix].n_out``;
* **leaf-table consistency** — ``leaves`` / ``leaf_pos`` / ``leaf_avals``
  agree, every ``("l", j)`` descriptor is in range, and ``direct_uses``
  matches an actual recount of leaf occurrences (the donation mask's
  refcount budget is built from it);
* **donation-mask soundness** — every donated leaf index is a live,
  non-deleted ``jax.Array`` and the frame-isolated refcount test still
  proves it dead (nothing outside the graph references it); a donated leaf
  that a user alias still reaches would be destroyed under them;
* **signature determinism** — the cache signature re-derived from the wired
  graph equals the incrementally-memoized one (``keyparts`` +
  ``leaf_avals``), so the executable cache can never serve a stale program;
* **deferred-check bookkeeping** — entries queued for the async runtime's
  off-critical-path NaN scan / memory census are well-formed.

Violations raise :class:`GraphInvariantError` naming the offending node
(index + op name) and rule. The disabled path costs one flag probe per
flush (pinned by a tier-1 tripwire).
"""
from __future__ import annotations

from typing import Optional, Sequence

__all__ = ["GraphInvariantError", "verify_before_dispatch", "verify_graph"]


class GraphInvariantError(RuntimeError):
    """A lazy-graph structural invariant does not hold. Carries the rule
    name and (when attributable) the offending node's index and op name so
    tests and post-mortems can pin the exact corruption."""

    def __init__(self, rule: str, message: str,
                 node_index: Optional[int] = None,
                 op_name: Optional[str] = None):
        loc = ""
        if node_index is not None:
            loc = f" [node {node_index}" + (f" ({op_name})" if op_name else "") + "]"
        super().__init__(f"lazy-graph invariant violated: {rule}{loc}: {message}")
        self.rule = rule
        self.node_index = node_index
        self.op_name = op_name


def _fail(rule, message, node_index=None, op_name=None):
    raise GraphInvariantError(rule, message, node_index, op_name)


def _op_name(node) -> str:
    try:
        return str(node.key[0])
    except Exception:
        return "?"


def verify_graph(g) -> None:
    """Check the wiring/leaf-table/signature invariants of a pending
    ``_Graph`` (donation and deferred state are flush-scoped — see
    :func:`verify_before_dispatch` for the full pre-dispatch pass)."""
    from ..core import lazy as lazy_mod

    nodes = g.nodes
    n_nodes = len(nodes)
    leaves = g.leaves
    n_leaves = len(leaves)

    if not (len(g.descs) == len(g.keyparts) == n_nodes):
        _fail(
            "wiring",
            f"per-node tables out of step: {n_nodes} nodes, "
            f"{len(g.descs)} descriptors, {len(g.keyparts)} signature parts",
        )
    if not (len(g.leaf_avals) == n_leaves == len(g.leaf_pos)):
        _fail(
            "leaf-table",
            f"{n_leaves} leaves vs {len(g.leaf_avals)} leaf avals vs "
            f"{len(g.leaf_pos)} leaf positions",
        )
    for j in range(n_leaves):
        if g.leaf_pos.get(id(leaves[j])) != j:
            _fail(
                "leaf-table",
                f"leaf {j} is not indexed at its own position "
                f"(leaf_pos says {g.leaf_pos.get(id(leaves[j]))!r})",
            )

    recount: dict = {}
    for i, node in enumerate(nodes):
        name = _op_name(node)
        if node.gix != i:
            _fail("wiring", f"node.gix={node.gix} disagrees with position", i, name)
        if node.graph is not g:
            _fail("wiring", "node does not belong to this graph epoch", i, name)
        if node.out_refs is None or len(node.out_refs) != node.n_out:
            _fail(
                "wiring",
                f"{0 if node.out_refs is None else len(node.out_refs)} output "
                f"refs for n_out={node.n_out}", i, name,
            )
        descs = g.descs[i]
        inputs = node.inputs
        if len(descs) != len(inputs):
            _fail(
                "wiring",
                f"{len(descs)} descriptors for {len(inputs)} inputs", i, name,
            )
        for d, x in zip(descs, inputs):
            if d[0] == "n":
                _, gix, out_ix = d
                if not (0 <= gix < i):
                    _fail(
                        "acyclicity",
                        f"input references node {gix} — not strictly earlier "
                        "in the append-only order (cycle or dangling wire)",
                        i, name,
                    )
                if not (0 <= out_ix < nodes[gix].n_out):
                    _fail(
                        "wiring",
                        f"input output-index {out_ix} out of range for node "
                        f"{gix} (n_out={nodes[gix].n_out})", i, name,
                    )
                if not (isinstance(x, lazy_mod.LazyArray) and x._concrete is None):
                    _fail(
                        "wiring",
                        f"descriptor says node-output {gix}:{out_ix} but the "
                        "stored input is not a pending LazyArray", i, name,
                    )
                if x._node is not nodes[gix] or x._idx != out_ix:
                    _fail(
                        "wiring",
                        f"pending input wired to node {gix}:{out_ix} but the "
                        "LazyArray points elsewhere", i, name,
                    )
            elif d[0] == "l":
                j = d[1]
                if not (0 <= j < n_leaves):
                    _fail(
                        "leaf-table",
                        f"input references leaf {j} of {n_leaves} (dangling leaf)",
                        i, name,
                    )
                if leaves[j] is not x:
                    _fail(
                        "leaf-table",
                        f"leaf {j} in the table is not the object this node "
                        "recorded as its input", i, name,
                    )
                recount[id(x)] = recount.get(id(x), 0) + 1
            else:
                _fail("wiring", f"unknown descriptor kind {d[0]!r}", i, name)

    tracked = {k: v for k, v in g.direct_uses.items() if v}
    if recount != tracked:
        bad = next(
            i for i in (set(recount) | set(tracked))
            if recount.get(i, 0) != tracked.get(i, 0)
        )
        jx = next(
            (j for j in range(n_leaves) if id(leaves[j]) == bad), None
        )
        _fail(
            "leaf-table",
            f"direct_uses for leaf {'?' if jx is None else jx} says "
            f"{tracked.get(bad, 0)} occurrence(s) but a recount of the "
            f"wiring gives {recount.get(bad, 0)} — the donation refcount "
            "budget would be wrong",
        )

    # signature determinism: re-derive what record() memoized incrementally
    for i, node in enumerate(nodes):
        if g.keyparts[i] != (node.key, tuple(g.descs[i])):
            _fail(
                "signature",
                "memoized signature part disagrees with the wired graph — "
                "the flush cache would key this program incorrectly",
                i, _op_name(node),
            )
    for j in range(n_leaves):
        if g.leaf_avals[j] != lazy_mod._leaf_sig(leaves[j]):
            _fail(
                "signature",
                f"memoized aval for leaf {j} disagrees with the live leaf "
                f"({g.leaf_avals[j]!r} vs {lazy_mod._leaf_sig(leaves[j])!r})",
            )


def _verify_donation(g, donate_ix: Sequence[int]) -> None:
    """The donation mask must only name leaves that are provably dead after
    this flush. Re-runs the frame-isolated refcount test from the live
    tables; a donated leaf that is still user-referenced (or that is not a
    real device buffer) fails here instead of being destroyed under the
    holder."""
    import jax

    from ..core import lazy as lazy_mod

    leaves = g.leaves
    for j in donate_ix:
        if not (0 <= j < len(leaves)):
            _fail("donation", f"donated leaf index {j} of {len(leaves)}")
        x = leaves[j]
        if not isinstance(x, jax.Array):
            _fail("donation", f"donated leaf {j} is not a jax.Array ({type(x).__name__})")
        try:
            if x.is_deleted():
                _fail("donation", f"donated leaf {j} is already deleted")
        except AttributeError:
            pass
        # a donated leaf that a pending node ALSO consumes is fine (one
        # executable, XLA schedules the read before the alias) — but its
        # only remaining owners must be the graph's own input lists, which
        # the frame-isolated refcount recheck below proves
        x = None
    if donate_ix:
        recheck = lazy_mod._donation_mask(
            leaves, {id(leaves[j]) for j in donate_ix}, g.direct_uses
        )
        stale = set(donate_ix) - set(recheck)
        if stale:
            j = sorted(stale)[0]
            _fail(
                "donation",
                f"leaf {j} is marked for donation but something outside the "
                "pending graph still references it (refcount above the "
                "graph-only budget) — donating would corrupt the live alias",
            )


def _verify_deferred(deferred) -> None:
    """The async runtime's deferred NaN-scan / census queue: each entry is
    ``(span, payload, census, results)`` with payload either None
    (census-only) or the 6-tuple the deferred ``_nan_check`` replays."""
    if not deferred:
        return
    for k, entry in enumerate(deferred):
        if not (isinstance(entry, tuple) and len(entry) == 4):
            _fail(
                "deferred",
                f"queued entry {k} is not a (span, payload, census, results) "
                f"tuple ({type(entry).__name__})",
            )
        payload = entry[1]
        if payload is None:
            continue
        if not (isinstance(payload, tuple) and len(payload) == 6):
            _fail(
                "deferred",
                f"entry {k} carries a malformed NaN-scan payload "
                f"(len {len(payload) if isinstance(payload, tuple) else '?'}, "
                "want 6: keys/fns/live/results/leaves/descs)",
            )
        keys, fns, live, results, _leaves, descs = payload
        if not (len(keys) == len(fns) == len(descs)):
            _fail(
                "deferred",
                f"entry {k}: {len(keys)} op keys vs {len(fns)} fns vs "
                f"{len(descs)} wiring rows",
            )
        if results is not None and len(live) != len(results):
            _fail(
                "deferred",
                f"entry {k}: {len(live)} live slots vs {len(results)} results",
            )


def verify_before_dispatch(g, donate_ix: Sequence[int] = (),
                           deferred=None) -> None:
    """The full pre-dispatch pass ``_flush_impl`` runs under
    ``FLAGS_lazy_verify``: structural graph invariants, donation-mask
    soundness for THIS flush, and deferred-queue bookkeeping. Bumps the
    ``lazy_verify_passes`` counter so the zero-cost tripwire can assert the
    disabled path never reaches here."""
    from ..core.dispatch import _prof

    verify_graph(g)
    _verify_donation(g, donate_ix)
    _verify_deferred(deferred)
    _prof().counter_inc("lazy_verify_passes")
