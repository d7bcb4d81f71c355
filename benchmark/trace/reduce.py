"""From intervals to numbers. Every function takes plain lists of
``[name, start_ns, dur_ns]`` (see ``capture.py``) so that it can be checked
on the recorded trace under ``trace/recorded/``."""
from __future__ import annotations

import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|allgather|allreduce", re.I)


_HLO = re.compile(r"^%?([A-Za-z_][\w\-.]*) = \(?(\w+)\[([\d,]*)\]")


def short_name(name: str) -> str:
    """A device operation's name as the trace gives it is its whole HLO line.
    Keep the instruction's name without its number and the type and shape
    of its (first) result: ``%fusion.2929 = bf16[2,2048,2048]{..} fusion(..``
    -> ``fusion_bf16_2_2048_2048``; operations of one kind and shape then
    sum together and the name survives a recompile."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:64]
    base = re.sub(r"\.\d+", "", m.group(1))  # fusion.49.remat2 -> fusion.remat2
    dims = m.group(3).replace(",", "_")
    return f"{base}_{m.group(2)}_{dims}".rstrip("_")[:64]


def leaves(events):
    """Drop operations that hold others (a ``while`` and its body are both
    on the line): what is left are the operations that did the work, and
    their durations add up to the busy time."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    keep = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < e[1] + e[2] and nxt[1] + nxt[2] <= e[1] + e[2]:
            continue  # the next event lies inside this one: a parent
        keep.append(e)
    return keep


def clip(events, t0, t1):
    """Events cut to [t0, t1]; those outside it dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(events):
    """Merged [start, end) intervals of the events, sorted."""
    merged = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events) -> int:
    return sum(e - s for s, e in union(events))


def idle_share(events, t0, t1) -> float:
    """1 - (time in which some operation ran) / (traced window)."""
    if t1 <= t0:
        raise ValueError("empty window")
    return 1.0 - busy_ns(clip(events, t0, t1)) / (t1 - t0)


def idle_gaps(events, t0, t1):
    """[start, end) stretches of [t0, t1] in which no operation ran."""
    gaps, at = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if t1 > at:
        gaps.append([at, t1])
    return gaps


def attribute(gap, host_spans, unknown="unattributed-host") -> str:
    """Name of the innermost (shortest) host span that covers at least half
    of the gap. ``host_spans`` are [name, start_ns, dur_ns] on the trace's
    clock."""
    best, best_len = unknown, None
    for name, s, d in host_spans:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover * 2 >= gap[1] - gap[0] and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def gap_breakdown(events, t0, t1, host_spans, top=5):
    """The longest gaps by what the host was doing, then the sum per name.

    One sweep over the gaps and the spans, both in order of time: a gap is
    held only against the spans that touch it. A serving trace has tens of
    thousands of gaps (most a few nanoseconds, between two operations) and a
    51 s window thousands of spans; every gap against every span took
    minutes there, and the driver stopped that traced run at its limit of
    360 s (PR 23, BENCHMARK_REFUSED.md)."""
    gaps = idle_gaps(events, t0, t1)
    spans = sorted((s for s in host_spans if s[1] < t1 and s[1] + s[2] > t0),
                   key=lambda s: s[1])
    named, live, j = [], [], 0
    for g in gaps:
        while j < len(spans) and spans[j][1] < g[1]:
            live.append(spans[j])
            j += 1
        live = [s for s in live if s[1] + s[2] > g[0]]
        named.append((attribute(g, live), g[1] - g[0]))
    longest = sorted(named, key=lambda x: -x[1])[:top]
    sums = defaultdict(int)
    for name, ns in named:
        sums[name] += ns
    rows = [[n, ns / 1e9] for n, ns in longest]
    rows += [["sum:" + n, ns / 1e9] for n, ns in
             sorted(sums.items(), key=lambda x: -x[1])[:10 - len(rows)]]
    return rows


def top_ops(per_device, top=10):
    """Summed duration by ``short_name`` over the leaf operations of each
    device's line (leaves are taken per line: an operation of one chip that
    happens to lie inside another chip's is no parent of it), averaged over
    the devices."""
    sums = defaultdict(int)
    for events in per_device:
        for name, _, d in leaves(events):
            sums[short_name(name)] += d
    n = max(len(per_device), 1)
    return [[k, ns / n / 1e9] for k, ns in sorted(sums.items(), key=lambda x: -x[1])[:top]]


def instruction(line: str) -> tuple:
    """(name, opcode) of a device operation. The trace names an operation
    by its whole HLO line, operands and all: ``%fusion.7 = bf16[8]{0}
    fusion(bf16[8]{0} %all-gather.3), kind=kLoop``. What the operation IS
    stands before the operands: its name left of `` = `` and its opcode
    right of the result's shape. A line cut short gives an empty opcode."""
    name, sep, rest = line.partition(" = ")
    if not sep:
        return line.lstrip("%"), ""
    if rest.startswith("("):  # a tuple of results: skip to its closing bracket
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    op, bracket, _ = rest.partition("(")
    return name.lstrip("%"), op if bracket and re.fullmatch(r"[\w\-]+", op) else ""


def is_collective(line: str) -> bool:
    """Whether the operation itself is a collective. Never asked of the whole
    line: a matmul fusion that CONSUMES ``%all-gather.3`` names it among its
    operands and is no collective."""
    name, op = instruction(line)
    return bool(COLLECTIVE.search(name) or COLLECTIVE.search(op))


def exposed_collective_ns(events) -> int:
    """Time in which a collective runs on the device and no other operation
    does: the union of the collectives' intervals less the union of the
    other operations' leaves (a ``while`` that holds collectives in its body
    covers them on the line and is no work of its own: the body's other
    operations lie inside it, so it is no leaf among them)."""
    coll = union([e for e in events if is_collective(e[0])])
    rest = union(leaves([e for e in events if not is_collective(e[0])]))
    exposed, j = 0, 0
    for s, e in coll:
        at = s
        while j < len(rest) and rest[j][1] <= at:
            j += 1
        k = j
        while k < len(rest) and rest[k][0] < e:
            if rest[k][0] > at:
                exposed += rest[k][0] - at
            at = max(at, rest[k][1])
            k += 1
        if e > at:
            exposed += e - at
    return exposed


def total_ns(events, pattern) -> tuple:
    """(summed duration, count) of the events whose name matches."""
    rx = re.compile(pattern)
    hit = [d for name, _, d in events if rx.search(name)]
    return sum(hit), len(hit)
