"""One general traffic generator, driven by a traffic file.

The seed never changes HOW MUCH a run offers, only which request comes when:
lengths and inter-arrival gaps are evenly spaced quantiles of the stated
distributions (stratified, not sampled), so every run of a mix offers the
same multiset of requests and of gaps. ``--seed`` permutes both and draws
the token ids. The ramp before the window and the window itself are built
apart, each with its own multisets, so the number of requests, prompt tokens
and output tokens due INSIDE the window is the same for every seed.

A traffic file may PIN the order with ``order_seed`` (an integer): rounds,
requests inside a round and gaps are then permuted by that number, for the
ramp and the window, and ``--seed`` draws the token ids alone, so every run of
the mix offers the same request of the same lengths at the same due time.
For a cell whose step follows the live rows (a routed model below its knee),
where the order of the arrivals would otherwise be the run-to-run spread.

Open loop: ``OpenLoop`` submits each request at its due time whether or not
earlier ones finished, times it from the due time, and reports how late it
ran. One consumer thread per live stream stamps tokens as the client's
iterator yields them.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from fractions import Fraction
from statistics import NormalDist

import numpy as np

_PAIRING = 0x5EED  # pairs prompt with output lengths; never the run's seed


def _shares(weights: list, n: int) -> list:
    """``n`` split in proportion to ``weights`` by largest remainder (a tie
    goes to the earlier part). The weights are read as the decimals the file
    states, so that 0.1 + 0.2 + 0.7 is 1 and 0.29 x 100 is 29."""
    exact = [Fraction(str(w)) for w in weights]
    if sum(exact) != 1 or min(exact) < 0:
        raise ValueError(f"mixture: the weights {weights} are not shares that sum to 1")
    whole = [int(w * n) for w in exact]
    by_rest = sorted(range(len(exact)), key=lambda i: whole[i] - exact[i] * n)
    for i in by_rest[:n - sum(whole)]:
        whole[i] += 1
    return whole


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles ((i+0.5)/n) of a distribution file entry:
    lognormal {median, sigma, lo, hi}, exponential {mean}, linspace {lo, hi},
    const {value}. Lengths (lognormal, linspace, const) come back as ints.

    mixture {parts: [{weight, dist, ...}, ...]} is lengths of several modes:
    part i gets its share of the ``n`` by largest remainder and gives that
    many quantiles OF ITS OWN, part after part; stratified like the others, so
    the multiset depends on ``n`` alone."""
    kind = dist["dist"]
    if kind == "mixture":
        parts = dist["parts"]
        for p in parts:
            if p["dist"] in ("mixture", "exponential"):
                raise ValueError(f"mixture: a part may not be {p['dist']!r} "
                                 "(lengths of one mode each: lognormal, linspace, const)")
        counts = _shares([p["weight"] for p in parts], n)
        return np.concatenate([quantiles(p, k) for p, k in zip(parts, counts)])
    u = (np.arange(n) + 0.5) / n
    if kind == "exponential":
        return -np.log1p(-u) * dist["mean"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.rint(x), dist["lo"], dist["hi"]).astype(np.int64)
    if kind == "linspace":
        return np.rint(np.linspace(dist["lo"], dist["hi"], n)).astype(np.int64)
    if kind == "const":
        return np.full(n, dist["value"], np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


@dataclass
class Schedule:
    due: np.ndarray          # seconds from window open; the ramp is negative
    prompt_len: np.ndarray
    out_len: np.ndarray
    in_window: np.ndarray    # bool: due inside the window
    prompts: list            # token ids, one int32 array per request
    seconds: float
    ramp_s: float


def _pairs(traffic: dict, n: int) -> tuple:
    """The fixed (prompt, output) lengths of a phase of ``n`` requests, in
    order of prompt quantile: no seed has a part in them."""
    plen = quantiles(traffic["prompt_len"], n)
    olen = quantiles(traffic["output_len"], n)[
        np.random.default_rng(_PAIRING).permutation(n)]
    return plen, olen


def _counts(traffic: dict, seconds: float) -> tuple:
    """Requests of the ramp and of the window; the ramp may offer more than
    the window does, to fill the engine fast."""
    rate = float(traffic["rate_per_s"])
    return (round(float(traffic.get("ramp_rate_per_s", rate)) * float(traffic["ramp_s"])),
            round(rate * seconds))


def longest(traffic: dict, seconds: float) -> tuple:
    """(longest prompt, longest context = prompt + answer) a run of
    ``seconds`` offers, ramp and window: what the engine's context has to
    hold, known from the file alone."""
    pairs = [_pairs(traffic, n) for n in _counts(traffic, seconds) if n]
    return (max(int(p.max()) for p, _ in pairs),
            max(int((p + o).max()) for p, o in pairs))


def _phase(traffic: dict, n: int, span: float, rng) -> tuple:
    """``n`` requests over ``span`` seconds: fixed multisets, seeded order.
    Gaps are scaled so that they sum to ``span`` exactly.

    The stream is balanced in time too: the fixed (prompt, output) pairs are
    sorted by prompt length and dealt like cards into rounds of ``round``
    requests, so every round holds short and long prompts in the mix's own
    proportions. The seed orders the rounds and the requests inside each.
    Which requests a busy engine gets to within the window is then nearly the
    same work for every seed, not a new draw from a heavy-tailed mix."""
    if n == 0:
        z = np.zeros(0, np.int64)
        return np.zeros(0), z, z
    plen, olen = _pairs(traffic, n)
    by_prompt = np.argsort(plen, kind="stable")
    rounds = max(1, n // int(traffic.get("round", n)))
    dealt = [by_prompt[r::rounds] for r in range(rounds)]
    order = np.concatenate([rng.permutation(dealt[r]) for r in rng.permutation(rounds)])
    gaps = quantiles({"dist": traffic["arrivals"], "mean": 1.0}, n)
    gaps = rng.permutation(gaps * (span / gaps.sum()))
    # a request is due where its gap STARTS: the first at the phase's start,
    # the last one gap before its end
    due = np.cumsum(gaps) - gaps
    return due, plen[order], olen[order]


def build_schedule(traffic: dict, seconds: float, seed: int, vocab: int) -> Schedule:
    ramp_s = float(traffic["ramp_s"])
    rng = np.random.default_rng([int(seed), 0xA221])
    # a mix that pins its order draws it from the file's number, not the run's
    order = rng if "order_seed" not in traffic else np.random.default_rng(
        [int(traffic["order_seed"]), 0xA221])
    n_ramp, n_win = _counts(traffic, seconds)
    rd, rp, ro = _phase(traffic, n_ramp, ramp_s, order)
    wd, wp, wo = _phase(traffic, n_win, seconds, order)
    due = np.concatenate([rd - ramp_s, wd])
    plen, olen = np.concatenate([rp, wp]), np.concatenate([ro, wo])
    inw = np.concatenate([np.zeros(n_ramp, bool), np.ones(n_win, bool)])
    prompts = [rng.integers(0, vocab, int(n), dtype=np.int32) for n in plen]
    return Schedule(due, plen, olen, inw, prompts, float(seconds), ramp_s)


@dataclass
class Served:
    """What the client saw of one request."""
    index: int
    due: float = 0.0         # absolute, host clock
    sent: float = 0.0
    tokens: list = field(default_factory=list)
    stamps: list = field(default_factory=list)
    error: str = ""
    done: bool = False


class OpenLoop:
    """Drives ``submit(prompt, max_new_tokens) -> iterable of tokens`` through
    a schedule. ``start(t_open)`` returns at once; ``drain(timeout)`` waits
    for every stream to end and returns the ``Served`` records."""

    def __init__(self, schedule: Schedule, submit, clock=time.monotonic):
        self.schedule, self.submit, self.clock = schedule, submit, clock
        self.served = [Served(i) for i in range(len(schedule.due))]
        self._consumers = []
        self._thread = None

    def start(self, t_open: float) -> None:
        self._thread = threading.Thread(
            target=self._generate, args=(t_open,), name="bench-generator",
            daemon=True)
        self._thread.start()

    def _generate(self, t_open: float) -> None:
        s, clock = self.schedule, self.clock
        for i in range(len(s.due)):
            rec = self.served[i]
            rec.due = t_open + float(s.due[i])
            wait = rec.due - clock()
            if wait > 0:
                time.sleep(wait)
            rec.sent = clock()
            try:
                stream = self.submit(s.prompts[i], int(s.out_len[i]))
            except Exception as e:  # refused at the door: counted as failed
                rec.error, rec.done = f"{type(e).__name__}: {e}", True
                continue
            t = threading.Thread(target=self._consume, args=(rec, stream),
                                 name=f"bench-client-{i}", daemon=True)
            self._consumers.append(t)
            t.start()

    def _consume(self, rec: Served, stream) -> None:
        clock, tokens, stamps = self.clock, rec.tokens, rec.stamps
        try:
            for tok in stream:
                stamps.append(clock())
                tokens.append(tok)
        except Exception as e:
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = True

    def drain(self, timeout: float) -> list:
        end = self.clock() + timeout
        self._thread.join(max(0.0, end - self.clock()))
        for t in list(self._consumers):
            t.join(max(0.0, end - self.clock()))
        return self.served


def window_stats(served: list, schedule: Schedule, t_open: float) -> dict:
    """Reduce the clients' stamps to the window's samples. Gaps: between
    consecutive tokens of one stream, pooled, counted when the later token
    fell inside the window. TTFT: from DUE time, for requests due inside the
    window. A request due inside it that errored, or has no first token or
    fewer tokens than asked by the end of the drain, is failed."""
    t_close = t_open + schedule.seconds
    gaps, gap_at, ttft, late, failed, attempted = [], [], [], [], 0, 0
    tokens_in_window = 0
    for rec in served:
        st = rec.stamps
        for a, b in zip(st, st[1:]):
            if t_open <= b <= t_close:
                gaps.append(b - a)
                gap_at.append(b - t_open)
        tokens_in_window += sum(t_open <= x <= t_close for x in st)
        if rec.sent:
            late.append(rec.sent - rec.due)
        if not schedule.in_window[rec.index]:
            continue
        attempted += 1
        want = int(schedule.out_len[rec.index])
        if rec.error or not rec.done or len(rec.tokens) != want:
            failed += 1
        if st:
            ttft.append(st[0] - rec.due)
    return {"gaps": np.asarray(gaps), "gap_at": np.asarray(gap_at), "ttft": np.asarray(ttft),
            "late": np.asarray(late), "attempted": attempted, "failed": failed,
            "tokens_in_window": tokens_in_window}
