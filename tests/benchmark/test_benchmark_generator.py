"""The traffic generator: the seed changes the order, never the load."""
import json
import threading
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmark import generator as G

CHAT = json.loads((Path(G.__file__).parent / "traffic" / "chat-sat.json").read_text())
SEEDS = (1, 2_147_483_659, 3_000_000_019)


@pytest.mark.parametrize("seconds", [10, 30])
def test_same_multiset_any_seed_other_order(seconds):
    scheds = [G.build_schedule(CHAT, seconds, s, 50304) for s in SEEDS]
    ref = scheds[0]
    n_win = round(CHAT["rate_per_s"] * seconds)
    for s in scheds:
        w = s.in_window
        assert w.sum() == n_win
        assert Counter(zip(s.prompt_len[w], s.out_len[w])) == \
            Counter(zip(ref.prompt_len[ref.in_window], ref.out_len[ref.in_window]))
        assert Counter(zip(s.prompt_len[~w], s.out_len[~w])) == \
            Counter(zip(ref.prompt_len[~ref.in_window], ref.out_len[~ref.in_window]))
        gaps = np.diff(np.concatenate([s.due[w], [seconds]]))
        assert np.allclose(sorted(gaps), sorted(np.diff(np.concatenate(
            [ref.due[ref.in_window], [seconds]]))))
        assert gaps.sum() == pytest.approx(seconds)       # due times fill the window
        assert s.due[w].min() == 0 and s.due[w].max() < seconds
        assert s.due[~w].min() == pytest.approx(-CHAT["ramp_s"]) and s.due[~w].max() < 0
        assert [len(p) for p in s.prompts] == list(s.prompt_len)
    assert not np.array_equal(scheds[0].prompt_len, scheds[1].prompt_len)
    assert not np.array_equal(scheds[0].prompts[0], scheds[1].prompts[0])
    same = G.build_schedule(CHAT, seconds, SEEDS[1], 50304)
    assert all(np.array_equal(a, b) for a, b in zip(same.prompts, scheds[1].prompts))


def test_rounds_balance_the_stream_in_time():
    """Every 16 consecutive requests hold short and long prompts in the mix's
    proportions: the heaviest round offers under 1.3 x the lightest, where a
    plain permutation of this heavy-tailed mix offers over 2 x."""
    def round_sums(traffic):
        s = G.build_schedule(traffic, 32, 5, 50304)
        p = s.prompt_len[s.in_window]
        assert len(p) == 144
        return [int(p[i:i + 16].sum()) for i in range(0, 144, 16)]
    balanced = round_sums(CHAT)
    plain = round_sums({k: v for k, v in CHAT.items() if k != "round"})
    assert max(balanced) < 1.3 * min(balanced)
    assert max(plain) > 2.0 * min(plain)
    assert sum(balanced) == sum(plain)


def test_lengths_follow_the_file():
    s = G.build_schedule(CHAT, 45, 5, 50304)
    assert s.prompt_len.min() >= 16 and s.prompt_len.max() <= 1024
    assert s.out_len.min() >= 8 and s.out_len.max() <= 512
    assert (s.prompt_len + s.out_len).max() <= 1536
    assert abs(np.median(s.prompt_len[s.in_window]) - 192) <= 4
    assert abs(np.median(s.out_len[s.in_window]) - 128) <= 3


def test_quantiles_kinds():
    assert list(G.quantiles({"dist": "linspace", "lo": 1024, "hi": 1792}, 4)) == [1024, 1280, 1536, 1792]
    assert list(G.quantiles({"dist": "const", "value": 32}, 3)) == [32, 32, 32]
    e = G.quantiles({"dist": "exponential", "mean": 2.0}, 1000)
    assert e.mean() == pytest.approx(2.0, rel=0.01)
    with pytest.raises(ValueError):
        G.quantiles({"dist": "zipf"}, 3)


@pytest.mark.parametrize("dist,n,want", [
    ({"dist": "lognormal", "median": 640, "sigma": 0.7, "lo": 128, "hi": 1408}, 7,
     [229, 368, 495, 640, 827, 1114, 1408]),
    ({"dist": "linspace", "lo": 3, "hi": 50}, 5, [3, 15, 26, 38, 50]),
    ({"dist": "const", "value": 9}, 2, [9, 9]),
    ({"dist": "exponential", "mean": 1.5}, 4,
     [0.20029708893678394, 0.7050054438686033, 1.4712438795175893, 3.1191623125197534]),
])
def test_the_four_plain_kinds_return_what_they_did(dist, n, want):
    """Values taken at the parent commit of PR 44 (ae673ab), which brought
    ``mixture``: bit for bit, the gaps too."""
    assert G.quantiles(dist, n).tolist() == want


SHORT = {"dist": "lognormal", "median": 384, "sigma": 0.6, "lo": 128, "hi": 1024}
LONG = {"dist": "lognormal", "median": 2560, "sigma": 0.1, "lo": 2048, "hi": 3072}
TWO_MODES = {"dist": "mixture", "parts": [{"weight": 0.75, **SHORT}, {"weight": 0.25, **LONG}]}


@pytest.mark.parametrize("weights,n,want", [
    ([0.75, 0.25], 8, [6, 2]),
    ([0.75, 0.25], 10, [8, 2]),          # 7.5 and 2.5: the tie goes to the earlier part
    ([0.75, 0.25], 1, [1, 0]),
    ([0.25, 0.75], 2, [1, 1]),           # 0.5 and 1.5: the earlier part again
    ([0.1, 0.2, 0.7], 10, [1, 2, 7]),    # decimals as the file states them, not as floats
    ([0.29, 0.71], 100, [29, 71]),
    ([0.5, 0.3, 0.2], 7, [4, 2, 1]),     # 3.5 2.1 1.4: one left over, to the largest remainder
    ([1.0], 5, [5]),
    ([0.75, 0.25], 0, [0, 0]),
])
def test_a_mixture_shares_its_requests_by_largest_remainder(weights, n, want):
    parts = [{"weight": w, "dist": "const", "value": 10 * i} for i, w in enumerate(weights)]
    got = G.quantiles({"dist": "mixture", "parts": parts}, n)
    assert [int((got == 10 * i).sum()) for i in range(len(weights))] == want
    assert got.dtype == np.int64 and len(got) == n


@pytest.mark.parametrize("dist,says", [
    ({"dist": "mixture", "parts": [{"weight": 0.5, **SHORT}, {"weight": 0.4, **LONG}]},
     "not shares that sum to 1"),
    ({"dist": "mixture", "parts": [{"weight": 1.5, **SHORT}, {"weight": -0.5, **LONG}]},
     "not shares that sum to 1"),
    ({"dist": "mixture", "parts": [{"weight": 1.0, **TWO_MODES}]}, "may not be 'mixture'"),
    ({"dist": "mixture", "parts": [{"weight": 0.5, **SHORT},
                                   {"weight": 0.5, "dist": "exponential", "mean": 9.0}]},
     "may not be 'exponential'"),
])
def test_a_mixture_that_is_none_is_refused_by_name(dist, says):
    with pytest.raises(ValueError, match=says):
        G.quantiles(dist, 8)


def test_two_modes_of_length_in_one_queue():
    """A mix of short and long prompts: the same multiset for any seed, both
    modes in every round of 16 in the mix's proportions, and the longest
    context known from the file alone."""
    # part after part, each the quantiles of its own share
    assert G.quantiles(TWO_MODES, 10).tolist() == (G.quantiles(SHORT, 8).tolist()
                                                   + G.quantiles(LONG, 2).tolist())
    traffic = {**CHAT, "rate_per_s": 4.0, "ramp_rate_per_s": 4.0, "prompt_len": TWO_MODES}
    a, b = (G.build_schedule(traffic, 16, s, 50304) for s in SEEDS[:2])
    for w in (a.in_window, ~a.in_window):
        assert Counter(zip(a.prompt_len[w], a.out_len[w])) == \
            Counter(zip(b.prompt_len[w], b.out_len[w]))
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    p = a.prompt_len[a.in_window]
    assert len(p) == 64 and int((p >= 2048).sum()) == 16 and p.max() <= 3072
    assert [int((p[i:i + 16] >= 2048).sum()) for i in range(0, 64, 16)] == [4, 4, 4, 4]
    longest_prompt, longest_ctx = G.longest(traffic, 16)
    assert longest_prompt == max(a.prompt_len) and \
        longest_ctx == max(a.prompt_len + a.out_len) > 2048


def test_open_loop_times_from_due_and_reports_lateness():
    traffic = {**CHAT, "rate_per_s": 40.0, "ramp_rate_per_s": 40.0, "ramp_s": 0.25,
               "prompt_len": {"dist": "const", "value": 4},
               "output_len": {"dist": "const", "value": 3}}
    sched = G.build_schedule(traffic, 1.0, 9, 100)
    lock, seen = threading.Lock(), []

    def submit(prompt, n):
        with lock:
            seen.append(len(prompt))
        if len(seen) == 15:
            time.sleep(0.3)                       # the generator is held up once

        def stream():
            for k in range(n):
                time.sleep(0.002)
                yield k
        return stream()

    loop = G.OpenLoop(sched, submit)
    t_open = time.monotonic() + sched.ramp_s + 0.02
    loop.start(t_open)
    served = loop.drain(10.0)
    st = G.window_stats(served, sched, t_open)
    assert st["attempted"] == 40 and st["failed"] == 0 and len(seen) == 50
    assert all(r.done and r.tokens == [0, 1, 2] for r in served)
    assert st["late"].max() >= 0.1                # the stall shows as lateness
    assert len(st["ttft"]) == 40 and (st["ttft"] > 0).all()
    # TTFT counts from the due time: requests behind the stall waited for it
    assert st["ttft"].max() >= 0.1


def test_failed_requests_are_counted():
    traffic = {**CHAT, "rate_per_s": 20.0, "ramp_s": 0.0,
               "prompt_len": {"dist": "const", "value": 4},
               "output_len": {"dist": "const", "value": 2}}
    sched = G.build_schedule(traffic, 0.5, 3, 100)
    calls = []

    def submit(prompt, n):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("shed")
        if len(calls) == 3:
            return iter([7])                      # one token short
        return iter(range(n))

    loop = G.OpenLoop(sched, submit)
    t_open = time.monotonic() + 0.02
    loop.start(t_open)
    st = G.window_stats(loop.drain(5.0), sched, t_open)
    assert st["attempted"] == 10 and st["failed"] == 2


# -- a pinned order (``order_seed`` in the traffic file, PR 32)
PINNED = {**CHAT, "order_seed": 32}


def _digest(s):
    import hashlib

    h = hashlib.sha256()
    for a in (s.due, s.prompt_len, s.out_len, s.in_window, np.concatenate(s.prompts)):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seconds", [10, 51])
def test_a_pinned_order_leaves_the_seed_the_token_ids_alone(seconds):
    a, b = (G.build_schedule(PINNED, seconds, s, 50304) for s in SEEDS[:2])
    for field_ in ("due", "prompt_len", "out_len", "in_window"):
        assert np.array_equal(getattr(a, field_), getattr(b, field_)), field_
    assert [len(p) for p in a.prompts] == list(a.prompt_len)
    assert not any(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts) if len(p) > 4)
    again = G.build_schedule(PINNED, seconds, SEEDS[0], 50304)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, again.prompts))


def test_another_order_seed_offers_the_same_multisets_in_another_order():
    a = G.build_schedule(PINNED, 30, 5, 50304)
    b = G.build_schedule({**PINNED, "order_seed": 33}, 30, 5, 50304)
    free = G.build_schedule(CHAT, 30, 5, 50304)
    for s in (b, free):
        for w, end in ((a.in_window, 30.0), (~a.in_window, 0.0)):
            assert np.array_equal(s.in_window, a.in_window)
            assert Counter(zip(s.prompt_len[w], s.out_len[w])) == \
                Counter(zip(a.prompt_len[w], a.out_len[w]))
            gaps = lambda x: sorted(np.diff(np.concatenate([x.due[w], [end]])))
            assert np.allclose(gaps(s), gaps(a))
        assert not np.array_equal(s.prompt_len, a.prompt_len)
        assert not np.array_equal(s.due, a.due)


@pytest.mark.parametrize("mix,vocab,seed,seconds,want", [
    ("chat-sat", 50304, 4_400_000_021, 51, "c99b001b2e158512"),
    ("chat-sat", 50304, 7, 10, "65685084ae37300d"),
    ("longanswer-pinned", 131072, 4_400_000_021, 51, "567f8da601818d88"),
    ("longanswer-pinned", 131072, 7, 10, "78bcb2957c22f1fd"),
    ("reasoning-pinned", 200064, 4_400_000_021, 51, "173c91a47a2e7137"),
    ("reasoning-pinned", 200064, 7, 10, "54ce59f1503f0c29"),
    ("toolturn-pinned", 65536, 4_400_000_021, 51, "59b4de9309872081"),
    ("toolturn-pinned", 65536, 7, 10, "81974e82cbe5499c"),
])
def test_every_serving_mix_offers_the_parent_s_schedule(mix, vocab, seed, seconds, want):
    """Digests of each serving mix's schedule (due times, lengths, window,
    token ids) taken at the parent commit of PR 44 (ae673ab): ``mixture``, the
    ``engine`` entry and the fit check moved no cell's traffic."""
    traffic = json.loads((Path(G.__file__).parent / "traffic" / f"{mix}.json").read_text())
    assert "engine" not in traffic
    assert _digest(G.build_schedule(traffic, seconds, seed, vocab)) == want


@pytest.mark.parametrize("seed,seconds,want", [
    (2_147_483_659, 51, "9be7bfc34dd328ab"), (7, 10, "65685084ae37300d")])
def test_without_the_key_the_schedule_is_the_parent_s(seed, seconds, want):
    """Digests of ``chat-sat``'s schedule (due times, lengths, token ids)
    recorded from the parent commit of PR 32 (c1947d8): the dense serving
    cell's traffic did not move when the key came."""
    assert "order_seed" not in CHAT
    assert _digest(G.build_schedule(CHAT, seconds, seed, 50304)) == want


def test_the_manifest_finds_the_pinned_mix_by_name():
    from benchmark.manifest import Manifest

    m = Manifest()
    assert m.validate() == []
    mine = [w for w in m.data["workloads"] if w["traffic"] == "longanswer-pinned"]
    assert [w["name"] for w in mine] == ["serve-xing4-longanswer-pinned"]
    traffic = m.traffic("longanswer-pinned")
    assert isinstance(traffic["order_seed"], int)
    a, b = (G.build_schedule(traffic, 51, s, 131072) for s in SEEDS[:2])
    assert np.array_equal(a.due, b.due) and np.array_equal(a.out_len, b.out_len)
    assert a.in_window.sum() == round(traffic["rate_per_s"] * 51)
    # no file or entry of the cell it replaces is left
    assert not (m.root / "traffic" / "longanswer.json").exists()
    assert "serve-xing4-longanswer\"" not in m.path.read_text()
