"""The trace reduction, on the recorded trace (one training step of
train-xl-s2048 on a TPU v5 lite) and on hand-made intervals."""
import gzip
import json
from pathlib import Path

import pytest

from benchmark.manifest import Manifest
from benchmark.trace import reduce as R, summary

RECORDED = Path(R.__file__).parent / "recorded" / "train-xl-step.json.gz"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress(RECORDED.read_bytes()))


def test_recorded_busy_idle_and_leaves(recorded):
    t0, t1 = summary.window_ns(recorded)
    assert t1 - t0 == 249_249_423                         # one 249 ms step
    ops = summary.device_ops(recorded)["/device:TPU:0"]
    assert len(ops) == 7265
    assert R.busy_ns(ops) == 249_185_636
    b = summary.busy_and_window(recorded)
    assert b["busy_s"] == pytest.approx(0.249185636) and b["window_s"] == pytest.approx(0.249249423)
    assert R.idle_share(ops, t0, t1) == pytest.approx(1 - 249_185_636 / 249_249_423)
    # two `while` loops hold their bodies on the same line: leaves drop the holders
    leaves = R.leaves(ops)
    assert len(leaves) == 7263 and not any(e[0].startswith("%while") for e in leaves)
    assert abs(sum(e[2] for e in leaves) - R.busy_ns(ops)) < 5_000
    gaps = R.idle_gaps(ops, t0, t1)
    assert sum(e - s for s, e in gaps) == (t1 - t0) - R.busy_ns(ops)


def test_recorded_top_ops_and_breakdown(recorded):
    ops = summary.device_ops(recorded)["/device:TPU:0"]
    top = R.top_ops([ops], 3)
    assert [n for n, _ in top] == ["multiply_reduce_fusion_bf16_2048",
                                  "transpose_jvp_jit__lambda_____bf16_2_2048_2048",
                                  "convert_reduce_fusion_f32_2_2048"]
    assert top[0][1] == pytest.approx(0.031819409)
    bd = summary.breakdown(recorded, None)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(name == "unattributed-host" or name.startswith("sum:")
               for name, _ in bd["idle_gaps"])


def test_recorded_flash_roofline(recorded):
    m = Manifest()
    read = m.reader("flash_roofline")
    run = {"trace": recorded, "config": m.config("gpt3-xl-1p3b"),
           "family": m.family("gpt"), "peaks": m.peaks("TPU v5 lite")}
    # 72 kernels (24 layers x fwd, dQ, dK/dV) took 39.6 ms; the causal FLOPs
    # they need take 24 x 103.1 GFLOP / 197 TFLOP/s = 12.6 ms
    assert read(run) == pytest.approx(31.8, abs=0.3)
    # a family that states no head width has no such share, never GPT's
    assert read(dict(run, family=object())) is None
    import importlib.util
    spec = importlib.util.spec_from_file_location("fr", m.root / "metrics" / "flash_roofline.py")
    fr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fr)
    kinds = [fr.classify(e[0]) for e in recorded["devices"]["/device:TPU:0"]["ops"]]
    kinds = [k for k in kinds if k]
    assert len(kinds) == 72 and {k[0] for k in kinds} == {"fwd", "dq", "dkv"}
    assert all(k[1:] == (2, 2048, 2048) for k in kinds)


def test_short_name():
    assert R.short_name("%fusion.2929 = bf16[2,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[") \
        == "fusion_bf16_2_2048_2048"
    assert R.short_name("%while.13 = (u32[]{:T(128)}, u32[]{:T(128)}) while(") == "while_u32"
    assert R.short_name("%all-gather.7 = bf16[4,2048,4096]{2,1,0} all-gather(") \
        == "all-gather_bf16_4_2048_4096"
    assert R.short_name("%fusion.49.remat2 = bf16[8192,16,16,128]{3,2,1,0:T(8,128)(2,1)} fusion(") \
        == "fusion.remat2_bf16_8192_16_16_128"
    assert R.short_name("odd name!") == "odd_name_"


def test_union_idle_and_gaps_by_hand():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 10], ["inside", 32, 2], ["d", 60, 5]]
    assert R.union(ev) == [[0, 15], [30, 40], [60, 65]]
    assert R.busy_ns(ev) == 30
    assert R.idle_share(ev, 0, 100) == pytest.approx(0.70)
    assert R.idle_gaps(ev, 0, 100) == [[15, 30], [40, 60], [65, 100]]
    assert R.clip(ev, 8, 33) == [["a", 8, 2], ["b", 8, 7], ["c", 30, 3], ["inside", 32, 1]]
    assert [e[0] for e in R.leaves(ev)] == ["a", "b", "inside", "d"]
    with pytest.raises(ValueError):
        R.idle_share(ev, 5, 5)


def test_gap_attribution_by_hand():
    ev = [["op", 0, 10], ["op", 50, 10], ["op", 100, 10]]
    host = [["schedule", 8, 60], ["admit", 12, 30], ["bench.submit", 95, 2]]
    # gap [10,50): admit covers 30 of 40 and is the innermost -> admit
    assert R.attribute([10, 50], host) == "admit"
    # gap [60,100): schedule covers 8 of 40: under half -> nobody's
    assert R.attribute([60, 100], host) == "unattributed-host"
    rows = R.gap_breakdown(ev, 0, 110, host)
    assert rows[:2] == [["admit", 4e-08], ["unattributed-host", 4e-08]]
    assert ["sum:admit", 4e-08] in rows and ["sum:unattributed-host", 4e-08] in rows


def test_gap_breakdown_of_a_serving_sized_trace_is_a_sweep():
    """A 4 s serving trace has tens of thousands of gaps and a 51 s window
    thousands of host spans: every gap against every span took a minute and
    more, and the driver cut that traced run at 360 s (PR 23). The sweep gives
    what gap against every span gives, in well under a second."""
    import time

    ev, t = [], 0
    for i in range(20000):
        ev.append(["%fusion.1 = bf16[64]{0} fusion(", t, 90_000])
        t += 90_020 + (7_000_000 if i % 1000 == 999 else 0)
    t1, host = t, []
    for k in range(500):  # spans of the whole window; the trace is its end
        s = t1 - (500 - k) * 102_000_000
        host += [["schedule", s, 100_000_000], ["admit", s, 1_000_000],
                 ["decode_step", s + 2_000_000, 2_500_000],
                 ["page_alloc", s + 1000, 500], ["page_alloc", s + 3000, 500]]
        if k % 3 == 0:
            host.append(["prefill", s + 5_000_000, 27_000_000])
    host.append(["bench.whole", t1 - 60_000_000_000, 61_000_000_000])  # one long span
    at = time.monotonic()
    rows = R.gap_breakdown(ev, 0, t1, host)
    assert time.monotonic() - at < 5.0
    gaps = R.idle_gaps(ev, 0, t1)
    assert len(gaps) == 20000
    sums = {}
    for g in gaps[::97] + gaps[999::1000]:  # a sample, and every long gap
        name = R.attribute(g, host)
        sums[name] = sums.get(name, 0) + g[1] - g[0]
    assert rows[0][0] in ("schedule", "prefill") and rows[0][1] == pytest.approx(7.00002e-3)
    got = {r[0][4:]: r[1] for r in rows if r[0].startswith("sum:")}
    assert set(sums) <= set(got)
    for name, ns in sums.items():
        assert got[name] >= ns / 1e9 - 1e-12


def test_exposed_collective_by_hand():
    ev = [["%fusion.1 = bf16[8]{0} fusion(", 0, 100],
          ["%all-gather.1 = bf16[8]{0} all-gather(", 80, 50],      # 30 exposed
          ["%fusion.2 = bf16[8]{0} fusion(", 140, 20],
          ["%all-reduce.3 = f32[]{} all-reduce(", 150, 5],         # hidden
          ["%reduce-scatter.1 = f32[4]{0} reduce-scatter(", 200, 10],  # all exposed
          ["%collective-permute.2 = f32[4]{0} collective-permute(", 205, 10]]  # +5
    assert R.exposed_collective_ns(ev) == 30 + 10 + 5
    assert R.exposed_collective_ns(ev[:1]) == 0
    assert R.total_ns(ev, r"all-gather|all-reduce") == (55, 2)


# lines as the device trace of the four-chip cell gives them: the whole HLO
# instruction, operands and all
CONSUMER = ("%fusion.1841 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} fusion(bf16[2,2048,8192]"
            "{2,1,0:T(8,128)(2,1)} %all-gather.153, bf16[8192,4096]{1,0:T(8,128)(2,1)} "
            "%all-reduce.77, bf16[4096]{0} %copy-done.25), kind=kOutput, "
            "calls=%fused_computation.1322")
GATHER = ("%all-gather.153 = bf16[2,2048,8192]{2,1,0:T(8,128)(2,1)} all-gather(bf16[2,2048,4096]"
          "{2,1,0:T(8,128)(2,1)} %fusion.1839), channel_id=61, replica_groups={{0,1},{2,3}}, "
          "dimensions={2}, use_global_device_ids=true")
DONE = ("%all-reduce-done.12 = bf16[8192,4096]{1,0:T(8,128)(2,1)} all-reduce-done("
        "bf16[8192,4096]{1,0:T(8,128)(2,1)} %all-reduce-start.12)")
WHILE = ("%while.13 = (s32[]{:T(128)}, bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)}) while((s32[]"
         "{:T(128)}, bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)}) %tuple.9), condition=%cond, "
         "body=%body_with_all-gather")


def test_a_collective_is_told_by_the_instruction_not_by_its_operands():
    assert R.instruction(CONSUMER) == ("fusion.1841", "fusion")
    assert R.instruction(GATHER) == ("all-gather.153", "all-gather")
    assert R.instruction(WHILE) == ("while.13", "while")
    assert R.instruction("%copy-start.5 = (bf16[8]{0}, bf16[8]{0:S(1)}, u32[]{:S(2)}) copy-st") \
        == ("copy-start.5", "")  # a line cut short: no opcode, the name decides
    assert R.instruction("jit_step(123)") == ("jit_step(123)", "")
    assert not R.is_collective(CONSUMER) and not R.is_collective(WHILE)
    assert R.is_collective(GATHER) and R.is_collective(DONE)
    # a fusion that consumes collectives, alone on the device, exposes nothing
    assert R.exposed_collective_ns([[CONSUMER, 0, 100]]) == 0
    # the consumer hides the part of the gather it runs beside
    assert R.exposed_collective_ns([[CONSUMER, 0, 100], [GATHER, 60, 100]]) == 60
    # a `while` that holds the body's operations is no work of its own: the
    # gather inside it is hidden only while the body's fusion runs
    ev = [[WHILE, 0, 1000], [CONSUMER, 10, 300], [GATHER, 310, 200], [DONE, 510, 90],
          [CONSUMER, 600, 390]]
    assert R.exposed_collective_ns(ev) == 200 + 90


def test_top_ops_takes_leaves_per_device():
    # the second chip's fusion lies inside the first chip's: it is no parent
    one = [[CONSUMER, 0, 100], [GATHER, 100, 50]]
    two = [[CONSUMER, 10, 80], [GATHER, 100, 30]]
    assert R.top_ops([one, two]) == [["fusion_bf16_2_2048_4096", 90e-9],
                                     ["all-gather_bf16_2_2048_8192", 40e-9]]
