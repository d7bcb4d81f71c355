"""How a serving cell states its deployment (PR 44): the ``engine`` entry of an
``open_loop`` traffic file reaches ``serving.Engine`` as keywords, a mix that
does not fit the engine's context is refused before a weight is drawn (by
``serve_job.setup`` and by ``Manifest.validate``), and the reference check
pads in coarser steps past 2,048 positions. The fixture family of
``fixtures/`` at its own tiny widths, on the CPU."""
import json
import re

import numpy as np
import pytest
from test_benchmark_manifest import FIXTURES, _a_second_family, _copy, _manifest_of

from benchmark import generator as G, serve_job, weights as W
from benchmark.manifest import Manifest, _load

FAM = _load(FIXTURES / "families" / "llama.py", "benchmark_family_llama_deployment")
CFG = json.loads((FIXTURES / "configs" / "llama-tiny.json").read_text())
# the fixture's mix with the rehearsal's walk and pool (benchmark/rehearse.json)
TINY = {**json.loads((FIXTURES / "traffic" / "chat-tiny.json").read_text()),
        "warm_rows": 4, "pool_blocks": 128}
CONST = lambda n: {"dist": "const", "value": n}


class Ctx:
    """What ``serve_job.setup`` takes of ``run.Ctx``."""

    def __init__(self, config, traffic, seed=44):
        self.config, self.traffic, self.family, self.seed = config, traffic, FAM, seed
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


@pytest.fixture
def engines(monkeypatch):
    """Every ``Engine(...)`` call ``setup`` makes, by its keywords."""
    from paddle_tpu import serving

    calls = []

    class Recorded(serving.Engine):
        def __init__(self, model, **kw):
            calls.append(kw)
            super().__init__(model, **kw)

    monkeypatch.setattr(serving, "Engine", Recorded)
    return calls


@pytest.mark.parametrize("entry,sizes", [
    (None, (256, 64, 4)),                       # the flags', the context the model's
    ({"prefill_batch": 1, "max_batch": 8}, (256, 8, 1)),
    ({"max_seq_len": 192, "prefill_batch": 2}, (192, 64, 2)),
])
def test_the_entry_reaches_the_engine_and_without_it_the_call_is_the_old_one(
        entry, sizes, engines):
    traffic = dict(TINY) if entry is None else {**TINY, "engine": entry}
    ctx = Ctx(CFG, traffic)
    model, eng = serve_job.setup(ctx, G.build_schedule(traffic, 1.0, 3, CFG["vocab_size"]))
    try:
        assert engines == [{"num_blocks": 128, **(entry or {})}]
        got = eng.config
        assert (got.max_seq_len, got.max_batch, got.prefill_batch) == sizes
        assert (ctx.notes["engine_max_seq_len"], ctx.notes["engine_max_batch"],
                ctx.notes["engine_prefill_batch"]) == sizes
        assert ctx.notes["pool_blocks"] == 128
    finally:
        eng.close()


def test_a_request_past_2048_positions_is_served_and_judged(engines):
    """A context above the flag's default: two modes of length in one queue,
    one row a prefill call; the long request's tokens are the float32
    reference's own, read at a padded length of 3,072."""
    cfg = {**CFG, "max_position_embeddings": 4096}
    traffic = {**TINY, "rate_per_s": 2.0, "pool_blocks": 400,
               "engine": {"max_seq_len": 3072, "prefill_batch": 1, "max_batch": 4},
               "prompt_len": {"dist": "mixture", "parts": [
                   {"weight": 0.5, **CONST(40)}, {"weight": 0.5, **CONST(2100)}]},
               "output_len": CONST(12)}
    sched = G.build_schedule(traffic, 2.0, 9, cfg["vocab_size"])
    assert sorted(set(sched.prompt_len)) == [40, 2100]
    ctx = Ctx(cfg, traffic)
    model, eng = serve_job.setup(ctx, sched)
    try:
        assert engines == [{"num_blocks": 400, **traffic["engine"]}]
        assert ctx.notes["engine_max_seq_len"] == 3072 > 2048
        long = int(np.argmax(sched.prompt_len))
        out = eng.submit(sched.prompts[long], max_new_tokens=12).result(timeout=600)
        with pytest.raises(ValueError, match="exceeds max_seq_len 3072"):
            eng.submit(sched.prompts[long], max_new_tokens=1000)
    finally:
        eng.close()
    assert len(out) == 2112 and list(out[:2100]) == list(sched.prompts[long])
    weights = W.make_weights(cfg, ctx.seed, FAM.leaf_specs(cfg))
    gaps = serve_job.served_gap(FAM, cfg, weights, sched.prompts[long], out[2100:])
    assert gaps.shape == (12,) and gaps.max() <= 1e-3
    wrong = list(out[2100:])
    wrong[7] = (wrong[7] + 1) % cfg["vocab_size"]
    assert serve_job.served_gap(FAM, cfg, weights, sched.prompts[long], wrong).argmax() == 7


REFUSED = [
    ({"engine": {"num_blocks": 99}}, r"engine: 'num_blocks' is the harness's own"),
    ({"engine": {"block_size": 32}}, r"engine: 'block_size' is the harness's own"),
    ({"engine": {"max_len": 4096}}, r"engine: 'max_len' is no keyword of serving.EngineConfig"),
    ({"engine": {"prefill_batch": 0}}, r"prefill_batch"),     # the program's own refusal
    # 200 + 60 = 260, and the warm-up's 2 x 4 rows + 8 behind it
    ({"prompt_len": CONST(200), "output_len": CONST(60)},
     r"longest context of 260 tokens .* a request of 276, over max_seq_len 256"),
    # the mix's longest context alone would fit: its warm-up does not
    ({"prompt_len": CONST(200), "output_len": CONST(50)},
     r"longest context of 250 tokens .* a request of 266, over max_seq_len 256"),
    ({"engine": {"max_seq_len": 64}, "prompt_len": CONST(40), "output_len": CONST(12)},
     r"longest context of 52 tokens .* a request of 68, over max_seq_len 64"),
    # the engine's rows are the warm-up's where the file walks no fewer
    ({"warm_rows": 64, "prompt_len": CONST(100), "output_len": CONST(30)},
     r"a request of 266, over max_seq_len 256"),
]


@pytest.mark.parametrize("over,says", REFUSED)
def test_setup_refuses_before_a_weight_is_drawn(over, says, monkeypatch):
    def drawn(*a, **k):
        raise AssertionError("the weights were drawn first")

    monkeypatch.setattr(W, "make_weights", drawn)
    traffic = {**TINY, **over}
    with pytest.raises(ValueError, match=says):
        serve_job.setup(Ctx(CFG, traffic),
                        G.build_schedule(traffic, 1.0, 3, CFG["vocab_size"]))


def _with_the_fixture_cell(tmp_path, over, rehearse=None):
    """A copy of the benchmark with the fixture family's cell listed and
    ``over`` laid over its traffic file."""
    m, root, data = _copy(tmp_path)
    _a_second_family(m, root, data)
    (root / "traffic" / "chat-tiny.json").write_text(json.dumps({**TINY, **over}))
    if rehearse:
        path = root / "rehearse.json"
        sizes = json.loads(path.read_text())
        sizes["open_loop"].update(rehearse)
        path.write_text(json.dumps(sizes))
    return _manifest_of(tmp_path, data)


@pytest.mark.parametrize("over,says", REFUSED)
def test_validate_refuses_when_the_file_is_loaded(over, says, tmp_path):
    faults = _with_the_fixture_cell(tmp_path, over).validate()
    assert len(faults) == 1, faults
    assert faults[0].startswith("cell serve-llama-tiny: traffic/chat-tiny.json: ")
    assert re.search(says, faults[0]), faults[0]


def test_validate_names_a_key_the_mix_lacks(tmp_path):
    m = _with_the_fixture_cell(tmp_path, {})
    path = m.root / "traffic" / "chat-tiny.json"
    path.write_text(json.dumps({k: v for k, v in TINY.items() if k != "output_len"}))
    assert m.validate() == [
        "cell serve-llama-tiny: traffic/chat-tiny.json: lacks the key 'output_len'"]


def test_validate_takes_a_stated_deployment_that_fits(tmp_path):
    m = _with_the_fixture_cell(tmp_path, {"engine": {"max_seq_len": 192, "max_batch": 8,
                                                     "prefill_batch": 1}})
    assert m.validate() == []


def test_a_rehearsal_checks_the_rehearsal_s_sizes(tmp_path, monkeypatch):
    """Under ``--rehearse`` the mix and the model are the rehearsal's, and so
    is what has to fit: prompts of 250 tokens do not fit the 256 positions of
    the family's ``REHEARSE``, whatever the file says of the real sizes."""
    from benchmark import run

    monkeypatch.setattr(W, "make_weights",
                        lambda *a, **k: pytest.fail("the weights were drawn first"))
    _with_the_fixture_cell(tmp_path, {"engine": {"max_seq_len": 8192}},
                           rehearse={"prompt_len": CONST(250), "output_len": CONST(4)})
    with pytest.raises(ValueError, match=r"longest context of 254 tokens .* a request "
                                         r"of 270, over max_seq_len 256"):
        run.main(["--workload", "serve-llama-tiny", "--seed", "5", "--seconds", "1",
                  "--rehearse", "--manifest", str(tmp_path / "BENCHMARK.json")])


@pytest.mark.parametrize("cell,longest", [
    ("serve-xl-chat-sat", 1275), ("serve-xing4-longanswer-pinned", 1792),
    ("serve-phi4flash-reasoning", 1634), ("serve-lfm2-toolturn-pinned", 1792)])
def test_the_four_serving_cells_fit_at_the_flags_sizes(cell, longest):
    """No cell states an ``engine`` entry: 2,048 positions, 64 rows, four rows
    a prefill call, and the warm-up's tail of 2 x 64 + 8 behind the longest
    context of a 51 s run."""
    m = Manifest()
    w = m.workload(cell)
    traffic, cfg = m.traffic(w["traffic"]), m.config(w["config"])
    assert "engine" not in traffic
    prompt, ctx = G.longest(traffic, m.data["run_seconds"])
    assert ctx == longest and ctx + 136 <= 1928
    got = serve_job.fit(traffic, cfg, prompt, ctx)
    assert (got.max_seq_len, got.max_batch, got.prefill_batch) == (2048, 64, 4)


@pytest.mark.parametrize("n,pad_to,want", [
    (1, 256, 256), (255, 256, 256), (256, 256, 256), (257, 256, 512), (1928, 256, 2048),
    (2048, 256, 2048), (2049, 256, 3072), (3072, 256, 3072), (3073, 256, 4096),
    (8191, 256, 8192), (8192, 256, 8192), (33, 32, 64), (2049, 32, 3072)])
def test_the_check_pads_in_steps_of_256_up_to_2048_and_of_1024_beyond(n, pad_to, want):
    assert serve_job.padded_len(n, pad_to) == want


def test_a_check_up_to_8192_compiles_six_programs_past_2048():
    assert sorted({serve_job.padded_len(n) for n in range(2049, 8193)}) == \
        [3072, 4096, 5120, 6144, 7168, 8192]
    assert sorted({serve_job.padded_len(n) for n in range(1, 2049)}) == \
        list(range(256, 2049, 256))


@pytest.mark.parametrize("gap_at,want", [
    ([1.0, 3.0, 6.0, 9.0], [10.0, 15.0, 20.0, 25.0]),
    ([6.0, 7.0, 8.0, 9.0], [None, None, 15.0, 25.0]),    # a slow start: no gap in the first half
])
def test_the_log_of_a_window_whose_first_quarter_is_empty(gap_at, want):
    """A rehearsal beside a busy machine lands its first gap late; the log
    line says so, it does not end the run."""
    gaps = np.array([10.0, 20.0, 30.0, 40.0])
    assert serve_job.settling(gaps, np.array(gap_at), 10.0) == want
