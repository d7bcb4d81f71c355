"""Autotuned kernel registry (ops/kernels/) — the ISSUE-18 contract.

Pins the three load-bearing promises of the layer:

- **Inert when off** (the default): config resolution is a dict probe
  returning the hand-frozen constants; the autotuner, the verifier, and the
  tuning DB are never touched (monkeypatch-exploded here) and the tune dir
  stays empty. Registered call sites (flash attention, fused CE, the paged
  and int8 serving kernels) behave byte-identically to the pre-registry
  code.
- **Search never does worse than the defaults**: the default config is
  always measured first and a candidate can only win if it is faster AND
  its output verifies against the default's; a broken candidate is a
  counted disqualification, never a result.
- **DB durability**: winners round-trip through the atomic-write DB; a
  torn/truncated/out-of-space entry is a structured reject (counted, file
  removed, re-tuned or defaulted) — a wrong config is never returned, and
  deleting the DB is always a silent fallback to the defaults.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — env/flag setup
from paddle_tpu.cost_model import CostModel
from paddle_tpu.framework import flags
from paddle_tpu.ops import kernels as K
from paddle_tpu.ops.kernels import autotune, db, registry
from paddle_tpu.profiler import counters

# the hand-frozen constants each call site used before the registry existed;
# the inert-mode contract is that resolve_config returns exactly these
PINNED = {
    "flash_attention": {"block_q": 512, "block_k": 512},
    "fused_ce": {"block_rows": 2048},
    "paged_attention": {"blocks_per_chunk": 0},  # 8 until PR 46; 0: by a block's bytes
    "int8_matmul": {"block_n": 512},
}


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated tune dir + fast search knobs; the in-process memo is cleared
    on both sides so resolutions can't leak between tests."""
    monkeypatch.setitem(flags._FLAGS, "FLAGS_kernel_tune_dir", str(tmp_path))
    monkeypatch.setitem(flags._FLAGS, "FLAGS_kernel_tune_samples", 2)
    monkeypatch.setitem(flags._FLAGS, "FLAGS_kernel_tune_budget_s", 60.0)
    autotune.clear_cache()
    yield tmp_path
    autotune.clear_cache()


def _stub(name, sleeps, wrong=()):
    """Register a stub kernel whose per-config runtime/output is scripted:
    ``sleeps[width]`` seconds per call; widths in ``wrong`` return a
    different output (must be rejected by verify)."""

    def runner(key):
        def make(config):
            w = config["width"]

            def step():
                time.sleep(sleeps.get(w, 0.0))
                if w in wrong:
                    return np.full((4,), 7.0, np.float32)
                return np.zeros((4,), np.float32)

            return step

        return make

    return registry.register_kernel(
        name, defaults={"width": 8}, space={"width": (8, 16, 32)},
        runner=runner)


class TestInertOff:
    def test_defaults_are_the_pinned_constants(self):
        for name, want in PINNED.items():
            assert K.resolve_config(name, ()) == want

    def test_off_never_touches_autotuner_or_db(self, tmp_path, monkeypatch):
        """The tier-1 tripwire: with autotune off, a resolve through every
        registered kernel AND real traced call sites must never reach the
        autotuner, the verifier, or the DB — and must write zero files."""
        import jax.numpy as jnp

        monkeypatch.setitem(flags._FLAGS, "FLAGS_kernel_tune_dir",
                            str(tmp_path))
        assert flags.flag("FLAGS_kernel_autotune", "off") == "off"

        def boom(*a, **k):  # pragma: no cover - must never run
            raise AssertionError("autotune layer touched with autotune off")

        monkeypatch.setattr(autotune, "resolve", boom)
        monkeypatch.setattr(autotune, "search", boom)
        monkeypatch.setattr(autotune, "verify", boom)
        monkeypatch.setattr(db, "lookup", boom)
        monkeypatch.setattr(db, "store", boom)
        before = {k: v for k, v in counters().items()
                  if k.startswith("kernel_tune")}

        for name in K.kernel_names():
            cfg = K.resolve_config(name, ())
            assert isinstance(cfg, dict) and cfg

        # real registered call sites, config resolved inside the trace
        rng = np.random.RandomState(0)
        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

        x = jnp.asarray(rng.randn(8, 16), jnp.float32)
        w = jnp.asarray(rng.randn(33, 16), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 33, (8,)), jnp.int32)
        float(fused_linear_cross_entropy(x, w, labels))

        q = jnp.asarray(rng.randn(2, 4, 8, 16), jnp.float32)
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_array,
        )

        np.asarray(flash_attention_array(q, q, q, causal=True))

        kpool = jnp.asarray(rng.randn(1, 16, 8, 2, 16), jnp.float32)
        tables = jnp.asarray(rng.randint(1, 16, (2, 2)), jnp.int32)
        pos = jnp.asarray([3, 9], jnp.int32)
        qr = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
        np.asarray(K.paged_attention_rows(qr, kpool, kpool, 0, tables, pos))

        qw = jnp.asarray(rng.randint(-127, 127, (32, 16)), jnp.int8)
        np.asarray(K.int8_matmul(jnp.asarray(rng.randn(3, 16), jnp.float32),
                                 qw, jnp.asarray(2.0, jnp.float32)))

        after = {k: v for k, v in counters().items()
                 if k.startswith("kernel_tune")}
        assert after == before
        assert not os.path.exists(str(tmp_path)) or \
            os.listdir(str(tmp_path)) == []


class TestTuningDB:
    def test_store_lookup_roundtrip(self, tune_env):
        key = (64, 32, "float32")
        db.store("stub_rt", key, {"width": 16}, 1.0, 2.0)
        assert db.lookup("stub_rt", key) == {"width": 16}
        # a different key is a plain miss, no reject
        before = counters().get("kernel_tune_db_rejects", 0)
        assert db.lookup("stub_rt", (65, 32, "float32")) is None
        assert counters().get("kernel_tune_db_rejects", 0) == before

    def test_truncated_entry_is_structured_reject(self, tune_env):
        key = (64, 32, "float32")
        path = db.store("stub_torn", key, {"width": 16}, 1.0, 2.0)
        with open(path) as f:
            raw = f.read()
        with open(path, "w") as f:
            f.write(raw[: len(raw) // 2])  # torn write
        before = counters().get("kernel_tune_db_rejects", 0)
        assert db.lookup("stub_torn", key) is None  # never a wrong config
        assert counters().get("kernel_tune_db_rejects", 0) == before + 1
        assert not os.path.exists(path)  # bad file removed

    def test_db_deleted_is_silent_default_fallback(self, tune_env,
                                                   monkeypatch):
        monkeypatch.setitem(flags._FLAGS, "FLAGS_kernel_autotune", "ondemand")
        spec = _stub("stub_deleted", sleeps={})
        key = (1,)
        assert autotune.resolve(spec, key, "ondemand") == {"width": 8}
        assert os.listdir(str(tune_env)) == []  # ondemand never searches

    def test_out_of_space_entry_rejected_not_traced(self, tune_env):
        spec = _stub("stub_oos", sleeps={})
        key = (2,)
        db.store("stub_oos", key, {"width": 999}, 1.0, 2.0)
        before = counters().get("kernel_tune_db_rejects", 0)
        assert autotune.resolve(spec, key, "ondemand") == {"width": 8}
        assert counters().get("kernel_tune_db_rejects", 0) == before + 1


class TestSearch:
    def test_winner_is_fastest_verified_and_persists(self, tune_env):
        # width 16 is fastest and correct; 32 is slower than the default
        spec = _stub("stub_win", sleeps={8: 0.02, 16: 0.0, 32: 0.05})
        key = (64, "float32")
        c0 = dict(counters())
        cfg = autotune.resolve(spec, key, "search")
        assert cfg == {"width": 16}
        c1 = dict(counters())
        assert c1.get("kernel_tune_searches", 0) == \
            c0.get("kernel_tune_searches", 0) + 1
        assert os.path.exists(db.entry_path("stub_win", key))

        # a fresh process (memo cleared) resolves straight from disk:
        # zero re-search, counted as a DB hit
        autotune.clear_cache()
        cfg2 = autotune.resolve(spec, key, "search")
        c2 = dict(counters())
        assert cfg2 == cfg
        assert c2.get("kernel_tune_searches", 0) == \
            c1.get("kernel_tune_searches", 0)
        assert c2.get("kernel_tune_hits", 0) == \
            c1.get("kernel_tune_hits", 0) + 1

    def test_wrong_output_candidate_never_wins(self, tune_env):
        # width 16 would be fastest but returns a different output; 32 is
        # slower than the default — so the defaults must win
        spec = _stub("stub_wrong", sleeps={8: 0.02, 16: 0.0, 32: 0.05},
                     wrong=(16,))
        c0 = counters().get("kernel_tune_verify_fails", 0)
        cfg = autotune.resolve(spec, (3,), "search")
        assert cfg == {"width": 8}  # never worse than the pinned defaults
        assert counters().get("kernel_tune_verify_fails", 0) == c0 + 1

    def test_corrupt_db_entry_triggers_retune(self, tune_env):
        spec = _stub("stub_corrupt", sleeps={8: 0.01, 16: 0.0, 32: 0.05})
        key = (4,)
        autotune.resolve(spec, key, "search")
        path = db.entry_path("stub_corrupt", key)
        with open(path, "w") as f:
            f.write("{")  # torn
        autotune.clear_cache()
        c0 = dict(counters())
        cfg = autotune.resolve(spec, key, "search")
        c1 = dict(counters())
        assert cfg == {"width": 16}
        assert c1.get("kernel_tune_db_rejects", 0) == \
            c0.get("kernel_tune_db_rejects", 0) + 1
        assert c1.get("kernel_tune_searches", 0) == \
            c0.get("kernel_tune_searches", 0) + 1

    def test_broken_runner_degrades_to_defaults(self, tune_env):
        def runner(key):
            def make(config):
                raise RuntimeError("no backend")

            return make

        spec = registry.register_kernel(
            "stub_broken", defaults={"width": 8}, space={"width": (8, 16)},
            runner=runner)
        cfg = autotune.resolve(spec, (5,), "search")
        assert cfg == {"width": 8}
        # nothing was measured, so nothing may persist
        assert not os.path.exists(db.entry_path("stub_broken", (5,)))


class TestCostModel:
    def test_padding_waste_and_grid_overhead_ordering(self):
        cm = CostModel()
        # fused CE at N=1000: block_rows=8192 pads to 8x the real rows
        small = cm.kernel_estimate("fused_ce", (1000, 512, 50000, "float32"),
                                   {"block_rows": 512})
        huge = cm.kernel_estimate("fused_ce", (1000, 512, 50000, "float32"),
                                  {"block_rows": 8192})
        assert small < huge
        # flash at t=8192: 128-wide blocks launch 4x the programs of 512
        key = (8, 8, 8192, 8192, 128, "bfloat16", True)
        assert cm.kernel_estimate("flash_attention", key,
                                  {"block_q": 512, "block_k": 512}) < \
            cm.kernel_estimate("flash_attention", key,
                               {"block_q": 128, "block_k": 128})
        assert cm.kernel_estimate("no_such_kernel", (), {}) == 0.0

    def test_candidates_visit_order_matches_estimates(self):
        spec = registry.get_kernel("fused_ce")
        key = (1000, 512, 50000, "float32")
        cands = autotune.candidates(spec, key)
        assert cands  # non-default configs exist
        assert all(c != dict(spec.defaults) for c in cands)
        cm = CostModel()
        ests = [cm.kernel_estimate("fused_ce", key, c) for c in cands]
        assert ests == sorted(ests)
