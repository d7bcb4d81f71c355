"""The gated-convolution / grouped-query / routed-expert hybrid
(``paddle_tpu.models.lfm2_moe``) against its plain reference
(``benchmark/reference/lfm2.py``: float32 ``jax.numpy``, one sequence at a
time, written from the published equations and sharing no code with the
program), at a small size on the CPU with seeded weights: two dense
convolution layers, then two scanned turns of [attention, conv] with eight
routed experts of which a token takes two (the family's ``REHEARSE``), and a
nine-layer variant whose last, partial turn is unrolled.

Tolerances. Everything here runs in float32 on both sides, so what is left
between program and reference is the order of the sums: ``TOL`` = 2e-5 of the
largest reference value + 2e-6, the bound ``paged_attention``'s tests hold. A
convolution tap dropped, a state left from the slot's last request or a query
that reads its neighbour's half of a line moves the logits by a hundred times
that and more, which the controls below show. A served token is held to the
reference by its GAP (how far its reference logit lies under the reference's
best): an exact 0 in float32 unless two logits tie to rounding, so <= 1e-5.
"""
import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
import paddle_tpu.models.generation as G
from paddle_tpu.models import lfm2_moe as L
from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.profiler import counters, spans
from paddle_tpu.serving import Engine

REPO = pathlib.Path(__file__).parent.parent


def _family():
    spec = importlib.util.spec_from_file_location(
        "lfm2_family_under_test", REPO / "benchmark/families/lfm2.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
REF = FAM.reference
PUBLISHED = json.loads((REPO / "benchmark/configs/lfm2-24b-a2b-10l.json").read_text())
# initializer_range 1 / sqrt(64): a projection of the normed stream then keeps
# its size, as 0.02 x sqrt(2048) = 0.9 does at the published width. At 0.02 a
# layer of width 64 adds a twentieth of the embedding to the stream, the tied
# head finds the fed token again whatever the layers did, and no check below
# would see a fault in them
TINY = {**PUBLISHED, **FAM.REHEARSE, "initializer_range": 0.125}
# nine layers: two dense, three whole turns of [attention, conv], and an
# attention layer of a fourth turn, unrolled with leaves of its own
TAILED = {**TINY, "num_hidden_layers": 9,
          "layer_types": TINY["layer_types"] + ["full_attention", "conv", "full_attention"]}
BS = 8  # the engine's block in these tests


def tol(ref):
    return 2e-5 * np.abs(np.asarray(ref)).max() + 2e-6


def close(a, b, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol(b), (what, np.abs(a - b).max(), tol(b))


def build(cfg, seed=3):
    """(model, leaves) of a configuration dict, seeded as the benchmark seeds."""
    from benchmark import weights as Wt

    w = Wt.make_weights(cfg, seed, FAM.leaf_specs(cfg))
    net, _ = FAM.build(cfg, w)
    net.eval()
    return net, w


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


@pytest.fixture(scope="module")
def tailed():
    return build(TAILED)


def reference(cfg, w, ids):
    return np.asarray(REF.forward_logits(cfg, w, np.asarray(ids)[None], "f32")[0])


def _gaps(cfg, w, prompt, out):
    """How far each served token's reference logit lies under the best."""
    ref = reference(cfg, w, out[:-1])[len(prompt) - 1:]
    return ref.max(-1) - ref[np.arange(len(ref)), np.asarray(out[len(prompt):])]


def _kernels(monkeypatch, on):
    real = G.lfm2_moe_decode_state
    monkeypatch.setattr(G, "lfm2_moe_decode_state", lambda m, k=None: real(m, on))


def _engine(net, **kw):
    return Engine(net, **{**dict(block_size=BS, num_blocks=64, max_batch=4,
                                 max_seq_len=64), **kw})


# -- (a) the model's forward, and the programs logit by logit ---------------------
@pytest.mark.parametrize("which", ["tiny", "tailed"])
def test_forward_equals_the_reference(which, request):
    net, w = request.getfixturevalue(which)
    cfg = TINY if which == "tiny" else TAILED
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    out = np.asarray(net(ids)._data)
    for row in range(2):
        close(out[row], reference(cfg, w, ids[row]), which)


class Programs:
    """The arch's prefill and decode programs over pools of their own, as the
    engine builds them, with the logits of every step in hand."""

    def __init__(self, net, kernels, blocks=24, max_batch=3, table=8):
        _, self.arch, self.params, _ = G.lfm2_moe_decode_state(net, kernels)
        self.mb = table
        self.pools = tuple(
            jnp.zeros(shape, dtype or jnp.float32) for _, shape, dtype in
            G.cache_pools(self.arch, 0, blocks, BS, max_batch))

    def prefill(self, prompts, bucket, tables, slots):
        ids = np.zeros((len(prompts), bucket), np.int32)
        for r, p in enumerate(prompts):
            ids[r, :len(p)] = p
        fn = G.build_paged_prefill(self.arch, len(prompts), bucket, BS, self.mb)
        *pools, logits, counts = fn(
            self.params, jnp.asarray(ids), jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.asarray(tables, jnp.int32), jnp.asarray(slots, jnp.int32), *self.pools)
        self.pools = tuple(pools)
        return np.asarray(logits), np.asarray(counts)

    def step(self, toks, pos, tables, slots):
        B = len(toks)
        fn = G.build_paged_decode_kernel(self.arch, B, BS, self.mb)
        *pools, _, counts = fn(
            self.params, *self.pools, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32),
            jnp.asarray(toks, jnp.int32), jnp.zeros((B,), jnp.float32),
            jax.random.PRNGKey(0))
        # the logits themselves, through the same stack
        toks, pos, tables, slots = (jnp.asarray(a, jnp.int32)
                                    for a in (toks, pos, tables, slots))
        x = self.arch["embed"](self.params, toks, pos)[:, None]
        bids = jnp.take_along_axis(tables, (pos // BS)[:, None], axis=1)[:, 0]
        x, _, _ = self.arch["decode_stack"](
            self.params, x, self.pools, tables, pos, bids, pos % BS, slots, BS)
        self.pools = tuple(pools)
        return np.asarray(self.arch["head"](self.params, x[:, -1])), np.asarray(counts)


def _reference_counts(cfg, w, ids, upto=None):
    """(expert layers, experts): how many of ``ids``'s positions (``upto``:
    that one alone) the reference's router sends to each expert."""
    x = np.asarray(w["wte"], np.float32)[np.asarray(ids)]
    x = jnp.asarray(x)
    out = []
    static = REF.static(cfg)
    for i in range(cfg["num_hidden_layers"]):
        leaves = REF.layer_leaves(cfg, w, i)
        if "mlp.router.w" in leaves:
            n = REF.rms(x + (REF.gated_conv if "conv.conv.w" in leaves else REF.attention)(
                static, leaves, REF.rms(x, leaves["op_norm.g"], cfg["norm_eps"]), "f32"),
                leaves["ffn_norm.g"], cfg["norm_eps"])
            took = np.asarray(REF.routing(
                REF._router(cfg), {"mlp.router.w": leaves["mlp.router.w"],
                                   "mlp.router.e_bias": leaves["mlp.router.expert_bias"]},
                n, "f32")) > 0
            out.append(took[upto].astype(int) if upto is not None else took.sum(0))
        x, _ = REF.layer(static, leaves, x, "f32")
    return np.stack(out)


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
@pytest.mark.parametrize("n", [1, 2, 3, 16], ids=lambda n: f"prompt_{n}")
def test_prefill_then_decode_equals_the_reference_logits(tiny, kernels, n):
    """A prompt of ``n`` (shorter than the taps, as long as them, a bucket's
    edge) beside a neighbour of another length in one bucket, then six decode
    steps through the caches: every logit row is the reference's full forward
    pass at that position, and the counts the programs land are the
    reference router's."""
    net, w = tiny
    seq = np.random.default_rng(7).integers(0, TINY["vocab_size"], n + 6)
    other = np.random.default_rng(8).integers(0, TINY["vocab_size"], 11)
    full = reference(TINY, w, seq)
    prog = Programs(net, kernels)
    tables = [[1, 2, 3, 0, 0, 0, 0, 0], [4, 5, 6, 0, 0, 0, 0, 0]]
    logits, counts = prog.prefill([seq[:n], other], 16, tables, [1, 2])
    close(logits[0], full[n - 1], "prefill")
    assert np.array_equal(counts, _reference_counts(TINY, w, seq[:n])
                          + _reference_counts(TINY, w, other))
    for t in range(n, n + 6):
        # the neighbour's row pads the bucket from here on: slot 0, block 0
        logits, counts = prog.step([seq[t], 0], [t, 0], [tables[0], [0] * 8], [1, 0])
        close(logits[0], full[t], f"decode at {t}")
        assert np.array_equal(counts, _reference_counts(TINY, w, seq[:t + 1], upto=t))


def test_padding_rows_leave_slot_0_and_block_0_to_themselves(tiny):
    """Rows that pad a bucket write the trash slot and the trash block and
    nothing else: a live row's logits are the same with three of them beside
    it as alone, and no other slot or block changes."""
    net, w = tiny
    seq = np.random.default_rng(9).integers(0, TINY["vocab_size"], 12)
    table = [1, 2, 0, 0, 0, 0, 0, 0]
    alone, padded = Programs(net, False), Programs(net, False)
    alone.prefill([seq[:10]], 16, [table], [2])
    padded.prefill([seq[:10], [], []], 16, [table, [0] * 8, [0] * 8], [2, 0, 0])
    for a, b in zip(alone.pools, padded.pools):
        kept = np.ones(a.shape[1], bool)
        kept[0] = False  # block 0 / slot 0
        assert np.array_equal(np.asarray(a)[:, kept], np.asarray(b)[:, kept])
    one, _ = alone.step([seq[10]], [10], [table], [2])
    many, _ = padded.step([seq[10], 0, 0, 0], [10, 0, 0, 0], [table] + [[0] * 8] * 3,
                          [2, 0, 0, 0])
    assert np.abs(one[0] - many[0]).max() <= tol(one)
    close(one[0], reference(TINY, w, seq)[10])


# -- (b) through serving.Engine ------------------------------------------------------
@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
@pytest.mark.parametrize("which", ["tiny", "tailed"])
def test_serving_equals_the_reference_forward(which, monkeypatch, kernels, request):
    """Through ``serving.Engine``: prompts of 1, 2 and 3 tokens and on both
    sides of the prefill buckets' edges (8, 16), answers of different lengths
    so that rows leave mid-stream, and two requests that join once the others
    are under way. Every served token is the reference's best at its
    position, and the expert table the engine keeps is the reference
    router's over every position it served."""
    net, w = request.getfixturevalue(which)
    cfg = TINY if which == "tiny" else TAILED
    _kernels(monkeypatch, kernels)
    # this test compiles some sixty programs, and a worker that already
    # keeps a few hundred XLA:CPU executables alive aborts inside JAX's read
    # of the persistent cache (PERF.md, PR 45 (6); three whole runs of PR 48
    # lost a worker HERE): what the worker holds goes first
    jax.clear_caches()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (1, 2, 3, 8, 9, 17)]
    new = [20, 12, 18, 14, 6, 16]
    with _engine(net) as eng:
        handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[:4], new)]
        while eng.stats()["decode_steps"] < 5:
            pass
        handles += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[4:], new[4:])]
        outs = [h.result(timeout=600) for h in handles]
        stats = eng.stats()
    want = 0
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        assert _gaps(cfg, w, p, out).max() <= 1e-5
        want = want + _reference_counts(cfg, w, out[:-1])  # the last token is never fed
    assert stats["state_slots_used"] == 0 and stats["pages_used"] == 0
    assert stats["state_slots_total"] == 4
    assert np.array_equal(np.asarray(stats["expert_tokens"]), want)


def _one_after_another(net, first, second, new):
    """``second`` served alone on an engine of ONE slot that has just served
    ``first``: it takes the slot ``first`` held."""
    with _engine(net, max_batch=1) as eng:
        eng.submit(first, max_new_tokens=new).result(timeout=600)
        return eng.submit(second, max_new_tokens=new).result(timeout=600)


def test_a_slot_is_clean_for_its_next_request(tiny, monkeypatch):
    """A prompt of ONE token (its convolutions see zeros before it) in the
    slot a longer request just left gives the tokens a fresh engine gives;
    and the control: a prefill that does NOT write the slot's states leaves
    the first request's there, and the check sees it."""
    net, w = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
                     for n in (20, 1))
    out = _one_after_another(net, first, second, 12)
    assert _gaps(TINY, w, second, out).max() <= 1e-5
    with _engine(net, max_batch=1) as eng:
        assert eng.submit(second, max_new_tokens=12).result(timeout=600) == out
    real = G._lfm2_moe_arch

    def stale(cfg, kernels):
        arch = real(cfg, kernels)
        inner = arch["prompt_stack"]

        def prompt_stack(params, x, pools, *rest):
            x, new, counts = inner(params, x, pools, *rest)
            return x, (*new[:2], pools[2]), counts

        return {**arch, "prompt_stack": prompt_stack}

    monkeypatch.setattr(G, "_lfm2_moe_arch", stale)
    prog = Programs(net, False)
    prog.prefill([first], 32, [[1, 2, 3, 0, 0, 0, 0, 0]], [1])
    prog.prefill([second], 8, [[4, 0, 0, 0, 0, 0, 0, 0]], [1])
    seq = np.asarray(out)
    got, _ = prog.step([seq[1]], [1], [[4, 0, 0, 0, 0, 0, 0, 0]], [1])
    full = reference(TINY, w, seq)
    assert np.abs(got[0] - full[1]).max() > 100 * tol(full)


def test_evict_and_re_prefill_mid_answer_gives_the_same_tokens(tiny):
    """A pool too small for three answers at once: a row is evicted
    mid-answer, its blocks AND its slot are freed, and the re-prefill rebuilds
    K/V and the convolution states from the tokens so far."""
    net, w = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, TINY["vocab_size"], 9).astype(np.int32) for _ in range(3)]
    with _engine(net, num_blocks=11) as eng:
        outs = [h.result(timeout=600)
                for h in [eng.submit(p, max_new_tokens=30) for p in prompts]]
        stats = eng.stats()
    assert stats["state_rebuilds"] >= 1 and stats["state_slots_used"] == 0
    for p, out in zip(prompts, outs):
        assert _gaps(TINY, w, p, out).max() <= 1e-5


def test_the_check_sees_a_dropped_tap_and_a_zeroed_state(tiny):
    """Controls of the tolerance: the convolution states zeroed mid-answer,
    or the reference's first tap dropped, move the logits far out of it."""
    net, w = tiny
    seq = np.random.default_rng(6).integers(0, TINY["vocab_size"], 14)
    full = reference(TINY, w, seq)
    prog = Programs(net, False)
    tables = [[1, 2, 0, 0, 0, 0, 0, 0]]
    prog.prefill([seq[:10]], 16, tables, [1])
    close(prog.step([seq[10]], [10], tables, [1])[0][0], full[10], "sound")
    prog.pools = (*prog.pools[:2], jnp.zeros_like(prog.pools[2]))
    moved = np.abs(prog.step([seq[11]], [11], tables, [1])[0][0] - full[11]).max()
    assert moved > 100 * tol(full)
    two_taps = {k: (v.at[..., 0, :].set(0) if k.endswith("conv.conv.w") else v)
                for k, v in w.items()}
    assert np.abs(reference(TINY, two_taps, seq) - full).max() > 100 * tol(full)


# -- (c) the packed two-heads-a-line read --------------------------------------------
def _packed_case(B=3, H=8, G_=4, D=16, blocks=12, seed=0):
    cfg = Lfm2MoeConfig(hidden_size=H * D, num_attention_heads=H, num_key_value_heads=G_)
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(B, H, D), f(2, blocks * BS, G_, D), f(2, blocks * BS, G_, D)
    pack = lambda a: L.pair_keys(cfg, a).reshape(2, blocks, BS * G_ // 2, 2 * D)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([19, 8, 0], jnp.int32)
    return cfg, q, k, v, pack(k), pack(v), tables, pos


@pytest.mark.parametrize("form", ["plain", "interpreter"])
def test_the_packed_read_equals_a_plain_gather(form):
    """Two key/value heads a line, queries padded to their own half: the
    block-table kernel (Pallas interpreter) and the plain form of the arch's
    decode read both give what a gather of the UNPACKED heads and a grouped
    softmax give, for rows of 20, 9 and 1 live tokens in layer 1 of 2."""
    from paddle_tpu.models.phi4flash import attend_dense
    from paddle_tpu.ops.kernels import paged_attention_rows

    cfg, q, k, v, kp, vp, tables, pos = _packed_case()
    B, H, D = q.shape
    want = []
    for b in range(B):
        t = np.concatenate([np.arange(int(x) * BS, int(x) * BS + BS) for x in tables[b]])
        t = t[:int(pos[b]) + 1]
        kk, vv = np.asarray(k)[1, t], np.asarray(v)[1, t]       # (T, G, D)
        qq = np.asarray(q)[b].reshape(kk.shape[1], -1, D)       # (G, rep, D)
        s = np.einsum("grd,tgd->grt", qq, kk) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        want.append(np.einsum("grt,tgd->grd", p / p.sum(-1, keepdims=True), vv).reshape(H, D))
    if form == "interpreter":
        o = paged_attention_rows(L.pair_queries(cfg, q), kp, vp, 1, tables, pos,
                                 scale=D ** -0.5, kv_heads=cfg.kv_row[0], interpret=True)
        got = L.own_half(cfg, o.reshape(B, H, 2 * D))
    else:
        T_pad = tables.shape[1] * BS
        kc = kp[1, tables].reshape(B, T_pad, cfg.num_key_value_heads, D)
        vc = vp[1, tables].reshape(B, T_pad, cfg.num_key_value_heads, D)
        seen = jnp.arange(T_pad)[None, None, :] <= pos[:, None, None]
        got = attend_dense(cfg, q[:, None], kc, vc, seen)[:, 0]
    close(got, np.stack(want), form)
    # the control: a query laid on its neighbour's half reads the other head
    if form == "interpreter":
        swapped = jnp.roll(L.pair_queries(cfg, q), D, axis=-1)
        o = paged_attention_rows(swapped, kp, vp, 1, tables, pos, scale=D ** -0.5,
                                 kv_heads=cfg.kv_row[0], interpret=True)
        wrong = L.own_half(cfg, o.reshape(B, H, 2 * D))
        assert np.abs(np.asarray(wrong) - np.stack(want)).max() > 100 * tol(np.stack(want))


def test_pair_and_own_half_are_inverse_layouts():
    cfg = Lfm2MoeConfig(hidden_size=128, num_attention_heads=8, num_key_value_heads=4)
    q = jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16) + 1
    padded = L.pair_queries(cfg, q)
    assert padded.shape == (2, 8, 32)
    assert np.array_equal(L.own_half(cfg, padded), q)
    # heads 0-1 (K/V group 0) fill the left half, heads 2-3 (group 1) the right
    assert not np.asarray(padded[:, :2, 16:]).any() and not np.asarray(padded[:, 2:4, :16]).any()


@pytest.mark.parametrize("layer", [0, 1])
def test_the_expert_kernel_reads_its_layer_out_of_the_stack(layer):
    """``moe_experts(layer=...)`` over the stacks of two layers' experts (the
    interpreter) equals the plain form over that layer's slice."""
    from paddle_tpu.models.mla_moe import experts_plain
    from paddle_tpu.ops.kernels.moe_experts import moe_experts

    rng = np.random.default_rng(layer)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    x, wg, wu, wd = f(5, 32), f(2, 6, 32, 64), f(2, 6, 32, 64), f(2, 6, 64, 32)
    slot = jnp.asarray(rng.integers(0, 7, (5, 2)), jnp.int32)  # 6: no expert
    gates = jnp.asarray(rng.random((5, 2)), jnp.float32)
    got = moe_experts(x, slot, gates, wg, wu, wd, layer=jnp.int32(layer), interpret=True)
    close(got, experts_plain(x, slot, gates, wg[layer], wu[layer], wd[layer]))


# -- (d) pools, spans, counters ----------------------------------------------------------
def test_the_pools_are_a_kind_each_over_their_own_layers(tiny):
    net, _ = tiny
    with _engine(net) as eng:
        assert eng._cache_kinds == ("paged", "paged", "state")
        # two attention layers: 64 blocks of 8 tokens x 1 pair lines of 2 x 16;
        # four convolution layers: 4 slots + the trash slot of 2 inputs
        assert [p.shape for p in eng._cache] == [(2, 64, 8, 32), (2, 64, 8, 32),
                                                 (4, 5, 2, 64)]
        assert eng._paged_kernel and eng._row_slots is not None
        assert eng.stats()["cache_bytes"] == {"paged": 2 * 2 * 64 * 8 * 32 * 4,
                                              "state": 4 * 5 * 2 * 64 * 4}


def test_spans_and_counters_count_real_rows_and_tokens_alone(tiny):
    """One live row in a decode bucket of 4, a prompt of 5 in a bucket of 8:
    what the spans carry is of the real row and its real tokens."""
    net, _ = tiny
    seen = []
    before = counters().get("serve_state_rows", 0)
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, decode_buckets=(4,)) as eng:
            eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=12).result(timeout=600)
            stats = eng.stats()
    finally:
        spans.remove_span_observer(seen.append)
    fills = [sp.attrs for sp in seen if sp.name == "prefill"]
    assert [(a["prompt_tokens"], a["bucket_t"]) for a in fills] == [(5, 8)]
    assert fills[0]["experts_touched"] > 0
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert len(steps) == 10  # 11 decode steps, the first only enqueued
    # the step that lands writes position 5, 6, ...: its context is one more
    assert [a["paged_kv_tokens"] for a in steps] == list(range(6, 16))
    assert all(a["state_rows"] == 1 and a["rows"] == 1 and a["bucket"] == 4 for a in steps)
    # one live row takes 2 of 8 experts in each of 4 expert layers
    assert all(a["experts_touched"] == 8 and a["expert_tokens_max"] == 1 for a in steps)
    assert not any("shared_kv_tokens" in a or "window_tokens" in a for a in steps)
    # the plain gather has no chunks: none of the kernel's counts (PR 46)
    assert not any(k in a for a in steps for k in ("paged_blocks", "paged_chunks", "paged_full_chunks"))
    assert counters()["serve_state_rows"] - before == 11 == stats["state_rows"]


def test_decode_spans_carry_the_block_table_reads_copy_schedule(tiny, monkeypatch):
    """``paged_blocks`` / ``paged_chunks`` / ``paged_full_chunks`` of a
    ``decode_step`` span are what the landing step's positions give by hand,
    summed over the step's calls of the kernel, BESIDE what the span said of
    the caches before (the plain gather has no chunks and says nothing: the
    test of the spans above). A
    table of 4 blocks of 8 tokens, so chunks of 4: a row that writes position
    22, 23 reads 3 blocks a call (a partial chunk), 24, 25 reads 4 (a full
    one). Two attention layers by the position."""
    net, _ = tiny
    _kernels(monkeypatch, True)
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, decode_buckets=(4,), max_seq_len=32) as eng:
            eng.submit(np.arange(22, dtype=np.int32), max_new_tokens=6).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert [a["paged_kv_tokens"] for a in steps] == [23, 24, 25, 26]
    assert all(a["state_rows"] == 1 for a in steps)
    assert [(a["paged_blocks"], a["paged_chunks"], a["paged_full_chunks"])
            for a in steps] == [(6, 2, 0), (6, 2, 0), (8, 2, 2), (8, 2, 2)]


# -- (e) what is not built is refused by name ------------------------------------------
def test_unknown_mechanisms_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="layer_types"):
        Lfm2MoeConfig(layer_types=("conv", "sliding_attention"), num_hidden_layers=2)
    with pytest.raises(NotImplementedError, match="conv_bias"):
        Lfm2MoeConfig(conv_bias=True)
    with pytest.raises(NotImplementedError, match="untied head"):
        Lfm2MoeConfig(tie_word_embeddings=False)
    with pytest.raises(NotImplementedError, match="two a line"):
        Lfm2MoeConfig(num_attention_heads=6, num_key_value_heads=3, hidden_size=96)
    with pytest.raises(NotImplementedError, match="rope_parameters"):
        Lfm2MoeConfig(rope_parameters={"rope_type": "yarn", "rope_theta": 1e6})
    with pytest.raises(ValueError, match="differ"):
        Lfm2MoeForCausalLM(Lfm2MoeConfig.from_dict(TINY), weights={"model.norm.weight": 1})


def test_every_key_of_the_file_is_mapped_by_name():
    cfg = Lfm2MoeConfig.from_dict(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.vocab_size, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_dense_layers, cfg.conv_L_cache) == (
        2048, 10, 32, 8, 11776, 1536, 65536, 64, 4, 2, 3)
    assert (cfg.head_dim, cfg.kv_row, cfg.rope_theta, cfg.norm_eps) == (64, (4, 128), 1e6, 1e-5)
    assert cfg.period == ("full_attention", "conv", "conv", "conv")
    assert (cfg.periods, cfg.tail_start, cfg.layer_types.count("conv"),
            cfg.layer_types.count("full_attention")) == (2, 10, 8, 2)
    # what models/mla_moe.py's router reads of a config
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == (64, tuple(range(64)), True, 1)
    # the published depth: nine whole turns and [attention, conv] unrolled
    whole = Lfm2MoeConfig.from_dict({**PUBLISHED, "num_hidden_layers": 40, "layer_types": (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"])})
    assert (whole.periods, whole.tail_start) == (9, 38)


@pytest.mark.parametrize("kw,path", [
    ({"tp": 2}, "tp"), ({"int8": True}, "int8"),
    ({"spec_k": 2}, "speculative verify"),
    ({"prefix_cache": True}, "prefix cache / tail prefill"),
    ({"prefill_chunk": 16}, "chunked prefill")])
def test_unsupported_engine_paths_raise_at_construction(tiny, kw, path):
    with pytest.raises(NotImplementedError) as e:
        _engine(tiny[0], **kw)
    assert "lfm2_moe" in str(e.value) and path in str(e.value)


def test_unsupported_calls_raise_at_the_call(tiny):
    net, _ = tiny
    with _engine(net) as eng:
        for call in (eng.snapshot, eng.handoff, lambda: eng.adopt({})):
            with pytest.raises(NotImplementedError, match="lfm2_moe.*snapshots"):
                call()
    with pytest.raises(NotImplementedError, match="serving.Engine"):
        net.generate(np.zeros((1, 4), np.int64))


def test_no_first_call_searches_for_a_kernel_config():
    """The kernel registry answers the new shapes from its defaults with the
    autotuner off (the default flag): the expert kernel at 2048 x 1536 in
    three slices of 512, the packed read in chunks of 32 blocks (8 until PR 46)."""
    from paddle_tpu.ops.kernels import paged_attention_key
    from paddle_tpu.ops.kernels.paged_attention import blocks_per_chunk
    from paddle_tpu.ops.kernels.moe_experts import moe_experts_key
    from paddle_tpu.ops.kernels.registry import resolve_config

    experts = resolve_config("moe_experts", moe_experts_key(256, 64, 2048, 1536, jnp.bfloat16))
    assert experts == {"rows_per_tile": 0, "f_slice": 512} and 1536 % 512 == 0
    key = paged_attention_key(64, 128, 16, 4, 8, 128, jnp.bfloat16)
    # 0: the chunk follows a block's bytes (32 blocks of 16 KB), no search
    assert resolve_config("paged_attention", key) == {"blocks_per_chunk": 0}
    assert blocks_per_chunk(key) == 32
