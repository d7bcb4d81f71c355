"""The Mamba / differential-attention hybrid (``paddle_tpu.models.phi4flash``)
against its plain reference (``benchmark/reference/phi4flash.py``: float32
``jax.numpy``, a ``lax.scan`` over the tokens, written from the published
equations and sharing no code with the program), at a small size on the CPU
with seeded weights: eight layers, every kind present, a window of 8 that
contexts of 40 wrap five times.

Tolerances. Everything here runs in float32 on both sides, so what is left
between program and reference is the order of the sums: ``TOL`` = 2e-5 of the
largest reference value + 2e-6, the bound ``paged_attention``'s tests hold. A
window one key short or a state left from the slot's last request moves the
logits by a hundred times that and more, a state dropped by ten times at this
size, which the controls below show.
"""
import hashlib
import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
import paddle_tpu.models.generation as G
from paddle_tpu.models import phi4flash as P
from paddle_tpu.models.phi4flash import PhiFlashConfig, PhiFlashForCausalLM
from paddle_tpu.ops.kernels import selective_scan as SS
from paddle_tpu.profiler import counters, spans
from paddle_tpu.serving import Engine

REPO = pathlib.Path(__file__).parent.parent


def _family():
    spec = importlib.util.spec_from_file_location(
        "phi4flash_family_under_test", REPO / "benchmark/families/phi4flash.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
PUBLISHED = json.loads((REPO / "benchmark/configs/phi4-mini-flash-3p8b.json").read_text())
TINY = {**PUBLISHED, **FAM.REHEARSE}
W = TINY["sliding_window"]
BS = 8  # the engine's block in these tests: the window is one block of it


def tol(ref):
    return 2e-5 * np.abs(np.asarray(ref)).max() + 2e-6


def close(a, b, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol(b), (what, np.abs(a - b).max(), tol(b))


def build(cfg, seed=3, over=None):
    """(model, leaves) of a configuration dict, seeded as the benchmark seeds;
    ``over(leaves)`` may replace leaves before both sides get them."""
    from benchmark import weights as Wt

    w = Wt.make_weights(cfg, seed, FAM.leaf_specs(cfg))
    if over is not None:
        w = over(w)
    net, _ = FAM.build(cfg, w)
    net.eval()
    return net, w


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


def reference(cfg, w, ids):
    return np.asarray(FAM.forward_logits(cfg, w, np.asarray(ids)[None], "f32")[0])


def _gaps(cfg, w, prompt, out):
    """How far each served token's reference logit lies below the best."""
    ref = reference(cfg, w, out[:-1])[len(prompt) - 1:]
    return ref.max(-1) - ref[np.arange(len(ref)), np.asarray(out[len(prompt):])]


def _kernels(monkeypatch, on):
    real = G.phi4flash_decode_state
    monkeypatch.setattr(G, "phi4flash_decode_state", lambda m, k=None: real(m, on))


def _engine(net, **kw):
    return Engine(net, **{**dict(block_size=BS, num_blocks=64, max_batch=4,
                                 max_seq_len=64), **kw})


# -- (a) the programs, logit by logit -------------------------------------------------
class Programs:
    """The arch's prefill and decode programs over pools of their own, as the
    engine builds them, with the logits of every step in hand."""

    def __init__(self, net, kernels, block_size=BS, blocks=24, max_batch=3, table=8):
        _, self.arch, self.params, _ = G.phi4flash_decode_state(net, kernels)
        self.bs, self.mb = block_size, table
        self.pools = tuple(
            jnp.zeros(shape, dtype or jnp.float32) for _, shape, dtype in
            G.cache_pools(self.arch, 0, blocks, block_size, max_batch))

    def prefill(self, prompts, bucket, tables, slots):
        ids = np.zeros((len(prompts), bucket), np.int32)
        for r, p in enumerate(prompts):
            ids[r, :len(p)] = p
        fn = G.build_paged_prefill(self.arch, len(prompts), bucket, self.bs, self.mb)
        *pools, logits = fn(self.params, jnp.asarray(ids),
                            jnp.asarray([len(p) for p in prompts], jnp.int32),
                            jnp.asarray(tables, jnp.int32),
                            jnp.asarray(slots, jnp.int32), *self.pools)
        self.pools = tuple(pools)
        return np.asarray(logits)

    def step(self, toks, pos, tables, slots):
        toks, pos, tables, slots = (jnp.asarray(a, jnp.int32)
                                    for a in (toks, pos, tables, slots))
        x = self.arch["embed"](self.params, toks, pos)[:, None]
        bids = jnp.take_along_axis(tables, (pos // self.bs)[:, None], axis=1)[:, 0]
        x, self.pools = self.arch["decode_stack"](
            self.params, x, self.pools, tables, pos, bids, pos % self.bs, slots, self.bs)
        return np.asarray(self.arch["head"](self.params, x[:, -1]))


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_prefill_then_decode_equals_the_reference_logits(tiny, kernels):
    """Two rows in one program, prompts on both sides of the bucket's edge (15
    and 16 of 16), decoded to a context of 40: the window of 8 wraps five
    times, the scans run 24 single steps from the state the prefill left at
    each row's TRUE length. Every logit is the reference's."""
    net, w = tiny
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, TINY["vocab_size"], 40) for _ in range(2)]
    full = [reference(TINY, w, s) for s in seqs]
    prog = Programs(net, kernels)
    tables = [[1, 2, 3, 4, 5, 0, 0, 0], [6, 7, 8, 9, 10, 0, 0, 0]]
    lens = [15, 16]
    logits = prog.prefill([s[:n] for s, n in zip(seqs, lens)], 16, tables, [2, 1])
    for r in range(2):
        close(logits[r], full[r][lens[r] - 1], f"prefill row {r}")
    for t in range(24):
        pos = [n + t for n in lens]
        logits = prog.step([s[p] for s, p in zip(seqs, pos)], pos, tables, [2, 1])
        for r in range(2):
            close(logits[r], full[r][pos[r]], f"row {r} position {pos[r]}")


def test_the_state_after_a_padded_bucket_is_the_bare_prompt_s(tiny):
    """A prompt of 9 in a bucket of 24 against the same prompt in a bucket of
    9 (blocks of 3): the scan states, the convolution tails and the live ring
    entries are equal, so the padding behind ``lens`` reached none of them."""
    net, _ = tiny
    prompt = np.random.default_rng(1).integers(0, TINY["vocab_size"], 9)
    kept = []
    for bucket in (9, 24):
        prog = Programs(net, False, block_size=3, blocks=12, table=8)
        logits = prog.prefill([prompt], bucket, [[1, 2, 3, 4, 5, 6, 7, 8]], [1])
        kept.append((logits, *(np.asarray(p[:, 1]) for p in prog.pools[4:]),
                     *(np.asarray(p)[:, W:2 * W] for p in prog.pools[2:4])))
    for bare, padded, what in zip(*kept, ("logits", "S", "tail", "k ring", "v ring")):
        close(padded, bare, what)
    assert np.abs(kept[0][1]).max() > 1e-3  # there IS a state to compare


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_serving_equals_the_reference_forward(tiny, monkeypatch, kernels):
    """Through ``serving.Engine``: prompts on both sides of the prefill
    buckets' edges (8, 16, 32), answers of different lengths so that rows
    leave mid-stream, and two requests that join once the others are under
    way. Every served token is the reference's best at its position (float32:
    the gap is an exact 0 unless two logits tie to rounding)."""
    net, w = tiny
    _kernels(monkeypatch, kernels)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for n in (7, 8, 9, 16, 17, 5)]
    new = [33, 12, 31, 24, 6, 35]
    with _engine(net) as eng:
        handles = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[:4], new)]
        while eng.stats()["decode_steps"] < 5:
            pass
        handles += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[4:], new[4:])]
        outs = [h.result(timeout=600) for h in handles]
        stats = eng.stats()
    for p, n, out in zip(prompts, new, outs):
        assert len(out) == len(p) + n
        assert _gaps(TINY, w, p, out).max() <= 1e-5
    assert stats["state_slots_used"] == 0 and stats["pages_used"] == 0
    assert stats["state_slots_total"] == 4


# -- (b) each kernel against its plain form ---------------------------------------
def _scan_operands(B, T, d_i, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return (jnp.abs(f(B, T, d_i)) * 0.1, f(B, T, d_i), f(B, T, N), f(B, T, N),
            -jnp.exp(f(N, d_i)), f(d_i))


def test_the_scan_in_chunks_equals_the_token_loop():
    """256 tokens are two chunks of 128 with the state carried in VMEM, 256
    channels two chunks of 128: against the ``lax.scan`` over the tokens."""
    ops = _scan_operands(2, 256, 256, 16)
    y0, S0 = SS.selective_scan_plain(*ops)
    y1, S1 = SS.selective_scan(*ops)
    close(y1, y0, "y")
    close(S1, S0, "S")
    # and a position whose Delta is 0 leaves the state as it was
    dt = ops[0].at[:, 200:].set(0.0)
    _, S_cut = SS.selective_scan(dt, *ops[1:])
    _, S_200 = SS.selective_scan_plain(*(a[:, :200] for a in ops[:4]), *ops[4:])
    close(S_cut, S_200, "stopped at 200")


def test_the_state_update_kernel_equals_its_plain_form():
    dt, c, Bm, Cm, A, D = _scan_operands(3, 1, 256, 16, seed=1)
    pool = jnp.asarray(np.random.default_rng(2).normal(size=(3, 5, 16, 256)), jnp.float32)
    slots = jnp.asarray([4, 1, 2], jnp.int32)
    args = (slots, dt[:, 0], c[:, 0], Bm[:, 0], Cm[:, 0], A, D)
    p0, y0 = SS.state_update_plain(pool, 1, *args)
    p1, y1 = SS.state_update(pool, jnp.asarray(1, jnp.int32), *args)
    close(p1, p0, "pool")
    close(y1, y0, "y")
    assert np.array_equal(np.asarray(p1[0]), np.asarray(pool[0]))  # other layers untouched
    assert np.array_equal(np.asarray(p1[1, 3]), np.asarray(pool[1, 3]))  # other slots too


def test_the_block_table_read_takes_a_pool_of_lines():
    """``paged_attention_rows`` over a 4-D pool (a block one slab of (token,
    kv head) lines, ``kv_heads`` given) and a score scale of its own equals
    the 5-D call on the same numbers."""
    from paddle_tpu.ops.kernels import paged_attention_rows

    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, kp, vp = f(2, 8, 32), f(2, 6, 4, 2, 32), f(2, 6, 4, 2, 32)
    tables, pos = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32), jnp.asarray([5, 9], jnp.int32)
    five = paged_attention_rows(q * 2.0, kp, vp, 1, tables, pos)
    four = paged_attention_rows(q, kp.reshape(2, 6, 8, 32), vp.reshape(2, 6, 8, 32), 1,
                                tables, pos, scale=2.0 * 32 ** -0.5, kv_heads=2)
    close(four, five, "4-D pool")


# -- (c) slots: evict, re-prefill, reuse ---------------------------------------------
def test_evict_and_re_prefill_mid_answer_gives_the_same_tokens(tiny):
    """A pool too small for three answers at once: a row is evicted mid-answer,
    its blocks AND its slot are freed, and the re-prefill rebuilds windows and
    states from the tokens so far. The tokens are the reference's all the
    same, and the rebuild is counted."""
    net, w = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, TINY["vocab_size"], 9).astype(np.int32) for _ in range(3)]
    before = counters().get("serve_state_rebuilds", 0)
    with _engine(net, num_blocks=11) as eng:
        handles = [eng.submit(p, max_new_tokens=30) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        stats = eng.stats()
    assert stats["state_rebuilds"] >= 1
    assert counters()["serve_state_rebuilds"] - before == stats["state_rebuilds"]
    assert stats["state_slots_used"] == 0
    for p, out in zip(prompts, outs):
        assert _gaps(TINY, w, p, out).max() <= 1e-5


def _one_after_another(net, first, second, new):
    """``second`` served alone on an engine of ONE slot that has just served
    ``first``: it takes the slot ``first`` held."""
    with _engine(net, max_batch=1) as eng:
        eng.submit(first, max_new_tokens=new).result(timeout=600)
        return eng.submit(second, max_new_tokens=new).result(timeout=600)


def test_a_slot_is_clean_for_its_next_request(tiny, monkeypatch):
    net, w = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
                     for n in (20, 6))
    out = _one_after_another(net, first, second, 20)
    assert _gaps(TINY, w, second, out).max() <= 1e-5
    # the control: a prefill that does NOT write the slot's states leaves the
    # first request's there, and the check sees it
    real = G._phi4flash_arch

    def stale(cfg, kernels):
        arch = real(cfg, kernels)
        inner = arch["prompt_stack"]

        def prompt_stack(params, x, pools, *rest):
            x, new = inner(params, x, pools, *rest)
            return x, (*new[:4], *pools[4:])

        return {**arch, "prompt_stack": prompt_stack}

    monkeypatch.setattr(G, "_phi4flash_arch", stale)
    out = _one_after_another(net, first, second, 20)
    assert _gaps(TINY, w, second, out).max() > 100 * 1e-5


def _moved_by_zeroing_the_state(net, w):
    """(how far the next logits move when the scan states are zeroed
    mid-answer, the tolerance they are held to)."""
    seq = np.random.default_rng(6).integers(0, TINY["vocab_size"], 30)
    full = reference(TINY, w, seq)
    prog = Programs(net, False)
    tables = [[1, 2, 3, 4, 0, 0, 0, 0]]
    prog.prefill([seq[:20]], 32, tables, [1])
    close(prog.step([seq[20]], [20], tables, [1])[0], full[20], "sound")
    prog.pools = (*prog.pools[:4], jnp.zeros_like(prog.pools[4]), prog.pools[5])
    return np.abs(prog.step([seq[21]], [21], tables, [1])[0] - full[21]).max(), tol(full)


def test_the_check_sees_the_state(tiny):
    """A state zeroed mid-answer moves the next logits out of the tolerance,
    nine times over at this size (where B and C are a sixth of what the
    published widths make them) and still twice over twelve tokens later:
    with Mamba's published initial values a state remembers."""
    moved, allowed = _moved_by_zeroing_the_state(*tiny)
    assert moved > 5 * allowed


# -- (d) one pool, eight readers --------------------------------------------------
def test_the_pools_are_a_kind_each_over_their_own_layers(tiny):
    net, _ = tiny
    cfg = net.config
    with _engine(net, num_blocks=16) as eng:
        kinds, shapes = eng._cache_kinds, [p.shape for p in eng._cache]
        stats = eng.stats()
    pairs, h = cfg.kv_pairs, cfg.head_dim
    assert kinds == ("paged", "paged", "window", "window", "state", "state")
    # ONE paged layer of the eight: the full-attention layer's K and V
    assert shapes[0] == shapes[1] == (1, 16, BS * pairs, 2 * h)
    # the two window layers: a ring of W tokens a slot (4 rows + the trash slot)
    assert shapes[2] == shapes[3] == (2, 5 * (W // BS), BS * pairs, 2 * h)
    # the three scan layers: N x d_i float32 and K - 1 inputs a slot
    assert shapes[4] == (3, 5, cfg.mamba_d_state, cfg.d_inner)
    assert shapes[5] == (3, 5, cfg.mamba_d_conv - 1, cfg.d_inner)
    assert eng._cache[4].dtype == jnp.float32
    assert set(stats["cache_bytes"]) == {"paged", "window", "state"}
    arch = G.phi4flash_decode_state(net)[1]
    reads = [r for _, r in arch["cache"]["layers"]]
    assert [k for k, _ in arch["cache"]["layers"]] == \
        ["state", "window", "state", "window", "state", "paged", None, None]
    assert reads == [None] * 7 + [5]  # the cross layer reads layer 5's pool


def test_the_published_sizes_give_one_eight_and_nine_layers():
    arch = G._phi4flash_arch(PhiFlashConfig.from_dict(PUBLISHED), False)
    pools = G.cache_pools(arch, 0, 8256, 16, 64)
    assert [(kind, shape[0]) for kind, shape, _ in pools] == [
        ("paged", 1), ("paged", 1), ("window", 8), ("window", 8), ("state", 9), ("state", 9)]
    assert pools[0][1] == (1, 8256, 160, 128) and pools[2][1] == (8, 65 * 32, 160, 128)
    assert pools[4][1:] == ((9, 65, 16, 5120), "float32")
    layers = arch["cache"]["layers"]
    assert [i for i, (_, r) in enumerate(layers) if r is not None] == list(range(19, 32, 2))
    assert {r for _, r in layers if r is not None} == {17}
    assert sum(k is None for k, _ in layers) == 14


def test_cross_layers_with_their_lambdas_swapped_differ():
    """Twelve layers have two cross layers on the ONE pool; each subtracts
    its second softmax by a lambda of its own. With the two layers' lambda
    vectors swapped the logits move (the vectors drawn wide enough for
    ``exp(lq . lk)`` to differ), in program and reference alike."""
    cfg = {**TINY, "num_hidden_layers": 12}
    rng = np.random.default_rng(7)

    def wide(w):
        return {k: (jnp.asarray(rng.normal(0, 0.25, v.shape), v.dtype)
                    if "cross.lambda_" in k else v) for k, v in w.items()}

    net, w = build(cfg, over=wide)
    assert w["back.cross.lambda_q1"].shape == (2, 16)
    ids = rng.integers(0, cfg["vocab_size"], 20)
    logits = np.asarray(net(ids[None])._data[0])
    close(logits, reference(cfg, w, ids), "twelve layers")
    swapped = {k: (v[::-1] if "cross.lambda_" in k else v) for k, v in w.items()}
    assert np.abs(reference(cfg, swapped, ids) - logits).max() > 30 * tol(logits)
    net2, _ = FAM.build(cfg, swapped)
    close(net2(ids[None])._data[0], reference(cfg, swapped, ids), "swapped")


# -- (e) spans and counters ------------------------------------------------------------
def test_spans_count_real_rows_and_tokens_alone(tiny):
    """One live row in a decode bucket of 4, a prompt of 5 in a bucket of 8:
    what the spans carry is of the real row and its real tokens."""
    net, _ = tiny
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, decode_buckets=(4,)) as eng:
            eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=12).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    fills = [sp.attrs for sp in seen if sp.name == "prefill"]
    assert [a["scan_tokens"] for a in fills] == [5]
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert len(steps) == 10  # 11 decode steps, the first only enqueued
    # the step that lands writes position 5, 6, ...: its context is one more
    assert [a["shared_kv_tokens"] for a in steps] == list(range(6, 16))
    assert [a["window_tokens"] for a in steps] == [min(c, W) for c in range(6, 16)]
    assert all(a["state_rows"] == 1 and a["rows"] == 1 and a["bucket"] == 4 for a in steps)
    # the plain gather has no chunks: none of the kernel's counts (PR 46)
    assert not any(k in a for a in steps for k in ("paged_blocks", "paged_chunks", "paged_full_chunks"))


def test_decode_spans_carry_the_block_table_reads_copy_schedule(tiny, monkeypatch):
    """``paged_blocks`` / ``paged_chunks`` / ``paged_full_chunks`` of a
    ``decode_step`` span are what the landing step's positions give by hand,
    summed over the step's calls of the kernel, BESIDE what the span said of
    the caches before (the plain gather has no chunks and says nothing: the
    test of the spans above). A
    table of 4 blocks of 8 tokens, so chunks of 4: a row that writes position
    22, 23 reads 3 blocks a call (a partial chunk), 24, 25 reads 4 (a full
    one). The full layer and the cross layer behind it by the position; two
    rings of one block (a chunk of one: always full)."""
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
    assert [a["shared_kv_tokens"] for a in steps] == [23, 24, 25, 26]
    assert all(a["window_tokens"] == W and a["state_rows"] == 1 for a in steps)
    assert [(a["paged_blocks"], a["paged_chunks"], a["paged_full_chunks"])
            for a in steps] == [(8, 4, 2), (8, 4, 2), (10, 4, 4), (10, 4, 4)]


def test_other_archs_carry_none_of_it():
    from serving_util import ENGINE_KW, tiny_gpt

    seen = []
    spans.add_span_observer(seen.append)
    try:
        with Engine(tiny_gpt(), **ENGINE_KW) as eng:
            eng.submit([1, 2, 3], max_new_tokens=4).result(timeout=600)
            stats = eng.stats()
    finally:
        spans.remove_span_observer(seen.append)
    assert not any("shared_kv_tokens" in sp.attrs or "scan_tokens" in sp.attrs for sp in seen)
    assert "state_slots_total" not in stats and "cache_bytes" not in stats


# -- (f) what is not built is refused by name ---------------------------------------
def test_unknown_mechanisms_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="mb_per_layer"):
        PhiFlashConfig(mb_per_layer=4)
    with pytest.raises(NotImplementedError, match="multiple of 4"):
        PhiFlashConfig(num_hidden_layers=6)
    with pytest.raises(NotImplementedError, match="hidden_act"):
        PhiFlashConfig(hidden_act="gelu")
    with pytest.raises(NotImplementedError, match="do not pair"):
        PhiFlashConfig(num_attention_heads=6, num_key_value_heads=3, hidden_size=96)
    with pytest.raises(NotImplementedError, match="untied head"):
        PhiFlashConfig(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="differ"):
        PhiFlashForCausalLM(PhiFlashConfig.from_dict(TINY),
                            weights={"model.final_layernorm.weight": 1})


def test_every_key_of_the_file_is_mapped_by_name():
    cfg = PhiFlashConfig.from_dict(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.intermediate_size, cfg.vocab_size,
            cfg.sliding_window, cfg.mb_per_layer) == (2560, 32, 40, 20, 10240, 200064, 512, 2)
    assert (cfg.head_dim, cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.dt_rank) == (64, 5120, 16, 4, 160)
    kinds = [cfg.layer_kind(i) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[18] == "gmu"
    assert abs(cfg.lambda_init(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12


@pytest.mark.parametrize("kw,path", [
    ({"tp": 2}, "tp"), ({"int8": True}, "int8"),
    ({"spec_k": 2}, "speculative verify"),
    ({"prefix_cache": True}, "prefix cache / tail prefill"),
    ({"prefill_chunk": 16}, "chunked prefill")])
def test_unsupported_engine_paths_raise_at_construction(tiny, kw, path):
    with pytest.raises(NotImplementedError) as e:
        _engine(tiny[0], **kw)
    assert "phi4flash" in str(e.value) and path in str(e.value)


def test_unsupported_calls_raise_at_the_call(tiny):
    net, _ = tiny
    with _engine(net) as eng:
        for call in (eng.snapshot, eng.handoff, lambda: eng.adopt({})):
            with pytest.raises(NotImplementedError, match="phi4flash.*snapshots"):
                call()
    with pytest.raises(NotImplementedError, match="serving.Engine"):
        net.generate(np.zeros((1, 4), np.int64))
    with pytest.raises(TypeError, match="PhiFlashForCausalLM"):
        Engine(object())


# -- (g) the archs that were served before run the programs they ran ------------------
# sha256 of the lowered decode and prefill programs (``.lower(...).as_text()``)
# of the tiny GPT of tests/serving_util.py and of the MLA arch at its family's
# rehearsal sizes, with the pools' shapes and the tables' width, recorded on
# commit b2b879f (the parent of the PR that taught the cache manager kinds)
PARENT = {
    "gpt": {"pools": [(2, 16, 8, 2, 16), (2, 16, 8, 2, 16)], "max_blocks": 8,
            "decode": "97b730ae7c87fecdf24ca4e7a39bcd250d897fff1794811fd4d80d52c7f49598",
            "prefill": "4005d84ea5bf2e58c564dfe50639508869b4be332c4c4d5491bdfad93f569903"},
    "mla_moe": {"pools": [(4, 16, 8, 128)], "max_blocks": 8,
                "decode": "ff9163613eca839197ce4b04894d1254a1fe65432bb58429d584bf2987fe92a2",
                "prefill": "cf30f994b3f3526144b5f01526e131655df5ee7dde3be125acb28fb1debf1444"},
}


def _old_arch(name):
    if name == "gpt":
        from serving_util import tiny_gpt

        return tiny_gpt()
    from benchmark import weights as Wt
    from benchmark.manifest import Manifest

    m = Manifest()
    fam = m.family("xing4")
    cfg = {**m.config("xing4-29b-a4b-8l"), **fam.REHEARSE}
    net, _ = fam.build(cfg, Wt.make_weights(cfg, 3, fam.leaf_specs(cfg)))
    net.eval()
    return net


@pytest.mark.parametrize("name", ["gpt", "mla_moe"])
def test_served_archs_keep_their_pools_tables_and_programs(name):
    sha = lambda lowered: hashlib.sha256(lowered.as_text().encode()).hexdigest()
    with Engine(_old_arch(name), block_size=8, num_blocks=16, max_batch=4,
                max_seq_len=64) as eng:
        want = PARENT[name]
        assert [p.shape for p in eng._cache] == want["pools"]
        assert all(p.dtype == jnp.float32 for p in eng._cache)
        assert eng._max_blocks == want["max_blocks"] and eng._row_slots is None
        mb = eng._max_blocks if eng._paged_kernel else 2
        ints = jnp.zeros((2, mb + G.STEP_COLS), jnp.int32)
        step = eng._get_fn("decode", 2, mb).lower(
            eng._compute_params, *eng._cache, ints, eng._no_prev, eng._key)
        assert sha(step) == want["decode"]
        fill = eng._get_fn("prefill", 4, 16).lower(
            eng._compute_params, jnp.zeros((4, 16), jnp.int32), jnp.ones((4,), jnp.int32),
            jnp.zeros((4, eng._max_blocks), jnp.int32), *eng._cache)
        assert sha(fill) == want["prefill"]
