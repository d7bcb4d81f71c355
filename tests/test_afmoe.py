"""The window-and-full / gated-attention / routed-expert share
(``paddle_tpu.models.afmoe``) against its plain reference
(``benchmark/reference/afmoe.py``: float32 ``jax.numpy``, one sequence at a
time, written from the published equations and sharing no code with the
program), at a small size on the CPU with seeded weights: a window of 8, two
dense layers, one scanned period [sliding, full, sliding, sliding] and half a
one unrolled, 4 of 16 experts held at 4 a token (the family's ``REHEARSE``),
and a ten-layer variant of two whole periods.

Tolerances. Everything here runs in float32 on both sides, so what is left
between program and reference is the order of the sums: ``TOL`` = 2e-5 of the
largest reference value + 2e-6, the bound ``paged_attention``'s tests hold. A
key left unrotated, a ring entry read from the slot's last request, a window
one position too wide or a gate left out moves the logits by a hundred times
that and more, which the controls below show. A served token is held to the
reference by its GAP (how far its reference logit lies under the reference's
best): an exact 0 in float32 unless two logits tie to rounding, so <= 1e-5.
"""
import functools
import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
import paddle_tpu.models.generation as G
from paddle_tpu.models import afmoe as A
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.ops.kernels.window_flash import window_flash, window_flash_takes
from paddle_tpu.profiler import spans
from paddle_tpu.serving import Engine

REPO = pathlib.Path(__file__).parent.parent


def _family():
    spec = importlib.util.spec_from_file_location(
        "afmoe_family_under_test", REPO / "benchmark/families/afmoe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
REF = FAM.reference
PUBLISHED = json.loads((REPO / "benchmark/configs/trinity-mini-16l-ep8.json").read_text())
# initializer_range 1 / sqrt(64): a projection of the normed stream then keeps
# its size, as 0.02 x sqrt(2048) = 0.9 does at the published width
TINY = {**PUBLISHED, **FAM.REHEARSE, "initializer_range": 0.125}
S, F = "sliding_attention", "full_attention"
# ten layers: two dense and two whole periods, nothing unrolled behind them
WHOLE = {**TINY, "num_hidden_layers": 10, "layer_types": [S, S] + [S, F, S, S] * 2}
W = TINY["sliding_window"]
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
def whole():
    return build(WHOLE)


def _padded(ids, to=64):
    """``ids`` with zeros behind them up to a multiple of ``to``: every layer is
    causal, so what lies behind a position does not reach it, and a helper
    compiles once a padded length and not once a length."""
    ids = np.asarray(ids)
    return np.pad(ids, (0, -len(ids) % to))


def reference(cfg, w, ids):
    """The reference pads to ONE length itself (``max_position_embeddings``
    here, 256)."""
    return np.asarray(REF.forward_logits(cfg, w, np.asarray(ids)[None], "f32")[0])


def _gaps(cfg, w, prompt, out):
    """How far each served token's reference logit lies under the best."""
    ref = reference(cfg, w, out[:-1])[len(prompt) - 1:]
    return ref.max(-1) - ref[np.arange(len(ref)), np.asarray(out[len(prompt):])]


def _kernels(monkeypatch, on):
    real = G.afmoe_decode_state
    monkeypatch.setattr(G, "afmoe_decode_state", lambda m, k=None: real(m, on))


def _engine(net, **kw):
    return Engine(net, **{**dict(block_size=BS, num_blocks=64, max_batch=4,
                                 max_seq_len=64), **kw})


@functools.partial(jax.jit, static_argnames=("cfg",))
def _took(cfg, w, ids):
    """(expert layers, positions, held experts) bool: which of the experts held
    here the reference's router sends each position of ``ids`` to."""
    held, eps = jnp.asarray(REF.held(cfg)), cfg["rms_norm_eps"]
    x = w["wte"][ids].astype(jnp.float32) * cfg["hidden_size"] ** 0.5
    out = []
    for i, kind in enumerate(cfg["layer_types"]):
        lw = REF.layer_leaves(cfg, w, i)
        if "mlp.router.w" in lw:
            mid = x + REF.rms(REF.attention(cfg, lw, jnp.asarray(kind == S), REF.rms(
                x, lw["in_norm.g"], eps), "f32"), lw["post_attn_norm.g"], eps)
            out.append(REF.routing(REF._router(cfg), lw, REF.rms(
                mid, lw["pre_mlp_norm.g"], eps), "f32")[:, held] > 0)
        x, _ = REF.layer(cfg, lw, kind, x, "f32")
    return jnp.stack(out)


def _reference_counts(cfg, w, ids, upto=None):
    """(expert layers, held experts): how many of ``ids``'s positions
    (``upto``: that one alone) the reference's router sends to each expert
    held here."""
    took = np.asarray(_took(REF.static(cfg), w, jnp.asarray(_padded(ids))))[:, :len(ids)]
    return took[:, upto].astype(int) if upto is not None else took.sum(1)


# -- (a) the model's forward, and the programs logit by logit ---------------------
@pytest.mark.parametrize("which", ["tiny", "whole"])
def test_forward_equals_the_reference(which, request):
    """Prompts of three windows: every position past the eighth sees a band."""
    net, w = request.getfixturevalue(which)
    cfg = TINY if which == "tiny" else WHOLE
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    out = np.asarray(net(ids)._data)
    for row in range(2):
        close(out[row], reference(cfg, w, ids[row]), which)


class Programs:
    """The arch's prefill and decode programs over pools of their own, as the
    engine builds them, with the logits of every step in hand. Each program is
    jitted ONCE a (model, kernels, shape): a process that compiled the scans
    anew every step would run out of room for code."""

    _compiled = {}

    def __init__(self, net, kernels, blocks=24, max_batch=3, table=8):
        key = (id(net), kernels)
        if key not in self._compiled:
            self._compiled[key] = (G.afmoe_decode_state(net, kernels)[1:3], {})
        (self.arch, self.params), self.fns = self._compiled[key]
        self.mb = table
        self.pools = tuple(
            jnp.zeros(shape, dtype or jnp.float32) for _, shape, dtype in
            G.cache_pools(self.arch, 0, blocks, BS, max_batch))

    def _fn(self, kind, B, bucket=None):
        if (kind, B, bucket, self.mb) in self.fns:
            return self.fns[kind, B, bucket, self.mb]
        arch = self.arch
        if kind == "prefill":
            fn = jax.jit(G.build_paged_prefill(arch, B, bucket, BS, self.mb))
        else:
            inner = G.build_paged_decode_kernel(arch, B, BS, self.mb)

            def fn(params, pools, tables, pos, slots, toks):
                *new, _, counts = inner(params, *pools, tables, pos, slots, toks,
                                        jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(0))
                # the logits themselves, through the same stack
                x = arch["embed"](params, toks, pos)[:, None]
                bids = jnp.take_along_axis(tables, (pos // BS)[:, None], axis=1)[:, 0]
                x, _, _ = arch["decode_stack"](params, x, pools, tables, pos, bids,
                                               pos % BS, slots, BS)
                return tuple(new), arch["head"](params, x[:, -1]), counts

            fn = jax.jit(fn)
        self.fns[kind, B, bucket, self.mb] = fn
        return fn

    def prefill(self, prompts, bucket, tables, slots):
        ids = np.zeros((len(prompts), bucket), np.int32)
        for r, p in enumerate(prompts):
            ids[r, :len(p)] = p
        *pools, logits, counts = self._fn("prefill", len(prompts), bucket)(
            self.params, jnp.asarray(ids), jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.asarray(tables, jnp.int32), jnp.asarray(slots, jnp.int32), *self.pools)
        self.pools = tuple(pools)
        return np.asarray(logits), np.asarray(counts)

    def step(self, toks, pos, tables, slots):
        toks, pos, tables, slots = (jnp.asarray(a, jnp.int32)
                                    for a in (toks, pos, tables, slots))
        self.pools, logits, counts = self._fn("decode", len(toks))(
            self.params, self.pools, tables, pos, slots, toks)
        return np.asarray(logits), np.asarray(counts)


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
@pytest.mark.parametrize("n", [1, 5, 8, 13, 16], ids=lambda n: f"prompt_{n}")
def test_prefill_then_decode_equals_the_reference_logits(tiny, kernels, n):
    """A prompt of ``n`` (one token, inside the window, the window exactly,
    past it: the prompt fills the ring, a bucket's edge) beside a neighbour of
    another length in one bucket, then twelve decode steps through the caches
    (the ring wraps at least once): every logit row is the reference's full
    forward pass at that position, and the counts the programs land are the
    reference router's over the held experts."""
    net, w = tiny
    seq = np.random.default_rng(7).integers(0, TINY["vocab_size"], n + 12)
    other = np.random.default_rng(8).integers(0, TINY["vocab_size"], 11)
    full = reference(TINY, w, seq)
    prog = Programs(net, kernels)
    tables = [[1, 2, 3, 4, 0, 0, 0, 0], [5, 6, 7, 0, 0, 0, 0, 0]]
    logits, counts = prog.prefill([seq[:n], other], 16, tables, [1, 2])
    close(logits[0], full[n - 1], "prefill")
    close(logits[1], reference(TINY, w, other)[-1], "the neighbour's prefill")
    assert np.array_equal(counts, _reference_counts(TINY, w, seq[:n])
                          + _reference_counts(TINY, w, other))
    for t in range(n, n + 12):
        # the neighbour's row pads the bucket from here on: slot 0, block 0
        logits, counts = prog.step([seq[t], 0], [t, 0], [tables[0], [0] * 8], [1, 0])
        close(logits[0], full[t], f"decode at {t}")
        assert np.array_equal(counts, _reference_counts(TINY, w, seq, upto=t))


def test_padding_rows_leave_slot_0_and_block_0_to_themselves(tiny):
    """Rows that pad a bucket write the trash slot and the trash block and
    nothing else: a live row's logits are the same with three of them beside
    it as alone, and no other slot's ring or block changes."""
    net, w = tiny
    seq = np.random.default_rng(9).integers(0, TINY["vocab_size"], 12)
    table = [1, 2, 0, 0, 0, 0, 0, 0]
    alone, padded = Programs(net, False), Programs(net, False)
    alone.prefill([seq[:10]], 16, [table], [2])
    padded.prefill([seq[:10], [], []], 16, [table, [0] * 8, [0] * 8], [2, 0, 0])
    for kind, a, b in zip(("paged", "paged", "window", "window"), alone.pools, padded.pools):
        kept = np.ones(a.shape[1], bool)
        kept[:1 if kind == "paged" else W // BS] = False  # block 0 / slot 0's ring
        assert np.array_equal(np.asarray(a)[:, kept], np.asarray(b)[:, kept])
    one, _ = alone.step([seq[10]], [10], [table], [2])
    many, _ = padded.step([seq[10], 0, 0, 0], [10, 0, 0, 0], [table] + [[0] * 8] * 3,
                          [2, 0, 0, 0])
    assert np.abs(one[0] - many[0]).max() <= tol(one)
    close(one[0], reference(TINY, w, seq)[10])


# -- (b) through serving.Engine ------------------------------------------------------
@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
@pytest.mark.parametrize("which", ["tiny", "whole"])
def test_serving_equals_the_reference_forward(which, monkeypatch, kernels, request):
    """Through ``serving.Engine``, contexts of 1, 2.5 and 5 windows in one
    batch: a prompt of 3 that ends at the window, prompts of 12 and 30 that are
    longer than the window and fill the ring at prefill (on both sides of the
    buckets' edges 16 and 32), a prompt of 20 whose answer wraps the ring twice
    more, and two requests that join once the others are under way. Every
    served token is the reference's best at its position, and the expert table
    the engine keeps is the reference router's over every position served."""
    net, w = request.getfixturevalue(which)
    cfg = TINY if which == "tiny" else WHOLE
    _kernels(monkeypatch, kernels)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (3, 12, 30, 20, 1, 17)]
    new = [5, 8, 10, 20, 7, 23]   # contexts 8, 20, 40, 40, 8, 40
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


def test_a_slot_is_clean_for_its_next_request(tiny):
    """One row slot, two requests one after the other: the first leaves a ring
    full of ITS keys, the second is shorter than the window, so the entries it
    never reaches still hold the first's: they are never read."""
    net, w = tiny
    rng = np.random.default_rng(4)
    first = rng.integers(0, TINY["vocab_size"], 19).astype(np.int32)
    second = rng.integers(0, TINY["vocab_size"], 2).astype(np.int32)
    with _engine(net, max_batch=1) as eng:
        eng.submit(first, max_new_tokens=9).result(timeout=600)
        out = eng.submit(second, max_new_tokens=5).result(timeout=600)
    assert _gaps(TINY, w, second, out).max() <= 1e-5


@pytest.mark.parametrize("fault", [{"sliding_window": W + 1}, {"rope_theta": 100.0},
                                   {"mup_enabled": False}], ids=lambda f: next(iter(f)))
def test_the_check_sees_a_fault(tiny, fault):
    """The controls: the same comparison fails, by orders of magnitude over
    the tolerance, when a window reads one position more, a window layer's keys
    are rotated by other angles, or the embedding is left unscaled."""
    net, w = tiny
    ids = np.random.default_rng(5).integers(0, TINY["vocab_size"], 20)
    ref = reference(TINY, w, ids)
    close(np.asarray(net(ids[None])._data)[0], ref)
    broken, _ = FAM.build({**TINY, **fault}, w)
    assert np.abs(np.asarray(broken(ids[None])._data)[0] - ref).max() > 100 * tol(ref)


# -- (c) one chip's share of a layer -----------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test. Eight chips hold 2 of 16 experts each and all
    hold the shared expert: the routed parts of the eight shares (what each
    chip's ``moe_ffn`` gives less the shared expert, which every chip computes
    alike) plus the shared expert counted ONCE are what the uncut reference
    gives for the whole expert FFN; the counts of the shares side by side are
    the router's over all 16."""
    rng = np.random.default_rng(6)
    d, f, E, k = 64, 32, 16, 4
    draw = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.125, jnp.float32)
    w = {"mlp.router.w": draw(d, E), "mlp.router.e_bias": draw(E) * 0.1,
         "mlp.experts.gate": draw(E, d, f), "mlp.experts.up": draw(E, d, f),
         "mlp.experts.down": draw(E, f, d), "mlp.shared.gate.w": draw(d, f),
         "mlp.shared.up.w": draw(d, f), "mlp.shared.down.w": draw(f, d)}
    x = draw(11, d) * 8
    uncut = {**TINY, "num_experts": E, "published": {"num_experts": E}}
    want = np.asarray(REF.expert_ffn(REF._router(uncut), w, x, "f32"))
    shared = A.M.gated_mlp(x, w["mlp.shared.gate.w"], w["mlp.shared.up.w"],
                           w["mlp.shared.down.w"])
    total, counts = shared, []
    for chip in range(8):
        mine = (2 * chip, 2 * chip + 1)
        cfg = AfmoeConfig.from_dict({**TINY, "num_experts": E, "held_experts": mine})
        part, c = A.M.moe_ffn(
            cfg, {"router": w["mlp.router.w"], "e_bias": w["mlp.router.e_bias"],
                  "experts_gate": w["mlp.experts.gate"][jnp.asarray(mine)],
                  "experts_up": w["mlp.experts.up"][jnp.asarray(mine)],
                  "experts_down": w["mlp.experts.down"][jnp.asarray(mine)],
                  "shared_gate": w["mlp.shared.gate.w"], "shared_up": w["mlp.shared.up.w"],
                  "shared_down": w["mlp.shared.down.w"]}, x, jnp.ones((11,), bool))
        total = total + (part - shared)
        counts.append(np.asarray(c)[list(mine)])
        # one share alone is NOT the layer: the absent experts' part is left out
        assert np.abs(np.asarray(part) - want).max() > 100 * tol(want)
    close(total, want, "the shares' sum")
    assert np.concatenate(counts).sum() == 11 * k
    assert np.array_equal(np.concatenate(counts),
                          (np.asarray(REF.routing(REF._router(uncut), w, x, "f32")) > 0).sum(0))


# -- (d) a prompt's attention is no (T, T) product ------------------------------------
def _dense(q, k, v, window):
    """The dense masked product the blocked forms replace."""
    B, T, H, D = q.shape
    G_ = k.shape[2]
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(B, T, G_, H // G_, D), k,
                   precision="highest") * D ** -0.5
    t = jnp.arange(T)
    sees = t[None, :] <= t[:, None]
    if window is not None:
        sees &= t[:, None] - t[None, :] < window
    p = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), -1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision="highest").reshape(B, T, H, D)


def _qkv(T, B=2, H=4, G_=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, T, n, D)), jnp.float32)
                 for n in (H, G_, G_))


@pytest.mark.parametrize("T,window,block", [
    (100, 8, 32), (100, None, 32),   # T no multiple of the block: padded inside
    (64, 16, 16), (64, 17, 16),      # the band's edge on a block's boundary, and inside one
    (48, 100, 16), (40, None, 512),  # T < W; one block
], ids=lambda x: str(x))
def test_blocked_prompt_attention_equals_the_dense_masked_product(T, window, block):
    q, k, v = _qkv(T)
    close(A.prompt_attention_plain(q, k, v, window, block=block), _dense(q, k, v, window))


@pytest.mark.parametrize("T,window,blocks", [
    (100, 8, (16, 32)), (100, None, (16, 32)),   # T padded to whole blocks
    (64, 16, (16, 16)),    # the band's low edge ON a key block's boundary
    (64, 17, (16, 16)),    # ... and one key inside the next block
    (96, 40, (32, 16)),    # query blocks wider than key blocks
    (48, 100, (16, 32)),   # T < W: the window never binds
    (40, None, (64, 512)),  # the defaults, cut to the prompt
], ids=lambda x: str(x))
def test_window_flash_under_the_interpreter_equals_the_plain_form(T, window, blocks):
    """Ragged lengths: row 1 is a third of the bucket, and the blocks of
    queries wholly past it are never computed (zeros)."""
    q, k, v = _qkv(T, seed=1)
    B, _, H, D = q.shape
    lens = np.asarray([T, max(T // 3, 1)], np.int32)
    got = window_flash(q.reshape(B, T, -1), k.reshape(B, T, -1), v.reshape(B, T, -1),
                       jnp.asarray(lens), heads=H, window=window, interpret=True,
                       config={"block_q": blocks[0], "block_k": blocks[1]}).reshape(q.shape)
    want = A.prompt_attention_plain(q, k, v, window, block=32)
    for b in range(B):
        close(got[b, :lens[b]], want[b, :lens[b]], f"row {b}")
    assert np.isfinite(np.asarray(got)).all()
    past = -(-int(lens[1]) // blocks[0]) * blocks[0]
    assert not np.asarray(got)[1, past:].any()


def test_the_kernel_takes_what_fits_and_the_plain_form_the_rest():
    assert window_flash_takes(8192, 128, jnp.bfloat16, interpret=False)
    assert window_flash_takes(16, 128, jnp.bfloat16, interpret=False)
    assert not window_flash_takes(8192, 64, jnp.bfloat16, interpret=False)     # half a line
    assert not window_flash_takes(32768, 128, jnp.bfloat16, interpret=False)   # K/V not resident
    assert window_flash_takes(100, 16, jnp.float32, interpret=True)
    with pytest.raises(ValueError, match="window_flash_takes"):
        window_flash(jnp.zeros((1, 16, 64)), jnp.zeros((1, 16, 32)), jnp.zeros((1, 16, 32)),
                     jnp.ones((1,), jnp.int32), heads=4, interpret=False)


def test_band_tokens_counts_the_pairs_inside_the_band():
    assert A.band_tokens([5]) == 15 == A.band_tokens([5], 8)
    assert A.band_tokens([12], 8) == 36 + 4 * 8
    assert A.band_tokens([5, 12], 8) == 15 + 68 and A.band_tokens([]) == 0
    # one long prompt of the cell: 5,632 positions under a window of 2,048
    assert A.band_tokens([5632], 2048) == 2048 * 2049 // 2 + 3584 * 2048


def test_rotation_only_in_sliding_layers(tiny):
    """A full layer has no positions at all: its attention does not change
    when every position shifts; a window layer's does."""
    net, _ = tiny
    cfg = net.config
    _, _, params, _ = net.decode_state()
    w, freqs = params["lead"][0], A.rope_freqs(cfg)
    u = jnp.asarray(np.random.default_rng(3).standard_normal((1, 6, 64)), jnp.float32)
    pos = jnp.arange(6)[None]
    seen = []

    def attend(q, k, v):
        seen.append((q, k))
        return A.prompt_attention_plain(q, k, v, None)

    out = {(kind, shift): A.attention(cfg, freqs, w, kind, u, pos + shift, attend)
           for kind in (F, S) for shift in (0, 5)}
    assert np.array_equal(out[F, 0], out[F, 5])
    assert np.array_equal(seen[0][1], seen[1][1])          # the keys a full layer caches
    assert np.abs(np.asarray(seen[2][1] - seen[3][1])).max() > 0.1   # rotated keys move
    # scores of rotated pairs depend on the DIFFERENCE of positions alone
    close(out[S, 0], out[S, 5], "a window layer under a common shift")


# -- (e) pools, spans, counters ----------------------------------------------------------
def test_the_pools_are_a_kind_each_over_their_own_layers(tiny):
    net, _ = tiny
    with _engine(net) as eng:
        assert eng._cache_kinds == ("paged", "paged", "window", "window")
        # two full layers: 64 blocks of 8 tokens x 2 heads, lines of 16; six
        # window layers: 4 slots + the trash slot, a ring of 8 = one block each
        assert [p.shape for p in eng._cache] == [(2, 64, 16, 16)] * 2 + [(6, 5, 16, 16)] * 2
        assert eng._paged_kernel and eng._row_slots is not None and eng._window == W
        assert eng.stats()["cache_bytes"] == {"paged": 2 * 2 * 64 * 16 * 16 * 4,
                                              "window": 2 * 6 * 5 * 16 * 16 * 4}
        assert np.asarray(eng.stats()["expert_tokens"]).shape == (6, 4)


def test_spans_count_real_rows_and_tokens_alone(tiny):
    """One live row in a decode bucket of 4, a prompt of 11 in a bucket of 16:
    what the spans carry is of the real row and its real tokens."""
    net, _ = tiny
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, decode_buckets=(4,)) as eng:
            eng.submit(np.arange(11, dtype=np.int32), max_new_tokens=8).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    fills = [sp.attrs for sp in seen if sp.name == "prefill"]
    assert [(a["prompt_tokens"], a["bucket_t"]) for a in fills] == [(11, 16)]
    # 11 queries: 66 pairs under the diagonal, of which a window of 8 keeps 36 + 3 x 8
    assert (fills[0]["band_tokens_full"], fills[0]["band_tokens_window"]) == (66, 60)
    assert fills[0]["experts_touched"] > 0
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert len(steps) == 6  # 7 decode steps, the first only enqueued
    # the step that lands writes position 11, 12, ...: its context is one more
    assert [a["paged_kv_tokens"] for a in steps] == list(range(12, 18))
    assert all(a["window_tokens"] == W for a in steps)
    assert all(a["rows"] == 1 and a["bucket"] == 4 for a in steps)
    # one live row picks 4 of 16 experts a layer, of which 4 are held: at most 4 x 6
    assert all(0 <= a["experts_touched"] == a["expert_assignments"] <= 24 for a in steps)
    assert not any("shared_kv_tokens" in a or "state_rows" in a for a in steps)
    # the plain gather has no chunks: none of the kernel's counts (PR 46)
    assert not any(k in a for a in steps for k in ("paged_blocks", "paged_chunks", "paged_full_chunks"))


def test_spans_carry_the_pairs_and_those_a_held_expert_took(tiny):
    """PR 48: a span that carries ``experts_touched`` also says how many
    (token, choice) pairs its live tokens made over the expert layers
    (``expert_pairs``) and how many of them an expert held here took
    (``expert_pairs_held``: the rows the expert product's combine moves).
    This chip holds 4 of the router's 16 experts and a token chooses 4."""
    net, w = tiny
    layers = TINY["num_hidden_layers"] - TINY["num_dense_layers"]
    k = TINY["num_experts_per_tok"]
    prompt = np.arange(11, dtype=np.int32)
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, decode_buckets=(4,)) as eng:
            eng.submit(prompt, max_new_tokens=4).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    fill, = [sp.attrs for sp in seen if sp.name == "prefill"]
    assert fill["expert_pairs"] == 11 * k * layers
    assert fill["expert_pairs_held"] == fill["expert_assignments"] \
        == int(_reference_counts(TINY, w, prompt).sum())
    assert 0 < fill["expert_pairs_held"] < fill["expert_pairs"]
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert steps and all(a["expert_pairs"] == k * layers for a in steps)
    assert all(a["expert_pairs_held"] == a["expert_assignments"] <= k * layers
               for a in steps)


def test_decode_spans_carry_the_block_table_reads_copy_schedule(tiny, monkeypatch):
    """``paged_blocks`` / ``paged_chunks`` / ``paged_full_chunks`` of a
    ``decode_step`` span are what the landing step's positions give by hand,
    summed over the step's calls of the kernel, BESIDE what the span said of
    the caches before (the plain gather has no chunks and says nothing: the
    test of the spans above). A
    table of 4 blocks of 8 tokens, so chunks of 4: a row that writes position
    22, 23 reads 3 blocks a call (a partial chunk), 24, 25 reads 4 (a full
    one). Two full layers by the position; six rings of one block (a chunk of
    one: always full)."""
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
    assert all(a["window_tokens"] == W for a in steps)
    assert [(a["paged_blocks"], a["paged_chunks"], a["paged_full_chunks"])
            for a in steps] == [(12, 8, 6), (12, 8, 6), (14, 8, 8), (14, 8, 8)]


# -- (f) what is not built is refused by name ------------------------------------------
@pytest.mark.parametrize("kw,match", [
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"n_group": 2}, "group-limited routing"), ({"topk_group": 2}, "group-limited routing"),
    ({"score_func": "softmax"}, "score_func 'softmax'"),
    ({"layer_types": ("sliding_attention", "conv"), "num_hidden_layers": 2}, "layer_types"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"num_shared_experts": 2}, "more than one shared expert"),
    ({"num_attention_heads": 6, "num_key_value_heads": 4}, "6 query heads on 4"),
    ({"num_dense_layers": 9}, "num_dense_layers 9"),
    ({"held_experts": (0, 128)}, "held_experts")])
def test_unknown_mechanisms_are_refused_by_name(kw, match):
    with pytest.raises(NotImplementedError, match=match):
        AfmoeConfig(**kw)


def test_weights_that_do_not_fit_are_refused():
    with pytest.raises(ValueError, match="differ"):
        AfmoeForCausalLM(FAM.program_config(TINY), weights={"model.norm.weight": 1})
    assert FAM.state_key("h3.stack.mlp.router.e_bias", 2) == "model.body.1.mlp.router.expert_bias"
    assert FAM.state_key("h15.attn.qkvg.w", 2) == "model.layers.15.self_attn.qkvg.weight"
    with pytest.raises(NotImplementedError, match="expects layers of both"):
        G._afmoe_arch(AfmoeConfig(layer_types=(F, F), num_hidden_layers=2), False)


def test_every_key_of_the_file_is_mapped_by_name():
    cfg = FAM.program_config(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.vocab_size, cfg.num_experts_per_tok,
            cfg.num_dense_layers, cfg.sliding_window, cfg.num_shared_experts) == (
        2048, 16, 32, 4, 128, 6144, 1024, 25024, 8, 2, 2048, 1)
    assert (cfg.kv_row, cfg.rope_theta, cfg.rms_norm_eps, cfg.mup_enabled,
            cfg.max_position_embeddings) == ((4, 128), 10000, 1e-5, True, 131072)
    assert cfg.period == (S, F, S, S)
    assert (cfg.periods, cfg.tail_start, cfg.layer_types.count(S),
            cfg.layer_types.count(F)) == (3, 14, 12, 4)
    # the router keeps its published width; 16 of its 128 experts are held here
    assert (cfg.num_experts, cfg.n_routed_experts, cfg.experts_held, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == (128, 128, tuple(range(16)), True, 2.826)
    shapes = {k: s for k, s, _ in AfmoeForCausalLM.parameter_specs(cfg)}
    assert shapes["model.body.1.mlp.router.weight"] == (3, 2048, 128)
    assert shapes["model.body.1.mlp.experts.gate"] == (3, 16, 2048, 1024)
    assert shapes["model.layers.0.self_attn.qkvg.weight"] == (2048, 9216)
    assert shapes["lm_head.weight"] == (2048, 25024)
    # the published depth: seven whole periods and [sliding, full] unrolled
    whole = AfmoeConfig.from_dict({**PUBLISHED, "num_hidden_layers": 32,
                                   "layer_types": PUBLISHED["layer_types"] * 2})
    assert (whole.periods, whole.tail_start) == (7, 30)


@pytest.mark.parametrize("kw,path", [
    ({"tp": 2}, "tp"), ({"int8": True}, "int8"),
    ({"spec_k": 2}, "speculative verify"),
    ({"prefix_cache": True}, "prefix cache / tail prefill"),
    ({"prefill_chunk": 16}, "chunked prefill")])
def test_unsupported_engine_paths_raise_at_construction(tiny, kw, path):
    with pytest.raises(NotImplementedError) as e:
        _engine(tiny[0], **kw)
    assert "afmoe" in str(e.value) and path in str(e.value)


def test_unsupported_calls_raise_at_the_call(tiny):
    net, _ = tiny
    with _engine(net) as eng:
        for call in (eng.snapshot, eng.handoff, lambda: eng.adopt({})):
            with pytest.raises(NotImplementedError, match="afmoe.*snapshots"):
                call()
    with pytest.raises(NotImplementedError, match="serving.Engine"):
        net.generate(np.zeros((1, 4), np.int64))


def test_no_first_call_searches_for_a_kernel_config():
    """The kernel registry answers the new shapes from its defaults with the
    autotuner off (the default flag)."""
    from paddle_tpu.ops.kernels import paged_attention_key
    from paddle_tpu.ops.kernels.paged_attention import blocks_per_chunk
    from paddle_tpu.ops.kernels.registry import resolve_config
    from paddle_tpu.ops.kernels.window_flash import window_flash_key

    assert resolve_config("window_flash", window_flash_key(
        1, 8192, 32, 4, 128, 2048, jnp.bfloat16)) == {"block_q": 64, "block_k": 512}
    # 0: the chunk follows a block's bytes (32 blocks of 16 KB), no search
    key = paged_attention_key(64, 512, 16, 4, 8, 128, jnp.bfloat16)
    assert resolve_config("paged_attention", key) == {"blocks_per_chunk": 0}
    assert blocks_per_chunk(key) == 32
