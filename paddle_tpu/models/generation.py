"""Autoregressive generation — KV-cached compiled decode.

Parity: the reference's decoding machinery (sampling ops ``top_k_op``/
``multinomial``, ``beam_search_op``/``beam_search_decode_op``, and the fluid
decoder loops PaddleNLP builds on them). TPU-native formulation: the WHOLE
decode — prefill, per-step cache update, logits, top-k/top-p filtering,
sampling — is one jitted program per (architecture, prompt-shape,
max-length): the step loop is a ``lax.fori_loop`` whose carry holds the KV
caches, so tokens never bounce to the host between steps.

Two architecture plugs share one loop driver and the paged builders:
  GPT   — LayerNorm + learned positions + fused qkv + GELU MLP, tied head;
  Llama — RMSNorm + RoPE at absolute cache positions + GQA (grouped-query
          attention against the UN-repeated KV cache) + SwiGLU, untied head.
Each states its layer ONCE (``embed`` / ``qkv`` / ``finish`` / ``head``); the
four reads of the attention context that the programs put between ``qkv`` and
``finish`` (causal, gathered context, block table, contiguous cache) are
written once beside ``_grouped_attention``, for both plugs and for their
tensor-parallel shards. A third plug (``_mla_moe_arch``) brings the layers of
``models/mla_moe.py``, whose cache is its own; a fourth (``_phi4flash_arch``)
the five kinds of layer of ``models/phi4flash.py``, whose caches are of three
kinds (:func:`cache_pools`) and whose values cross layers, so it brings its
whole stack (``prompt_stack`` / ``decode_stack``) and not a layer.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core import random as random_state
from ..core.engine import no_grad
from ..core.tensor import Tensor

_DECODE_CACHE = {}

# Steps actually executed by the most recent non-beam generate() call: the
# eos early-exit while_loop stops as soon as every row is finished, so this
# is < max_new_tokens whenever eos cut the batch short (diagnostic). Holds
# the still-dispatched jax scalar (or a plain int on the beam path).
_LAST_DECODE_STEPS = None


def last_decode_steps() -> Optional[int]:
    """Trip count of the most recent ``generate``/``generate_llama`` decode
    loop on this process (None before the first call). Not thread-safe —
    a diagnostic for tests and telemetry, not an API. The host-blocking
    coercion happens HERE, not in generate(), so the decode dispatch stays
    asynchronous for callers that never ask."""
    return None if _LAST_DECODE_STEPS is None else int(_LAST_DECODE_STEPS)


def top_k_top_p_filtering(logits, top_k=0, top_p=1.0):
    """Mask logits outside top-k / nucleus top-p (reference top_k_op +
    sampling ops role). Pure jnp; usable inside jit."""
    V = logits.shape[-1]
    if top_k and top_k > 0:
        k = min(int(top_k), V)  # top_k beyond vocab keeps everything
        kth = jnp.sort(logits, axis=-1)[..., V - k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _grouped_attention(q, kc, vc, live, rep):
    """Attention of q (B,T,H,D) against an UN-repeated KV cache
    (B,Tk,KV,D): GQA via a grouped einsum — the repeats are never
    materialized, so the cache streams once regardless of H/KV."""
    B, T, H, D = q.shape
    KV = kc.shape[2]
    with jax.named_scope("attention"):
        scale = jnp.asarray(1.0 / np.sqrt(D), q.dtype)
        qg = q.reshape(B, T, KV, rep, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc) * scale  # (B,KV,rep,T,Tk)
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, vc)
        return o.reshape(B, T, H * D)


# ---------------------------------------------------------------------------
# The four reads of the attention context
# ---------------------------------------------------------------------------
# An arch that caches K and V per KV head (GPT, Llama, and their
# tensor-parallel shards) says what a layer IS, once: ``qkv(w, x, posm) ->
# (q (B,T,H,D), k (B,T,KV,D), v (B,T,KV,D))`` (first norm, projections, the
# rotary step at ``posm``) and ``finish(w, x, o) -> x`` (output projection,
# residual, second norm, MLP, residual), with ``rep`` = H // KV. How the
# context is READ between the two is no business of the arch: the four
# functions below are every read the programs make, each written once.
# ``posm`` is (B, T) absolute positions, or (1, T) where the rows share them.
# (A step of one token a row embeds ``toks`` (B,) and then adds the token
# axis, not the other way round: rows looked up at (B, 1) indices come back
# in a layout XLA:TPU carries through the whole step, 89 MB of temporaries
# in the gather step at GPT-3 XL's widths where this order has 6.)

def _causal_layer(arch, w, x):
    """T prompt tokens against themselves: ``x`` (B,T,H·D) -> ``(x, k, v)``,
    the K and V rows to cache (the KV heads, never the repeats)."""
    T = x.shape[1]
    q, k, v = arch["qkv"](w, x, jnp.arange(T)[None])
    live = jnp.tril(jnp.ones((T, T), bool))[None, None, None]
    o = _grouped_attention(q, k, v, live, arch["rep"])
    return arch["finish"](w, x, o), k, v


def _context_layer(arch, w, x, k_ctx, v_ctx, live, posm):
    """T fresh tokens a row at ``posm`` (B,T) against a context gathered in
    sequence order, ``k_ctx``/``v_ctx`` (B,Tp,KV,D): their fresh K/V overwrite
    the slots ``posm`` in-context before the joint causal pass (token j
    attends to the fresh K/V of tokens <= j plus the cached context), ``live``
    (B,T,Tp) masks per (row, token). One token a row (the gather decode step,
    the drafter) is its T = 1 case. Returns ``(x, k, v)``; the caller owns
    writing the fresh (B,T,KV,D) rows back for later steps."""
    rows = jnp.arange(x.shape[0])[:, None]
    q, k, v = arch["qkv"](w, x, posm)
    kc = k_ctx.at[rows, posm].set(k)
    vc = v_ctx.at[rows, posm].set(v)
    o = _grouped_attention(q, kc, vc, live[:, None, None], arch["rep"])
    return arch["finish"](w, x, o), k, v


def _block_table_layer(arch, w, x, pools, li, tables, pos, bids, offs):
    """One fresh token a row, ``x`` (B,1,H·D) at ``pos`` (B,): the row's K and
    V go into the pools at ``(bids, offs)`` BEFORE the block-table kernel
    reads them (``build_paged_decode_kernel`` says why). Returns ``(x,
    pools)``."""
    from ..ops.kernels import paged_attention_rows

    kpool, vpool = pools
    q, k, v = arch["qkv"](w, x, pos[:, None])
    kpool = kpool.at[li, bids, offs].set(k[:, 0])
    vpool = vpool.at[li, bids, offs].set(v[:, 0])
    with jax.named_scope("attention"):
        o = paged_attention_rows(q[:, 0], kpool, vpool, li, tables, pos)
    return arch["finish"](w, x, o[:, None]), (kpool, vpool)


def _contiguous_layer(arch, w, x, kv, pos):
    """One fresh token a row at the SHARED position ``pos`` (a scalar)
    against dense (B,T_max,KV,D) caches, written by ``dynamic_update_slice``:
    the ``generate()`` and beam loops. Returns ``(x, (k cache, v cache))``."""
    q, k, v = arch["qkv"](w, x, pos[None, None])
    kc = lax.dynamic_update_slice(kv[0], k, (0, pos, 0, 0))
    vc = lax.dynamic_update_slice(kv[1], v, (0, pos, 0, 0))
    live = (jnp.arange(kc.shape[1]) <= pos)[None, None, None, None, :]
    o = _grouped_attention(q, kc, vc, live, arch["rep"])
    return arch["finish"](w, x, o), (kc, vc)


def _kv_arch(embed, qkv, finish, head, kv_heads, head_dim, rep):
    """The plug of an arch that caches K and V per KV head, from its one
    statement of itself: ``embed(params, ids, posm)`` (ids of any shape at
    the absolute positions ``posm``, its shape or one that broadcasts to it),
    ``qkv``, ``finish``, ``head(params, h)`` (final norm and the LM-head
    product over whatever rows the builder selected). Beside them the two
    layer functions the paged builders call of ANY arch:

    - ``prompt_layer(w, x, live) -> (x, cached rows a pool, counts)``: a layer
      over whole prompts (``live`` marks the real positions; causality makes
      them exact whatever pads the bucket, so it is not looked at);
    - ``decode_layer(w, x, pools, li, tables, pos, bids, offs, live) -> (x,
      pools, counts)``: a layer over one fresh token a row, ``x`` (B,1,...),
      read by block table.

    ``counts`` is what an arch with routed experts reports a layer (None
    here). The MLA arch brings both functions itself (``_mla_moe_arch``)."""
    arch = {"embed": embed, "qkv": qkv, "finish": finish, "head": head,
            "kv_heads": kv_heads, "head_dim": head_dim, "rep": rep}

    def prompt_layer(w, x, live):
        x, k, v = _causal_layer(arch, w, x)
        return x, (k, v), None

    def decode_layer(w, x, pools, li, tables, pos, bids, offs, live):
        x, pools = _block_table_layer(arch, w, x, pools, li, tables, pos,
                                      bids, offs)
        return x, pools, None

    return dict(arch, prompt_layer=prompt_layer, decode_layer=decode_layer)


def _last_rows(x, lens):
    """``x`` (B,T,...) at each row's own last position ``lens - 1``: the
    batch-packed analogue of ``x[:, -1]`` under per-row prompt lengths."""
    idx = (lens - 1).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


def _next_tokens(logits, temps, key):
    """A decode step's sampling tail: rows with ``temps > 0`` sample at that
    temperature from the step's one key, rows at 0 are greedy."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = (logits / jnp.maximum(temps, 1e-6)[:, None]).astype(jnp.float32)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# GPT architecture plug
# ---------------------------------------------------------------------------

def _gpt_layer_weights(layer):
    a = layer.attn
    return {
        "ln1_w": layer.ln1.weight._data, "ln1_b": layer.ln1.bias._data,
        "qkv_w": a.qkv.weight._data, "qkv_b": a.qkv.bias._data,
        "proj_w": a.proj.weight._data, "proj_b": a.proj.bias._data,
        "ln2_w": layer.ln2.weight._data, "ln2_b": layer.ln2.bias._data,
        "up_w": layer.mlp.up.weight._data, "up_b": layer.mlp.up.bias._data,
        "down_w": layer.mlp.down.weight._data, "down_b": layer.mlp.down.bias._data,
    }


def _ln(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _head_mm(params, rows, key, transpose):
    """LM-head matmul with an optional fused int8 path.

    When the engine attached a quantized head (``params["head_q"]`` — see
    serving/int8.attach_int8_head, behind FLAGS_serve_int8_kernel) the
    weight stays int8 end-to-end through the fused dequant matmul kernel
    (bit-identical to dequantize-then-matmul, so tokens cannot change).
    Otherwise: the exact dense matmul these head fns always did."""
    hq = params.get("head_q") if isinstance(params, dict) else None
    if hq is not None:
        from ..ops.kernels import int8_matmul

        return int8_matmul(rows, hq["q"], hq["scale"], transpose_w=transpose)
    w = params[key]
    return rows @ (w.T if transpose else w)


def _gpt_arch(H, D):
    def embed(params, ids, posm):
        return params["wte"][ids] + params["wpe"][posm]

    def qkv(w, x, posm):
        B, T = x.shape[0], x.shape[1]
        h = _ln(x, w["ln1_w"], w["ln1_b"])
        qkv_ = (h @ w["qkv_w"] + w["qkv_b"]).reshape(B, T, 3, H, D)
        return qkv_[:, :, 0], qkv_[:, :, 1], qkv_[:, :, 2]

    def finish(w, x, o):
        x = x + (o @ w["proj_w"] + w["proj_b"])
        with jax.named_scope("mlp"):
            h2 = _ln(x, w["ln2_w"], w["ln2_b"])
            ff = jax.nn.gelu(h2 @ w["up_w"] + w["up_b"], approximate=True) @ w["down_w"] + w["down_b"]
            return x + ff

    def head(params, h):
        return _head_mm(params, _ln(h, params["lnf_w"], params["lnf_b"]),
                        "wte", True)  # tied head

    return _kv_arch(embed, qkv, finish, head, H, D, 1)


# ---------------------------------------------------------------------------
# Llama architecture plug
# ---------------------------------------------------------------------------

def _llama_layer_weights(layer):
    a = layer.self_attn
    m = layer.mlp
    return {
        "ln1_w": layer.input_layernorm.weight._data,
        "q_w": a.q_proj.weight._data, "k_w": a.k_proj.weight._data,
        "v_w": a.v_proj.weight._data, "o_w": a.o_proj.weight._data,
        "ln2_w": layer.post_attention_layernorm.weight._data,
        "gate_w": m.gate_proj.weight._data, "up_w": m.up_proj.weight._data,
        "down_w": m.down_proj.weight._data,
    }


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * w


def _rope_grid(x, pos, theta):
    """Rotary embedding of ``x`` (B, T, H, D) at the absolute positions
    ``pos``, int, (B, T) a (row, token) or (1, T) where the rows share them."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, :, None] * inv[None, None, :]  # (B,T,D/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., ::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape)


def _llama_arch(H, KV, D, theta, eps):
    def embed(params, ids, posm):
        return params["wte"][ids]

    def qkv(w, x, posm):
        # RoPE at each (row, token)'s own absolute position; K and V keep
        # the un-repeated KV heads
        B, T = x.shape[0], x.shape[1]
        h = _rms(x, w["ln1_w"], eps)
        q = (h @ w["q_w"]).reshape(B, T, H, D)
        k = (h @ w["k_w"]).reshape(B, T, KV, D)
        v = (h @ w["v_w"]).reshape(B, T, KV, D)
        return _rope_grid(q, posm, theta), _rope_grid(k, posm, theta), v

    def finish(w, x, o):
        x = x + o @ w["o_w"]
        with jax.named_scope("mlp"):
            h2 = _rms(x, w["ln2_w"], eps)
            ff = (jax.nn.silu(h2 @ w["gate_w"]) * (h2 @ w["up_w"])) @ w["down_w"]
            return x + ff

    def head(params, h):
        return _head_mm(params, _rms(h, params["lnf_w"], eps), "head_w", False)

    return _kv_arch(embed, qkv, finish, head, KV, D, H // KV)


# ---------------------------------------------------------------------------
# Shared decode driver
# ---------------------------------------------------------------------------

def _prompt_pass(arch, params, ids, T_max):
    """The prompt pass of a dense loop: the causal forward over ``ids``
    (B,T0), each layer's K and V rows at the head of zeroed (B,T_max,KV,D)
    caches. Returns ``(x, [(k cache, v cache) a layer])``."""
    B, T0 = ids.shape
    shape = (B, T_max, arch["kv_heads"], arch["head_dim"])
    x = arch["embed"](params, ids, jnp.arange(T0)[None])
    caches = []
    for w in params["layers"]:
        x, k, v = _causal_layer(arch, w, x)
        caches.append((jnp.zeros(shape, x.dtype).at[:, :T0].set(k),
                       jnp.zeros(shape, x.dtype).at[:, :T0].set(v)))
    return x, caches


def _token_pass(arch, params, toks, caches, pos):
    """One step of a dense loop: ``toks`` (B,) at the shared position ``pos``
    against the dense caches. Returns ``(logits, caches)``."""
    x = arch["embed"](params, toks, pos[None])[:, None]
    new_caches = []
    for w, kv in zip(params["layers"], caches):
        x, kv = _contiguous_layer(arch, w, x, kv, pos)
        new_caches.append(kv)
    return arch["head"](params, x[:, -1]), tuple(new_caches)


def _build_decode(arch, T0, T_max, max_new_tokens, temperature, top_k, top_p,
                  eos_token_id, do_sample):
    def decode(params, ids, key):
        B = ids.shape[0]

        # ---- prefill: full forward over the prompt, caches captured -------
        x, caches = _prompt_pass(arch, params, ids, T_max)
        logits0 = arch["head"](params, x[:, -1])

        # Tail pre-filled with eos: a finished row's remaining slots already
        # hold the pad value, so its writes below are no-ops (live-row
        # freeze) and the while_loop can exit as soon as EVERY row is done
        # instead of burning steps to max_new_tokens.
        fill = 0 if eos_token_id is None else int(eos_token_id)
        out = jnp.full((B, T_max), fill, jnp.int32).at[:, :T0].set(ids)
        finished = jnp.zeros((B,), bool)

        def sample_from(logits, key):
            if do_sample:
                logits = logits / max(temperature, 1e-6)
                logits = top_k_top_p_filtering(logits, top_k, top_p)
                return jax.random.categorical(key, logits, axis=-1)
            return jnp.argmax(logits, axis=-1)

        def step(carry):
            i, out, caches, finished, key, logits = carry
            key, sub = jax.random.split(key)
            nxt = sample_from(logits, sub).astype(jnp.int32)
            if eos_token_id is not None:
                # frozen rows re-write the eos their slot already holds
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            pos = T0 + i
            out = lax.dynamic_update_slice(
                out, nxt[:, None], (jnp.asarray(0, pos.dtype), pos)
            )
            logits, caches = _token_pass(arch, params, nxt, caches, pos)
            return i + 1, out, caches, finished, key, logits

        def cond(carry):
            i, _, _, finished, _, _ = carry
            live = i < max_new_tokens
            if eos_token_id is not None:
                live = live & ~jnp.all(finished)
            return live

        steps, out, _, _, _, _ = lax.while_loop(
            cond, step,
            # default int dtype (x64-dependent) so `pos = T0 + i` matches the
            # literal indices inside _contiguous_layer's dynamic_update_slice
            (jnp.asarray(0), out, tuple(caches), finished, key, logits0),
        )
        return out, steps

    return decode


def _build_beam_decode(arch, T0, T_max, max_new_tokens, num_beams, eos_token_id,
                       length_penalty):
    """Beam search inside ONE jitted program (reference
    ``operators/math/beam_search.cc`` + ``beam_search_op``/
    ``beam_search_decode_op`` roles): the KV caches are stacked per beam
    (B·K leading dim) and re-gathered along the beam axis every step inside
    the ``lax.fori_loop`` carry — no host round trips."""
    K = int(num_beams)

    def decode(params, ids, key):
        B = ids.shape[0]

        # ---- prefill on the raw batch, then tile caches across beams ------
        x, caches = _prompt_pass(arch, params, ids, T_max)
        caches = [(jnp.repeat(kc, K, axis=0), jnp.repeat(vc, K, axis=0))
                  for kc, vc in caches]
        logits0 = jnp.repeat(arch["head"](params, x[:, -1]), K, axis=0)  # (B*K, V)

        out = jnp.zeros((B * K, T_max), jnp.int32).at[:, :T0].set(
            jnp.repeat(ids, K, axis=0)
        )
        # only beam 0 is live initially so step 1 draws K distinct tokens
        scores = jnp.tile(
            jnp.asarray([0.0] + [-1e30] * (K - 1), jnp.float32), (B, 1)
        )  # (B, K)
        finished = jnp.zeros((B, K), bool)

        def gather_beams(t, beam_idx):
            # t: (B*K, ...) → reorder rows by beam_idx (B, K)
            flat = beam_idx + (jnp.arange(B) * K)[:, None]  # (B, K) global rows
            return jnp.take(t, flat.reshape(-1), axis=0)

        def step(i, carry):
            out, caches, scores, finished, logits = carry
            V = logits.shape[-1]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = logp.reshape(B, K, V)
            if eos_token_id is not None:
                # a finished beam may only extend with eos at no cost
                eos_only = jnp.full((V,), -jnp.inf).at[eos_token_id].set(0.0)
                logp = jnp.where(finished[..., None], eos_only[None, None], logp)
            total = scores[..., None] + logp  # (B, K, V)
            flat = total.reshape(B, K * V)
            new_scores, idx = lax.top_k(flat, K)  # (B, K)
            beam_idx = idx // V
            token = (idx % V).astype(jnp.int32)

            out = gather_beams(out, beam_idx)
            caches = tuple(
                (gather_beams(kc, beam_idx), gather_beams(vc, beam_idx))
                for kc, vc in caches
            )
            finished = jnp.take_along_axis(finished, beam_idx, axis=1)
            if eos_token_id is not None:
                finished = finished | (token == eos_token_id)

            pos = T0 + i
            out = lax.dynamic_update_slice(out, token.reshape(-1)[:, None], (0, pos))
            logits, caches = _token_pass(arch, params, token.reshape(-1),
                                         caches, pos)
            return out, caches, new_scores, finished, logits

        out, _, scores, _, _ = lax.fori_loop(
            0, max_new_tokens, step,
            (out, tuple(caches), scores, finished, logits0),
        )
        # GNMT-style length penalty (reference beam_search length
        # normalization); generated length is uniform here so it only
        # matters when eos ended beams early — scores already froze then
        norm = scores / (float(T0 + max_new_tokens) ** float(length_penalty))
        best = jnp.argmax(norm, axis=1)  # (B,)
        rows = best + jnp.arange(B) * K
        return jnp.take(out, rows, axis=0)

    return decode


def _run(arch_key, arch, params, ids_in, T0, max_new_tokens, temperature,
         top_k, top_p, eos_token_id, do_sample, num_beams=1, length_penalty=0.0):
    global _LAST_DECODE_STEPS
    B = ids_in.shape[0]
    T_max = T0 + int(max_new_tokens)
    key = random_state.next_key()
    if num_beams and int(num_beams) > 1:
        cache_key = arch_key + ("beam", B, T0, int(max_new_tokens),
                                int(num_beams), eos_token_id, float(length_penalty))
        fn = _DECODE_CACHE.get(cache_key)
        if fn is None:
            fn = jax.jit(_build_beam_decode(
                arch, T0, T_max, int(max_new_tokens), int(num_beams),
                eos_token_id, float(length_penalty)))
            _DECODE_CACHE[cache_key] = fn
        _LAST_DECODE_STEPS = int(max_new_tokens)  # beam loop has no early exit
        return Tensor(fn(params, ids_in, key), stop_gradient=True)
    cache_key = arch_key + (B, T0, int(max_new_tokens), float(temperature),
                            int(top_k), float(top_p), eos_token_id,
                            bool(do_sample))
    fn = _DECODE_CACHE.get(cache_key)
    if fn is None:
        fn = jax.jit(_build_decode(
            arch, T0, T_max, int(max_new_tokens), float(temperature),
            int(top_k), float(top_p), eos_token_id, bool(do_sample)))
        _DECODE_CACHE[cache_key] = fn
    out, steps = fn(params, ids_in, key)
    _LAST_DECODE_STEPS = steps  # dispatched jax scalar; coerced on read
    return Tensor(out, stop_gradient=True)


@no_grad()
def generate(
    model,
    input_ids,
    max_new_tokens: int = 32,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token_id: Optional[int] = None,
    do_sample: bool = True,
    num_beams: int = 1,
    length_penalty: float = 0.0,
):
    """Sample continuations for a GPTForPretraining-style model. Returns
    (B, T_prompt + max_new_tokens) int ids (generation stops writing after
    eos but shapes stay static — XLA-friendly)."""
    arch_key, arch, params, max_pos = gpt_decode_state(model)
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    T0 = ids.shape[1]
    if T0 + int(max_new_tokens) > max_pos:
        raise ValueError(
            f"generate: {T0 + int(max_new_tokens)} exceeds "
            f"max_position_embeddings {max_pos}"
        )
    return _run(arch_key, arch, params, ids, T0, max_new_tokens,
                temperature, top_k, top_p, eos_token_id, do_sample,
                num_beams=num_beams, length_penalty=length_penalty)


@no_grad()
def generate_llama(
    model, input_ids, max_new_tokens=32, temperature=1.0, top_k=0, top_p=1.0,
    eos_token_id=None, do_sample=True,
):
    """KV-cached compiled decode for LlamaForCausalLM: RoPE applied at
    absolute cache positions; GQA attends against the un-repeated KV cache."""
    arch_key, arch, params, max_pos = llama_decode_state(model)
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    T0 = ids.shape[1]
    if T0 + int(max_new_tokens) > max_pos:
        raise ValueError("generate: length exceeds max_position_embeddings")
    return _run(arch_key, arch, params, ids, T0, max_new_tokens,
                temperature, top_k, top_p, eos_token_id, do_sample)


# ---------------------------------------------------------------------------
# Scheduler-drivable decode state + paged prefill/step programs
# ---------------------------------------------------------------------------
# The serving engine (paddle_tpu/serving/) drives these directly: the state
# extractors are the single weight-tree + arch-plug extraction point shared
# with generate(), and the builders return batch-packed, cache-position-
# explicit pure functions the engine jits per bucket shape.

def gpt_decode_state(model):
    """(arch_key, arch, params, max_positions) for a GPTForPretraining-style
    model — the extraction point shared by ``generate()`` and the serving
    engine's paged prefill/decode programs."""
    gpt = model.gpt
    cfg = model.config
    H = cfg.num_heads
    D = cfg.hidden_size // H
    qkv_w = gpt.layers[0].attn.qkv.weight._data
    if qkv_w.shape[-1] != 3 * cfg.hidden_size:
        raise NotImplementedError(
            "generate(): weights are physically mp-sharded "
            f"(qkv local shape {qkv_w.shape}); decode assumes full logical "
            "weights — gather them (state_dict round-trip) or generate before "
            "engine.place()"
        )
    params = {
        "wte": gpt.embeddings.word_embeddings.weight._data,
        "wpe": gpt.embeddings.position_embeddings.weight._data,
        "lnf_w": gpt.final_ln.weight._data,
        "lnf_b": gpt.final_ln.bias._data,
        "layers": [_gpt_layer_weights(l) for l in gpt.layers],
    }
    arch_key = ("gpt", H, D, len(params["layers"]))
    return arch_key, _gpt_arch(H, D), params, cfg.max_position_embeddings


def llama_decode_state(model):
    """(arch_key, arch, params, max_positions) for LlamaForCausalLM."""
    cfg = model.model.config
    H = cfg.num_heads
    KV = cfg.kv_heads
    D = cfg.hidden_size // H
    q_w = model.model.layers[0].self_attn.q_proj.weight._data
    if q_w.shape[-1] != cfg.hidden_size:
        raise NotImplementedError("generate: physically mp-sharded weights")
    params = {
        "wte": model.model.embed_tokens.weight._data,
        "lnf_w": model.model.norm.weight._data,
        "head_w": model.lm_head.weight._data,
        "layers": [_llama_layer_weights(l) for l in model.model.layers],
    }
    # theta/eps are baked into the compiled fn: they MUST key the cache
    arch_key = ("llama", H, KV, D, len(params["layers"]),
                float(cfg.rope_theta), float(cfg.rms_norm_eps))
    arch = _llama_arch(H, KV, D, float(cfg.rope_theta), float(cfg.rms_norm_eps))
    return arch_key, arch, params, cfg.max_position_embeddings


def mla_moe_params(cfg, sd):
    """The weight tree the serving programs take, from ``{state_dict key:
    array}`` (arrays or their shapes: ``jax.eval_shape`` goes through)."""
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        w = {"attn_norm": sd[p + "attn_norm.weight"],
             "ffn_norm": sd[p + "ffn_norm.weight"]}
        for sub in ("attn", "ffn"):
            if cfg.hc_mult > 1:
                w[f"hc_{sub}"] = {k: sd[p + f"{sub}_hc.{k}"]
                                  for k in ("phi", "alpha", "bias")}
        for k in ("q_a", "q_b", "q", "kv_a", "kv_b", "o"):
            if p + f"attn.{k}.weight" in sd:
                w[k] = sd[p + f"attn.{k}.weight"]
        for k in ("q_a_norm", "kv_a_norm"):
            if p + f"attn.{k}.weight" in sd:
                w[k] = sd[p + f"attn.{k}.weight"]
        # the absorbed form's per-head views of the up-projection, cut once
        kv_b = w["kv_b"].reshape(cfg.kv_lora_rank, H, -1)
        w["uk"] = jnp.transpose(kv_b[..., :nope], (1, 2, 0))
        w["uv"] = jnp.transpose(kv_b[..., nope:], (1, 0, 2))
        if cfg.is_expert_layer(i):
            w.update(router=sd[p + "mlp.router.weight"],
                     e_bias=sd[p + "mlp.router.e_bias"],
                     experts_gate=sd[p + "mlp.experts.gate"],
                     experts_up=sd[p + "mlp.experts.up"],
                     experts_down=sd[p + "mlp.experts.down"])
            if cfg.n_shared_experts:
                w.update({f"shared_{k}": sd[p + f"mlp.shared.{k}.weight"]
                          for k in ("gate", "up", "down")})
        else:
            w.update({k: sd[p + f"mlp.{k}.weight"] for k in ("gate", "up", "down")})
        layers.append(w)
    params = {"wte": sd["model.embed_tokens.weight"],
              "norm": sd["model.norm.weight"], "layers": layers}
    if not cfg.tie_word_embeddings:
        params["head_w"] = sd["lm_head.weight"]
    return params


def _keyed_by_config(name, cfg, kernels):
    """``(kernels, arch_key)`` of an arch built from a config dataclass:
    whether its layers take their Pallas kernels (None: wherever Mosaic
    compiles) and the key of its compiled programs, which every number they
    bake in is part of."""
    import dataclasses

    from ..ops.pallas import interpret_default

    kernels = (not interpret_default()) if kernels is None else bool(kernels)
    return kernels, (name, kernels) + tuple(
        (f.name, repr(getattr(cfg, f.name))) for f in dataclasses.fields(cfg))


def mla_moe_decode_state(model, kernels=None):
    """(arch_key, arch, params, max_positions) for ``MLAMoEForCausalLM``: the
    weight tree the serving programs take and the arch plug around
    ``models/mla_moe.py``'s layer functions. ``kernels``: whether the layer
    takes its Pallas kernels (default: wherever Mosaic compiles, as the flash
    kernel is chosen) or their plain forms."""
    cfg = model.config
    kernels, arch_key = _keyed_by_config("mla_moe", cfg, kernels)
    params = mla_moe_params(
        cfg, {k: v._data for k, v in model.state_dict().items()})
    return arch_key, _mla_moe_arch(cfg, kernels), params, \
        cfg.max_position_embeddings


def _mla_moe_arch(cfg, kernels):
    """The arch plug of the latent-attention / routed-expert lineage. Its
    cache is ONE pool a layer, a padded latent row a token; its layer is
    ``mla_moe.decoder_layer`` for prompts (``prompt_layer``), for a call of
    positions against what the pool already holds of a row (``tail_layer``:
    chunked prefill) and for decode rows (``decode_layer``) alike, so the
    plug has no per-builder copy of it. It has the plain prefill and decode
    programs and the tail program, and no other yet (``plain_paths_only``:
    the engine refuses tp, int8, speculative verify, the prefix index and
    snapshots by name; an arch that brings ``tail_layer`` is served
    ``prefill_chunk``; ``prompt_max`` is the longest prompt the whole-prompt
    program takes, longer ones go through the tail program in calls). Where its decode reads the pool through the kernel,
    ``step_attrs`` says what the kernel's copy schedule does with a step's
    positions (blocks copied a layer, chunks, chunks copied whole), for the
    ``decode_step`` span; ``call_attrs`` says what a tail call reads of the
    pool, for the ``prefill`` span."""
    from . import mla_moe as M

    tabs = M.rope_tables(cfg)
    H = cfg.num_attention_heads

    def embed(params, ids, posm):
        return M.embed_streams(cfg, params, ids)

    def head(params, X):
        h = M.final_hidden(cfg, params, X)
        if "head_w" in params:
            return h @ params["head_w"]
        return h @ params["wte"].T

    def prompt_layer(w, X, live):
        X, latent, counts = M.prompt_layer(cfg, tabs, w, X, live, kernels)
        return X, (latent,), counts

    def decode_layer(w, X, pools, li, tables, pos, bids, offs, live):
        # one fresh token a row, (B,1,n,d) like every arch's; the layer's
        # own shapes have no token axis
        X, pool = X[:, 0], pools[0]

        def attend(u):
            nonlocal pool
            q_nope, q_rope, latent = M.latent_project(cfg, w, u, pos, tabs)
            # the fresh row goes into the pool BEFORE the read, as in the
            # K/V kernel step
            pool = pool.at[li, bids, offs].set(latent)
            q = M.absorb_queries(cfg, w, q_nope, q_rope)
            if kernels:
                from ..ops.kernels.mla_paged_attention import mla_paged_attention

                with jax.named_scope("attention"):
                    ol = mla_paged_attention(q, pool, li, tables, pos,
                                             cfg.kv_lora_rank, tabs[2])
            else:
                ol = M.attend_absorbed_plain(cfg, q, pool, li, tables, pos,
                                             tabs[2])
            o = jnp.einsum("bhc,hcv->bhv", ol, w["uv"])
            return o.reshape(o.shape[0], H * cfg.v_head_dim) @ w["o"]

        X, counts = M.decoder_layer(cfg, w, X, attend, live, kernels)
        return X[:, None], (pool,), counts

    def tail_layer(w, X, pools, li, tables, starts, lens, bids, live, scratch):
        # T fresh positions a row at ``starts + 0 .. T - 1``, (B,T,n,d); the
        # context is expanded from the pool, the call's own rows included
        pool = pools[0]
        B, T = X.shape[:2]
        posm = starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None]

        def attend(u):
            nonlocal pool, scratch
            q_nope, q_rope, latent = M.latent_project(cfg, w, u, posm, tabs)
            # the fresh rows go into the pool BEFORE the read, as in decode
            pool = pool.at[li, bids].set(
                latent.reshape((B, bids.shape[1], -1) + latent.shape[2:]))
            scratch = M.expand_context(cfg, w, pool, li, tables, starts + lens,
                                       scratch)
            o = M.attend_call(cfg, q_nope, q_rope, scratch, starts, lens,
                              tabs[2], kernels)
            return o @ w["o"]

        X, counts = M.decoder_layer(cfg, w, X, attend, live, kernels)
        return X, (pool,), counts, scratch

    def call_attrs(starts, feeds, block_size, max_blocks):
        # blocks of the pool a call's expansion gathers a layer: whole turns
        # up to the longest row's end, of every row of the call
        turn = M.expand_turn(block_size, max_blocks)
        ends = np.asarray(starts, np.int64) + np.asarray(feeds, np.int64)
        return {"latent_blocks_read": int(
            -(-int(ends.max()) // (turn * block_size)) * turn * len(ends))}

    arch = {"name": "mla_moe", "embed": embed, "head": head,
            "prompt_layer": prompt_layer, "decode_layer": decode_layer,
            "tail_layer": tail_layer,
            "tail_scratch": functools.partial(M.context_scratch, cfg),
            "call_attrs": call_attrs, "prompt_max": M.whole_prompt_max(cfg),
            "cache": ((cfg.cache_row,),), "plain_paths_only": True,
            "expert_layers": sum(cfg.is_expert_layer(i)
                                 for i in range(cfg.num_hidden_layers)),
            "experts": cfg.n_routed_experts,
            **({} if len(cfg.experts_held) == cfg.n_routed_experts
               else {"experts_held": cfg.experts_held})}
    if kernels:
        from ..ops.kernels import mla_paged_attention as K

        def step_attrs(pos, bucket, block_size, max_blocks, dtype):
            # the chunk the bucket's program resolved when it was traced
            C = K.blocks_per_chunk(K.mla_paged_attention_key(
                bucket, max_blocks, block_size, H, cfg.cache_row,
                cfg.kv_lora_rank, dtype))
            return K.chunk_counts(pos, block_size, C)

        arch["step_attrs"] = step_attrs
    return arch


def phi4flash_decode_state(model, kernels=None):
    """(arch_key, arch, params, max_positions) for ``PhiFlashForCausalLM``:
    the weight tree (the layers of a kind stacked, as the model holds them)
    and the arch plug around ``models/phi4flash.py``'s layer functions.
    ``kernels``: whether the scans and the cache reads take their Pallas
    kernels (default: wherever Mosaic compiles) or their plain forms."""
    from . import phi4flash as P

    cfg = model.config
    kernels, arch_key = _keyed_by_config("phi4flash", cfg, kernels)
    params = P.params_tree(
        cfg, {k: v._data for k, v in model.state_dict().items()})
    return arch_key, _phi4flash_arch(cfg, kernels), params, \
        cfg.max_position_embeddings


def window_block(tokens: int, block_size: int) -> int:
    """The block a window cache is cut into, so that the block-table read
    serves it too: the largest size that divides both the window and the
    engine's block."""
    return math.gcd(int(tokens), int(block_size))


def window_table(W: int, slots, block_size: int):
    """``(window_block, tables (B, W / window_block))``: the blocks of each
    row's ring, as a block table the block-table read takes: slot ``s`` holds
    blocks ``s W / wb .. (s + 1) W / wb - 1`` of the window pools."""
    wb = window_block(W, block_size)
    return wb, slots[:, None] * (W // wb) + jnp.arange(W // wb)[None]


def paged_step_attrs(reads, pos, bucket, block_size, max_blocks, dtype):
    """What the block-table kernel's copy schedule does in one decode step
    with live rows that write positions ``pos``, summed over the step's
    calls of ``paged_attention_rows``: ``paged_blocks`` copied from each
    pool, the ``paged_chunks`` they come in and ``paged_full_chunks``, those
    started as straight-line code and waited for once (``ops/kernels/
    paged_attention.chunk_counts``), for the ``decode_step`` span. ``reads``:
    ``(calls a step, KV heads, queries a KV head, head width, window)`` for
    each kind of read, as the kernel's key has them; ``window`` 0: the rows'
    block tables by ``pos``; else a ring of that many tokens, read through a
    table of its own with ``pos`` held at ``window - 1``. The chunk is the
    one the ``bucket``'s program resolved when it was traced."""
    from ..ops.kernels import paged_attention as K

    pos = np.asarray(pos, np.int64)
    total = dict.fromkeys(("paged_blocks", "paged_chunks", "paged_full_chunks"), 0)
    for calls, KV, rep, D, window in reads:
        BS, MB, at = block_size, max_blocks, pos
        if window:
            BS = window_block(window, block_size)
            MB, at = window // BS, np.minimum(pos, window - 1)
        C = K.blocks_per_chunk(
            K.paged_attention_key(bucket, MB, BS, KV, rep, D, dtype))
        for name, n in K.chunk_counts(at, BS, C).items():
            total[name] += calls * n
    return total


def _kernel_step_attrs(kernels, reads) -> dict:
    """The ``step_attrs`` entry of a plug whose decode reads are ``reads``
    (:func:`paged_step_attrs`) where they go through the kernel; the plain
    gather has no chunks and says nothing."""
    if not kernels:
        return {}
    return {"step_attrs": lambda *step: paged_step_attrs(reads, *step)}


def _phi4flash_arch(cfg, kernels):
    """The arch plug of the Mamba / differential-attention hybrid. A layer
    caches one of three things or nothing (``cache["layers"]``: the kind, and
    the layer whose cache a layer READS):

    - ``paged``: K and V rows a token by block table, as every arch's, for
      the ONE full-attention layer; the cross layers behind it read the same
      pool through the same table;
    - ``window``: the last ``sliding_window`` K and V rows of a row, a RING a
      slot (position ``p`` at entry ``p % W``; with no positional encoding
      the order of the entries says nothing). The ring is stored as
      ``W / window_block`` blocks of the slot, so the block-table read serves
      it with a table made from the slot and a position held at ``W - 1``;
    - ``state``: fixed shapes a slot, the scan's state (float32) and the
      convolution's last ``K - 1`` inputs.

    Its layers are ``models/phi4flash.py``'s, for prompts and decode rows
    alike; here are only the reads and writes of the pools. It has the plain
    prefill and decode programs and no other yet (``plain_paths_only``)."""
    from . import phi4flash as P

    W, L = cfg.sliding_window, cfg.num_hidden_layers
    H, h, pairs = cfg.num_attention_heads, cfg.head_dim, cfg.kv_pairs
    caches = {"mamba": "state", "window": "window", "full": "paged"}
    kinds = [cfg.layer_kind(i) for i in range(L)]
    layers = tuple((caches.get(k), L // 2 + 1 if k == "cross" else None)
                   for k in kinds)

    def embed(params, ids, posm):
        return params["wte"][ids]

    def head(params, x):
        return _head_mm(params, P.layer_norm(x, params["lnf_g"], params["lnf_b"],
                                             cfg.layer_norm_eps), "wte", True)

    def prompt_stack(params, x, pools, lens, live, tb, slots, block_size):
        B = x.shape[0]
        wb, wrows = window_table(W, slots, block_size)

        def keep(pools, kind, i, a, b):
            kp, vp, wk, wv, S, tails = pools
            if kind == "state":
                return (kp, vp, wk, wv, S.at[i, slots].set(a),
                        tails.at[i, slots].set(b.astype(tails.dtype)))
            # (B, T, pairs, 2 h) rows as whole blocks of (token, pair) lines
            cut = lambda r, size: r.reshape(B, -1, size * pairs, 2 * h)
            if kind == "window":
                return (kp, vp, wk.at[i, wrows].set(cut(a, wb)),
                        wv.at[i, wrows].set(cut(b, wb)), S, tails)
            return (kp.at[i, tb].set(cut(a, block_size)),
                    vp.at[i, tb].set(cut(b, block_size)), wk, wv, S, tails)

        return P.prompt_stack(cfg, params, x, pools, lens, live, kernels, keep)

    def decode_stack(params, x, pools, tables, pos, bids, offs, slots,
                     block_size):
        from ..ops.kernels import paged_attention_rows
        from ..ops.kernels.selective_scan import state_update, state_update_plain

        B = x.shape[0]
        wb, wtables = window_table(W, slots, block_size)
        ring = pos % W
        wbids, woffs = wtables[:, 0] + ring // wb, ring % wb
        wpos = jnp.minimum(pos, W - 1)  # a full ring: every entry is live
        # a token's lines inside its block
        lines = lambda offs: offs[:, None] * pairs + jnp.arange(pairs)[None]

        def read(kpool, vpool, li, q, tables, pos):
            """The fresh rows are in the pool: every live position by table."""
            if kernels:
                with jax.named_scope("attention"):
                    o = paged_attention_rows(q[:, 0], kpool, vpool, li, tables,
                                             pos, scale=h ** -0.5, kv_heads=pairs)
                return o.reshape(B, 1, H, 2 * h)
            T_pad = tables.shape[1] * kpool.shape[2] // pairs
            kc = kpool[li, tables].reshape((B, T_pad) + cfg.kv_row)
            vc = vpool[li, tables].reshape((B, T_pad) + cfg.kv_row)
            live = jnp.arange(T_pad)[None, None, :] <= pos[:, None, None]
            return P.attend_dense(cfg, q, kc, vc, live)

        def paged_read(pools, q, k, v):
            kp, vp, *rest = pools
            if k is not None:
                kp = kp.at[0, bids[:, None], lines(offs)].set(k[:, 0])
                vp = vp.at[0, bids[:, None], lines(offs)].set(v[:, 0])
            return (kp, vp, *rest), read(kp, vp, 0, q, tables, pos)

        def window_read(pools, i, q, k, v):
            kp, vp, wk, wv, S, tails = pools
            wk = wk.at[i, wbids[:, None], lines(woffs)].set(k[:, 0])
            wv = wv.at[i, wbids[:, None], lines(woffs)].set(v[:, 0])
            return (kp, vp, wk, wv, S, tails), read(wk, wv, i, q, wtables, wpos)

        def state_step(pools, i, a):
            kp, vp, wk, wv, S, tails = pools
            taps = jnp.concatenate([tails[i, slots], a.astype(tails.dtype)], 1)
            tails = tails.at[i, slots].set(taps[:, 1:])

            def recur(pools, dt, c, Bm, Cm, A, D):
                kp, vp, wk, wv, S, tails = pools
                with jax.named_scope("state_update"):
                    S, y = (state_update if kernels else state_update_plain)(
                        S, i, slots, dt[:, 0], c[:, 0], Bm[:, 0], Cm[:, 0], A, D)
                return y[:, None], (kp, vp, wk, wv, S, tails)

            return (kp, vp, wk, wv, S, tails), taps[:, None], recur

        return P.decode_stack(cfg, params, x, pools, state_step, window_read,
                              paged_read)

    reads = ((sum(k in ("full", "cross") for k in kinds), pairs, H // pairs,
              2 * h, 0),
             (kinds.count("window"), pairs, H // pairs, 2 * h, W))
    return {"name": "phi4flash", "embed": embed, "head": head,
            "prompt_stack": prompt_stack, "decode_stack": decode_stack,
            "plain_paths_only": True, **_kernel_step_attrs(kernels, reads),
            "cache": {"layers": layers, "window_tokens": W,
                      "span_attrs": {"shared_kv_tokens": "paged",
                                     "window_tokens": "window",
                                     "state_rows": "state"},
                      "paged": (cfg.kv_row,) * 2, "window": (cfg.kv_row,) * 2,
                      "state": (((cfg.mamba_d_state, cfg.d_inner), "float32"),
                                ((cfg.mamba_d_conv - 1, cfg.d_inner), None))}}


def lfm2_moe_decode_state(model, kernels=None):
    """(arch_key, arch, params, max_positions) for ``Lfm2MoeForCausalLM``:
    the weight tree (the repetitions of the layer pattern stacked, as the
    model holds them) and the arch plug around ``models/lfm2_moe.py``'s layer
    functions. ``kernels``: whether the experts and the cache read take their
    Pallas kernels (default: wherever Mosaic compiles) or their plain forms."""
    from . import lfm2_moe as L

    cfg = model.config
    kernels, arch_key = _keyed_by_config("lfm2_moe", cfg, kernels)
    params = L.params_tree(
        cfg, {k: v._data for k, v in model.state_dict().items()})
    return arch_key, _lfm2_moe_arch(cfg, kernels), params, \
        cfg.max_position_embeddings


def _lfm2_moe_arch(cfg, kernels):
    """The arch plug of the gated-convolution / grouped-query / routed-expert
    hybrid: a whole-stack arch (row slots, a cache of kinds) that ALSO routes
    experts, so its stacks hand the programs their counts beside the pools.

    - ``paged``: K and V rows a token by block table for the attention
      layers, NEIGHBOURING key/value heads two a 128-lane line (``kv_row``):
      heads of 64 fill half a line, and the block-table kernel takes whole
      ones. The queries are laid to match (``lfm2_moe.pair_queries``);
    - ``state``: the convolution's last ``conv_L_cache - 1`` inputs a slot, in
      the served dtype.

    A prompt's rows are written by ONE scatter a pool after the stack (the
    layers inside the scan stage them, ``lfm2_moe.prompt_reads``); a decode
    step writes its fresh row a layer, before the read. It has the plain prefill and decode
    programs and no other yet (``plain_paths_only``)."""
    from . import lfm2_moe as L
    from .phi4flash import attend_dense

    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    pairs = G // 2
    kinds = {"conv": "state", "full_attention": "paged"}
    if set(cfg.layer_types) != set(kinds):
        raise NotImplementedError(
            f"lfm2_moe: layer_types {cfg.layer_types!r}; the arch holds a pool "
            "a kind and expects layers of both")

    def embed(params, ids, posm):
        return params["wte"][ids]

    def head(params, x):
        return _head_mm(params, L.M.rms(x, params["norm"], cfg.norm_eps),
                        "wte", True)

    def prompt_stack(params, x, pools, lens, live, tb, slots, block_size):
        B, T = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        conv, attend = L.prompt_reads(cfg, lens, T)
        x, (k, v, tails), counts = L.stack(
            cfg, params, x, pos, live, L.prompt_staging(cfg, B, T, x.dtype),
            conv, attend, kernels)
        kp, vp, S = pools
        # (layers, B, T, pairs, 2 D) rows as whole blocks of (token, pair) lines
        cut = lambda r: r.reshape(r.shape[0], B, -1, block_size * pairs, 2 * D)
        return x, (kp.at[:, tb].set(cut(k)), vp.at[:, tb].set(cut(v)),
                   S.at[:, slots].set(tails.astype(S.dtype))), counts

    def decode_stack(params, x, pools, tables, pos, bids, offs, slots,
                     block_size):
        from ..ops.kernels import paged_attention_rows

        B = x.shape[0]
        # a token's lines inside its block
        lines = offs[:, None] * pairs + jnp.arange(pairs)[None]

        def conv(pools, i, z):
            kp, vp, S = pools
            taps = jnp.concatenate([S[i, slots], z.astype(S.dtype)], 1)
            return (kp, vp, S.at[i, slots].set(taps[:, 1:])), taps[:, None]

        def attend(pools, i, q, k, v):
            kp, vp, S = pools
            # the fresh row goes into the pool BEFORE the read
            kp = kp.at[i, bids[:, None], lines].set(L.pair_keys(cfg, k[:, 0]))
            vp = vp.at[i, bids[:, None], lines].set(L.pair_keys(cfg, v[:, 0]))
            if kernels:
                o = paged_attention_rows(
                    L.pair_queries(cfg, q[:, 0]), kp, vp, i, tables, pos,
                    scale=D ** -0.5, kv_heads=pairs)
                o = L.own_half(cfg, o.reshape(B, H, 2 * D))[:, None]
            else:
                T_pad = tables.shape[1] * block_size
                kc = kp[i, tables].reshape(B, T_pad, G, D)
                vc = vp[i, tables].reshape(B, T_pad, G, D)
                seen = jnp.arange(T_pad)[None, None, :] <= pos[:, None, None]
                o = attend_dense(cfg, q, kc, vc, seen)
            return (kp, vp, S), o

        live = tables[:, :1] != 0  # a row whose table is unmapped pads the bucket
        return L.stack(cfg, params, x, pos[:, None], live, pools, conv, attend,
                       kernels)

    reads = ((cfg.layer_types.count("full_attention"), pairs, H // pairs,
              2 * D, 0),)
    return {"name": "lfm2_moe", "embed": embed, "head": head,
            "prompt_stack": prompt_stack, "decode_stack": decode_stack,
            "plain_paths_only": True, **_kernel_step_attrs(kernels, reads),
            "expert_layers": cfg.num_hidden_layers - cfg.num_dense_layers,
            "experts": cfg.num_experts,
            "cache": {"layers": tuple((kinds[k], None) for k in cfg.layer_types),
                      "span_attrs": {"paged_kv_tokens": "paged",
                                     "state_rows": "state"},
                      "paged": (cfg.kv_row,) * 2,
                      "state": (((cfg.conv_L_cache - 1, cfg.hidden_size), None),)}}


def afmoe_decode_state(model, kernels=None):
    """(arch_key, arch, params, max_positions) for ``AfmoeForCausalLM``: the
    weight tree (the repetitions of the layer pattern stacked, as the model
    holds them) and the arch plug around ``models/afmoe.py``'s layer
    functions. ``kernels``: whether the prompt attention, the experts and the
    cache reads take their Pallas kernels (default: wherever Mosaic compiles)
    or their plain forms."""
    from . import afmoe as A

    cfg = model.config
    kernels, arch_key = _keyed_by_config("afmoe", cfg, kernels)
    params = A.params_tree(
        cfg, {k: v._data for k, v in model.state_dict().items()})
    return arch_key, _afmoe_arch(cfg, kernels), params, \
        cfg.max_position_embeddings


def _afmoe_arch(cfg, kernels):
    """The arch plug of the window-and-full / gated-attention / routed-expert
    lineage: a whole-stack arch (row slots, a cache of two kinds) that routes
    experts, so its stacks hand the programs their counts beside the pools.
    Both kinds hold K (after its norm and, in a window layer, its rotation)
    and V as they lie, ``(G, D)`` a token:

    - ``paged``: a row a token by block table, for the full layers;
    - ``window``: the last ``sliding_window`` rows of a row slot, a RING
      (position ``p`` at entry ``p % W``: a key is rotated before it is
      cached, so its place in the ring says nothing), stored as blocks so
      that the block-table read serves it (``window_table``).

    A prompt's rows and rings are written by ONE scatter a pool after the
    stack (the layers stage them, ``afmoe.prompt_reads``); a decode step
    writes its fresh row a layer, before the read. ``prefill_attrs`` says
    what a prefill's attention must score (``band_tokens_*``, one layer of the
    kind). It has the plain prefill and decode programs and no other yet
    (``plain_paths_only``)."""
    from . import afmoe as A
    from .phi4flash import attend_dense

    W = cfg.sliding_window
    H, (G, D) = cfg.num_attention_heads, cfg.kv_row
    kinds = {"sliding_attention": "window", "full_attention": "paged"}
    if set(cfg.layer_types) != set(kinds):
        raise NotImplementedError(
            f"afmoe: layer_types {cfg.layer_types!r}; the arch holds a pool a "
            "kind and expects layers of both")

    def embed(params, ids, posm):
        return A.embed(cfg, params, ids)

    def head(params, x):
        return _head_mm(params, A.M.rms(x, params["norm"], cfg.rms_norm_eps),
                        "head_w", False)

    def prompt_stack(params, x, pools, lens, live, tb, slots, block_size):
        B, T = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x, (k, v, rk, rv), counts = A.stack(
            cfg, params, x, pos, live, A.prompt_staging(cfg, B, T, x.dtype),
            A.prompt_reads(cfg, lens, kernels), kernels)
        kp, vp, wk, wv = pools
        wb, wrows = window_table(W, slots, block_size)
        # (layers, B, tokens, G, D) rows as whole blocks of (token, head) lines
        cut = lambda r, size: r.reshape(r.shape[0], B, -1, size * G, D)
        return x, (kp.at[:, tb].set(cut(k, block_size)),
                   vp.at[:, tb].set(cut(v, block_size)),
                   wk.at[:, wrows].set(cut(rk, wb)),
                   wv.at[:, wrows].set(cut(rv, wb))), counts

    def decode_stack(params, x, pools, tables, pos, bids, offs, slots,
                     block_size):
        from ..ops.kernels import paged_attention_rows

        B = x.shape[0]
        wb, wtables = window_table(W, slots, block_size)
        ring = pos % W
        wbids, woffs = wtables[:, 0] + ring // wb, ring % wb
        wpos = jnp.minimum(pos, W - 1)  # a full ring: every entry is live
        # a token's lines inside its block
        lines = lambda offs: offs[:, None] * G + jnp.arange(G)[None]

        def read(kpool, vpool, i, q, k, v, bids, offs, tables, pos):
            """The fresh row goes into the pool BEFORE the read: every live
            position by table."""
            kpool = kpool.at[i, bids[:, None], lines(offs)].set(k[:, 0])
            vpool = vpool.at[i, bids[:, None], lines(offs)].set(v[:, 0])
            if kernels:
                o = paged_attention_rows(q[:, 0], kpool, vpool, i, tables, pos,
                                         kv_heads=G).reshape(B, 1, H, D)
            else:
                T_pad = tables.shape[1] * kpool.shape[2] // G
                kc = kpool[i, tables].reshape(B, T_pad, G, D)
                vc = vpool[i, tables].reshape(B, T_pad, G, D)
                seen = jnp.arange(T_pad)[None, None, :] <= pos[:, None, None]
                o = attend_dense(cfg, q, kc, vc, seen)
            return kpool, vpool, o

        def full(pools, i, q, k, v):
            kp, vp, wk, wv = pools
            kp, vp, o = read(kp, vp, i, q, k, v, bids, offs, tables, pos)
            return (kp, vp, wk, wv), o

        def sliding(pools, i, q, k, v):
            kp, vp, wk, wv = pools
            wk, wv, o = read(wk, wv, i, q, k, v, wbids, woffs, wtables, wpos)
            return (kp, vp, wk, wv), o

        live = tables[:, :1] != 0  # a row whose table is unmapped pads the bucket
        return A.stack(cfg, params, x, pos[:, None], live, pools,
                       {"full_attention": full, "sliding_attention": sliding},
                       kernels)

    def prefill_attrs(lens):
        return {"band_tokens_window": A.band_tokens(lens, W),
                "band_tokens_full": A.band_tokens(lens)}

    reads = ((cfg.layer_types.count("full_attention"), G, H // G, D, 0),
             (cfg.layer_types.count("sliding_attention"), G, H // G, D, W))
    return {"name": "afmoe", "embed": embed, "head": head,
            "prompt_stack": prompt_stack, "decode_stack": decode_stack,
            "plain_paths_only": True, "prefill_attrs": prefill_attrs,
            **_kernel_step_attrs(kernels, reads),
            "expert_layers": cfg.num_hidden_layers - cfg.num_dense_layers,
            "experts": len(cfg.experts_held),
            "experts_per_token": cfg.num_experts_per_tok,
            "cache": {"layers": tuple((kinds[k], None) for k in cfg.layer_types),
                      "window_tokens": W,
                      "span_attrs": {"paged_kv_tokens": "paged",
                                     "window_tokens": "window"},
                      "paged": (cfg.kv_row,) * 2, "window": (cfg.kv_row,) * 2}}


def cache_row_shapes(arch):
    """What an arch whose layers all cache the same thing caches of one token
    in one layer, a trailing shape for each pool: the engine's pools are
    ``(layers, blocks, block_size) + shape``. An arch that declares none
    caches K and V per KV head."""
    if "cache" in arch:
        return tuple(tuple(s) for s in arch["cache"])
    return ((arch["kv_heads"], arch["head_dim"]),) * 2


def cache_slots(arch) -> bool:
    """Whether a request of this arch holds a ROW SLOT for its life beside
    its blocks: it has layers whose cache is a fixed size a row (``window``,
    ``state``)."""
    cache = arch.get("cache")
    return isinstance(cache, dict) and any(
        kind in ("window", "state") for kind, _ in cache["layers"])


def cache_pools(arch, n_layers, num_blocks, block_size, max_batch):
    """Every pool the engine holds for ``arch``: ``[(kind, shape, dtype)]``
    (``dtype`` None: the model's). An arch may say, a layer, what the layer
    caches (``arch["cache"]["layers"]``: ``(kind, layer it reads)`` a layer,
    kind ``paged``, ``window``, ``state`` or None); each kind then gets its
    pools over the layers of THAT kind alone:

    - ``paged``: ``(layers, blocks, block_size x row[0]) + row[1:]``, by
      block table. A block is ONE slab of ``(token, head)`` lines, as the
      block-table kernel copies it (ten heads of bfloat16 as an axis of
      their own are padded to a sublane tile of sixteen, and the kernel's
      view of the pool is then a copy of the pool);
    - ``window``: ``(layers, (max_batch + 1) x blocks a window, window_block
      x row[0]) + row[1:]``: a ring of ``window_tokens`` rows a slot, cut
      into blocks so that the block-table read serves it;
    - ``state``: ``(layers, max_batch + 1) + row``, a fixed shape a slot.

    Slot 0 is the trash slot, as block 0 is the trash block: the rows that
    pad a bucket write there. ``arch["cache"]["span_attrs"]`` (attribute ->
    kind) names what the engine's ``decode_step`` spans report of each kind
    a step: the live context summed over the rows (``paged``), its part
    inside the windows (``window``), the live rows (``state``). An arch that
    says nothing a layer (GPT, Llama,
    the MLA arch) caches the same rows in every layer: one paged kind over
    all ``n_layers`` (:func:`cache_row_shapes`)."""
    cache = arch.get("cache")
    if not isinstance(cache, dict):
        return [("paged", (n_layers, num_blocks, block_size) + row, None)
                for row in cache_row_shapes(arch)]
    count = lambda kind: sum(k == kind for k, _ in cache["layers"])
    slots = max_batch + 1
    pools = [("paged", (count("paged"), num_blocks, block_size * row[0])
              + tuple(row[1:]), None) for row in cache.get("paged", ())]
    if cache.get("window"):
        wb = window_block(cache["window_tokens"], block_size)
        pools += [("window", (count("window"), slots * (cache["window_tokens"] // wb),
                              wb * row[0]) + tuple(row[1:]), None)
                  for row in cache["window"]]
    return pools + [("state", (count("state"), slots) + tuple(row), dtype)
                    for row, dtype in cache.get("state", ())]


def build_paged_prefill(arch, B, T_bucket, block_size, max_blocks):
    """Compiled prompt prefill over a length-bucketed batch, writing the
    cache rows into the paged pools.

    The returned pure fn ``prefill(params, ids, lens, tables, *pools)``
    (``pools``: what the arch declares, :func:`cache_row_shapes`; K and V for
    GPT and Llama, one latent pool for the MLA arch) runs the dense causal
    forward over ``ids`` (B, T_bucket) — what is cached of a REAL position
    is exact regardless of the padding behind it: a cached row a token by
    causality, a recurrent state because it is taken at ``lens`` and not at
    the bucket's end (a padded position leaves it as it was), a window
    because its ring holds the last positions under ``lens`` — reshapes
    each layer's (B, T_bucket, ...) rows into ``T_bucket // block_size``
    blocks and scatters them at ``tables[:, :nb]`` (rows shorter than the
    bucket point their tail entries at the reserved trash block 0), and
    returns ``(*pools, logits)`` with logits taken at each row's true last
    prompt token (``lens - 1``). The layer is the arch's ``prompt_layer``,
    told which positions are real (``live``: inside ``lens``, of a row whose
    table is mapped); where it routes experts the program returns after the
    logits the tokens each expert took, ``(expert layers, experts)``.

    An arch whose layers are of several kinds, with values that cross them,
    brings its whole stack (``prompt_stack(params, x, pools, lens, live,
    tables[:, :nb], slots, block_size) -> (x, pools)``, or ``(x, pools,
    counts)`` where it routes experts too) and writes its pools itself; its
    program takes each row's slot after the tables: ``prefill(params, ids,
    lens, tables, slots, *pools)``."""
    if T_bucket % block_size:
        raise ValueError(
            f"prefill bucket {T_bucket} must be a multiple of block_size "
            f"{block_size}"
        )
    nb = T_bucket // block_size
    if nb > max_blocks:
        raise ValueError("prefill bucket exceeds max sequence blocks")

    def live_positions(lens, tables):
        return ((jnp.arange(T_bucket)[None, :] < lens[:, None])
                & (tables[:, :1] != 0))

    if "prompt_stack" in arch:
        def prefill(params, ids, lens, tables, slots, *pools):
            x = arch["embed"](params, ids, jnp.arange(T_bucket)[None])
            x, pools, *counts = arch["prompt_stack"](
                params, x, tuple(pools), lens, live_positions(lens, tables),
                tables[:, :nb], slots, block_size)
            with jax.named_scope("head"):
                logits = arch["head"](params, _last_rows(x, lens))
            return (*pools, logits, *counts)

        return prefill

    def prefill(params, ids, lens, tables, *pools):
        layer_ws = params["layers"]
        x = arch["embed"](params, ids, jnp.arange(T_bucket)[None])
        tb = tables[:, :nb]
        counts = []
        live = live_positions(lens, tables)
        for li, w in enumerate(layer_ws):
            x, rows, c = arch["prompt_layer"](w, x, live)
            if c is not None:
                counts.append(c)
            pools = tuple(
                p.at[li, tb].set(r.reshape((B, nb, block_size) + r.shape[2:]))
                for p, r in zip(pools, rows))
        with jax.named_scope("head"):
            logits = arch["head"](params, _last_rows(x, lens))
        return (*pools, logits) + ((jnp.stack(counts),) if counts else ())

    return prefill


def build_paged_decode(arch, B, block_size, max_blocks):
    """One packed continuous-batching decode step over the paged KV cache.

    The returned pure fn
    ``step(params, kpool, vpool, tables, pos, toks, temps, key)`` feeds one
    token per row (``toks`` at per-row write positions ``pos``), gathers each
    row's context from its block table (``kpool[l][tables]`` — the
    gather-based paged attention read), overwrites the slot at ``pos`` with
    the fresh K/V in-context, masks positions ``> pos`` (per-row live
    lengths), scatters the new K/V back into the pool for future steps, and
    returns ``(kpool, vpool, next_tokens)``. Rows with ``temps > 0`` sample
    at that temperature (one PRNG key per step — not replay-stable across
    batch compositions); rows at 0 are greedy. Dead/padding rows should
    point their tables at the trash block with ``pos = 0``; their outputs
    are garbage the scheduler ignores.

    The serving engine jits this step inside :func:`feed_tokens_back`, whose
    operand list is ``(params, *pools, ints, prev, key)``: the tables,
    ``pos`` and the temperatures packed into ONE int32 array, and ``toks`` taken from the previous step's ``next_tokens`` on
    the device (``prev[src]``) wherever a row was in that step."""
    KV, D = arch["kv_heads"], arch["head_dim"]
    T_pad = block_size * max_blocks

    def step(params, kpool, vpool, tables, pos, toks, temps, key):
        layer_ws = params["layers"]
        posm = pos[:, None]
        x = arch["embed"](params, toks, pos)[:, None]
        bids = jnp.take_along_axis(tables, (pos // block_size)[:, None], axis=1)[:, 0]
        offs = pos % block_size
        live = jnp.arange(T_pad)[None, None, :] <= posm[:, :, None]
        # all context gathers hoisted above the scatter chain: layer li's
        # gather reads kpool[li], which scatters to layers < li never touch,
        # so the values are identical — but with gathers interleaved, every
        # scatter's operand has a later reader and XLA copy-on-writes the
        # whole pool per layer (CPU: ~L pool-sized temps per step); hoisted,
        # only the first scatter pays one copy. ONE gather per layer straight
        # from the 5-D pool (``kpool[li, tables]``): sliced first
        # (``kpool[li][tables]``) XLA:TPU materializes every layer's slice,
        # a second pool's worth of temporaries, and a pool sized to fill the
        # chip no longer compiles (v5e, 1.3B, 3400 blocks: 16.97 of 15.75 GB)
        with jax.named_scope("kv_gather"):
            ctx = [(kpool[li, tables].reshape(B, T_pad, KV, D),
                    vpool[li, tables].reshape(B, T_pad, KV, D))
                   for li in range(len(layer_ws))]
        for li, w in enumerate(layer_ws):
            x, k_new, v_new = _context_layer(arch, w, x, ctx[li][0],
                                             ctx[li][1], live, posm)
            kpool = kpool.at[li, bids, offs].set(k_new[:, 0])
            vpool = vpool.at[li, bids, offs].set(v_new[:, 0])
        with jax.named_scope("head"):
            logits = arch["head"](params, x[:, -1])
        return kpool, vpool, _next_tokens(logits, temps, key)

    return step


def build_paged_decode_kernel(arch, B, block_size, max_blocks):
    """``build_paged_decode`` with the attention read done by the block-table
    Pallas kernel (``ops/kernels/paged_attention``) instead of the
    gather-then-dense path: same step signature, same sampling. The engine
    builds this step for kind ``decode`` wherever Mosaic compiles
    (:func:`paged_kernel_default`).

    Differences from the gather builder:
    - no ``kpool[li, tables]`` is materialized: the kernel copies each row's
      LIVE blocks straight out of the whole pool, so the step's work follows
      ``pos`` and ``max_blocks`` is only the width of the table it is handed;
    - the fresh K/V is scattered into the pool BEFORE the kernel reads it
      (the gather path overwrites the gathered copy at ``pos`` in-context:
      the same values in the same slot). Each layer's kernel call stands
      between that layer's scatter and the next one's in the data flow, so
      XLA updates the donated pool in place (``tests/test_tpu_lowering.py``
      holds the step's temporaries under 1 GB beside a pool that fills the
      chip);
    - the online softmax sums in another order: outputs agree with the gather
      builder within the kernel's stated tolerance, not bit for bit.
    The per-layer math around the read is the arch's one ``qkv`` and
    ``finish``, the same the gather step runs (``_block_table_layer`` puts
    them around the scatter and the kernel as the arch's ``decode_layer``).

    ``step(params, *pools, tables, pos, toks, temps, key)`` returns
    ``(*pools, next_tokens)``: the embedding, the write slots, the arch's
    ``decode_layer`` a layer (K and V pools for GPT and Llama; the MLA arch's
    own: one latent pool, several residual streams, routed experts), the head
    and the sampling. Where the layers route experts the program returns after
    the tokens the rows each expert took, ``(expert layers, experts)``, for
    the engine's one read-back. The engine jits it inside
    :func:`feed_tokens_back` (operands ``(params, *pools, ints, prev,
    key)``), as it does the gather step.

    An arch that brings its whole stack (``decode_stack(params, x, pools,
    tables, pos, bids, offs, slots, block_size) -> (x, pools)``, or ``(x,
    pools, counts)``) gets the same step around it, with each row's slot
    after ``pos``: ``step(params, *pools, tables, pos, slots, toks, temps,
    key)``."""
    def write_slots(tables, pos):
        bids = jnp.take_along_axis(tables, (pos // block_size)[:, None], axis=1)[:, 0]
        return bids, pos % block_size

    if "decode_stack" in arch:
        def step(params, *args):
            *pools, tables, pos, slots, toks, temps, key = args
            x = arch["embed"](params, toks, pos)[:, None]
            x, pools, *counts = arch["decode_stack"](
                params, x, tuple(pools), tables, pos, *write_slots(tables, pos),
                slots, block_size)
            with jax.named_scope("head"):
                logits = arch["head"](params, x[:, -1])
            return (*pools, _next_tokens(logits, temps, key), *counts)

        return step

    def step(params, *args):
        *pools, tables, pos, toks, temps, key = args
        layer_ws = params["layers"]
        x = arch["embed"](params, toks, pos)[:, None]
        bids, offs = write_slots(tables, pos)
        live = tables[:, 0] != 0  # a row whose table is unmapped pads the bucket
        counts = []
        for li, w in enumerate(layer_ws):
            # the layer scatters the step's fresh rows and reads its pools
            # by block table itself
            x, pools, c = arch["decode_layer"](w, x, tuple(pools), li, tables,
                                               pos, bids, offs, live)
            if c is not None:
                counts.append(c)
        with jax.named_scope("head"):
            logits = arch["head"](params, x[:, -1])
        return ((*pools, _next_tokens(logits, temps, key))
                + ((jnp.stack(counts),) if counts else ()))

    return step


# columns of a decode step's packed operand, after the block table
STEP_COLS = 4  # position, src, host token, temperature (float32 bits)


def feed_tokens_back(inner, B, max_batch, max_blocks, n_pools, slots=False):
    """Wrap a decode step (``build_paged_decode``, ``build_paged_decode_kernel``
    or ``build_tp_paged_decode``; their bodies stay as they are) so that the
    tokens it feeds may come from the step before it WITHOUT a trip to the
    host (the serving loop enqueues step k+1 before it has read step k's
    tokens), and so that the host hands it ONE array a step.

    ``step(params, *pools, ints, prev, key)``:

    - ``ints`` (B, max_blocks + ``STEP_COLS``) int32 packs what the host
      builds: the block table, then a column each of ``pos``, ``src``, the
      host's token and the temperature (float32, bit for bit); with
      ``slots`` (an arch whose rows hold a slot, :func:`cache_slots`) one
      column more, the row's slot, which ``inner`` takes after ``pos``;
    - ``prev`` is the previous step's ``next_tokens`` as the device array it
      is, ``max_batch`` long whatever bucket produced it; ``src`` is a row's
      index in that step, or -1 for a row that was not in it (one a prefill
      just landed, or any row when nothing is in flight), which takes the
      host's token: ``toks = where(src >= 0, prev[src], host_toks)``;
    - ``key`` is the step's PRNG key, which only sampling rows read: the
      engine makes one (``fold_in`` of its base key and the step's number)
      for a step that has such a row, and hands every other step the base
      key as the device array it already is. (Folding inside the program
      cost each decode program 0.6 s more to lower on the chip's host, seven
      programs an engine: PERF.md, PR 28.)

    Returns ``inner``'s outputs with ``next_tokens`` padded to ``max_batch``,
    so that one program a bucket serves whatever bucket ran before it. The
    function keeps the name ``step``: the device line names programs
    ``jit_step`` by it."""
    def step(params, *args):
        *pools, ints, prev, key = args
        tables = ints[:, :max_blocks]
        pos, src, host_toks, temps = (
            ints[:, max_blocks + c] for c in range(STEP_COLS))
        toks = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], host_toks)
        where = (pos, ints[:, max_blocks + STEP_COLS]) if slots else (pos,)
        out = inner(params, *pools, tables, *where, toks,
                    lax.bitcast_convert_type(temps, jnp.float32), key)
        nxt = jnp.zeros((max_batch,), jnp.int32).at[:B].set(out[n_pools])
        return (*out[:n_pools], nxt, *out[n_pools + 1:])

    return step


def paged_kernel_default(arch, mosaic=None) -> bool:
    """Whether the engine's ``decode`` program for ``arch`` reads K/V through
    the block-table kernel (:func:`build_paged_decode_kernel`): wherever
    Mosaic compiles it. That is a matter of the backend (``mosaic``: chosen
    as the flash kernel is chosen, by ``interpret_default``; under the Pallas
    interpreter of the CPU tier the kernel is only a slower way to the same
    numbers) and of the arch's head width, which Mosaic takes in multiples of
    128 (``paged_attention.mosaic_takes``): GPT-3 XL and up, the Llamas. A
    narrower model (GPT-2, GPT-3 up to 2.7B, the tiny test models) keeps the
    gather step on the chip too."""
    from ..ops.kernels.paged_attention import mosaic_takes
    from ..ops.pallas import interpret_default

    if "cache" in arch:
        # an arch with a cache of its own reads it by block table on every backend
        # (through its kernel where Mosaic compiles, a plain gather of the
        # table elsewhere): the step takes the table whole either way
        return True
    if mosaic is None:
        mosaic = not interpret_default()
    return bool(mosaic) and mosaic_takes(arch["head_dim"])


def kv_block_checksums(kpool, vpool, bids):
    """Per-block content fingerprints of paged KV state — the resume-at-
    position validation entry for serving snapshots.

    A re-attached sequence resumes mid-decode through ``build_paged_decode``
    with its restored block table and ``pos`` — the compiled step needs no
    special resume path, but the KV bytes it reads must be the ones the dead
    engine wrote. This computes, for each block id in ``bids``, a
    deterministic ``(Σ|K|, Σ|V|)`` float64 reduction over that block's rows
    across all layers. ``Engine.snapshot()`` records the fingerprints of
    every owned block and ``Engine.adopt()`` recomputes them over the
    handed-over arrays: a mismatch (tampered/zeroed pool rows, dtype drift)
    is a structured ``SnapshotError`` — never a wrong-KV serve. Same arrays
    + same backend → bit-identical sums, so a clean handoff always matches.

    Returns an ``np.ndarray`` of shape ``(len(bids), 2)``; O(blocks) device
    work on the recovery path only."""
    if not len(bids):
        return np.zeros((0, 2), dtype=np.float64)
    idx = jnp.asarray(np.asarray(bids, dtype=np.int32))
    k = jnp.abs(kpool[:, idx].astype(jnp.float32)).sum(axis=(0, 2, 3, 4))
    v = jnp.abs(vpool[:, idx].astype(jnp.float32)).sum(axis=(0, 2, 3, 4))
    return np.stack([np.asarray(k), np.asarray(v)], axis=1).astype(np.float64)


def build_paged_tail_prefill(arch, B, T_bucket, block_size, max_blocks):
    """Tail prefill (a prefix-cache hit, a call of chunked prefill): prompt
    heads already live in pool blocks, only the TAIL tokens run the forward
    pass.

    The returned pure fn
    ``prefill(params, ids, starts, lens, tables, kpool, vpool)`` feeds each
    row's tail ``ids`` (B, T_bucket, padded) at absolute positions
    ``starts + [0..T)`` (``starts`` is the cached token count, a multiple of
    ``block_size``), gathers the full context from the block table exactly
    like decode, overwrites the tail's in-context slots with fresh K/V
    before the joint causal attention (so tail token j sees the cached
    prefix plus tail tokens <= j — the batched pass is mathematically the
    sequential one), scatters the tail's blocks into the pool at table
    columns ``starts//block_size + j``, and returns ``(kpool, vpool,
    logits)`` at each row's true last tail token (``lens - 1``). Shared
    prefix blocks sit BELOW every written column, so a sharer's tail
    prefill can never touch a peer's mapped block. Rows whose tail bucket
    overshoots the table (or padding rows) write to the trash block.

    An arch with a cache of its own brings the layer of such a call
    (``tail_layer(w, x, pools, li, tables, starts, lens, bids, live, scratch)
    -> (x, pools, counts, scratch)``: it writes the call's rows at ``bids``
    and reads its context by block table itself; ``scratch``, from
    ``tail_scratch``, is what the layers hand on, made once a program) and
    gets the same program around it, over the pools it declares:
    ``prefill(params, ids, starts, lens, tables, *pools)`` returns ``(*pools,
    logits)`` and, where the layers route experts, the live tokens each
    expert took."""
    if T_bucket % block_size:
        raise ValueError(
            f"tail-prefill bucket {T_bucket} must be a multiple of "
            f"block_size {block_size}"
        )
    nb = T_bucket // block_size
    T_pad = block_size * max_blocks

    def write_blocks(tables, starts):
        cols = (starts // block_size)[:, None] + jnp.arange(nb)[None, :]
        bids = jnp.take_along_axis(
            tables, jnp.minimum(cols, max_blocks - 1), axis=1)
        return jnp.where(cols < max_blocks, bids, 0)  # 0 = trash block

    if "tail_layer" in arch:
        def prefill(params, ids, starts, lens, tables, *pools):
            posm = starts[:, None] + jnp.arange(T_bucket)[None, :]
            x = arch["embed"](params, ids, posm)
            bids = write_blocks(tables, starts)
            live = ((jnp.arange(T_bucket)[None, :] < lens[:, None])
                    & (tables[:, :1] != 0))
            width, scratch = arch["tail_scratch"](B, block_size, max_blocks,
                                                  pools[0].dtype)
            wide = jnp.pad(tables, ((0, 0), (0, width - max_blocks)))
            counts = []
            for li, w in enumerate(params["layers"]):
                x, pools, c, scratch = arch["tail_layer"](
                    w, x, tuple(pools), li, wide, starts, lens, bids, live,
                    scratch)
                if c is not None:
                    counts.append(c)
            with jax.named_scope("head"):
                logits = arch["head"](params, _last_rows(x, lens))
            return (*pools, logits) + ((jnp.stack(counts),) if counts else ())

        return prefill

    KV, D = arch["kv_heads"], arch["head_dim"]

    def prefill(params, ids, starts, lens, tables, kpool, vpool):
        layer_ws = params["layers"]
        posm = starts[:, None] + jnp.arange(T_bucket)[None, :]  # (B, T)
        x = arch["embed"](params, ids, posm)
        live = jnp.arange(T_pad)[None, None, :] <= posm[:, :, None]  # (B,T,Tp)
        bids = write_blocks(tables, starts)
        # gathers hoisted above the scatter chain (see build_paged_decode):
        # avoids a whole-pool copy-on-write per layer
        with jax.named_scope("kv_gather"):
            ctx = [(kpool[li, tables].reshape(B, T_pad, KV, D),
                    vpool[li, tables].reshape(B, T_pad, KV, D))
                   for li in range(len(layer_ws))]
        for li, w in enumerate(layer_ws):
            x, k_new, v_new = _context_layer(arch, w, x, ctx[li][0],
                                             ctx[li][1], live, posm)
            kpool = kpool.at[li, bids].set(
                k_new.reshape(B, nb, block_size, KV, D))
            vpool = vpool.at[li, bids].set(
                v_new.reshape(B, nb, block_size, KV, D))
        with jax.named_scope("head"):
            logits = arch["head"](params, _last_rows(x, lens))
        return kpool, vpool, logits

    return prefill


def build_paged_spec_decode(arch, B, k, block_size, max_blocks):
    """Speculative verify: ONE batched paged-decode step that feeds k+1
    tokens per row — the row's pending next-input token followed by k
    drafted tokens — and returns the target model's greedy continuation at
    EVERY fed position.

    The returned pure fn
    ``step(params, kpool, vpool, tables, pos, toks, temps, key)`` takes
    ``toks`` (B, k+1) fed at absolute positions ``pos + [0..k]``, gathers
    the paged context, overwrites the k+1 in-context slots with fresh K/V
    before the joint causal attention (feed j attends to the cache plus
    feeds <= j, so position j's logits are exactly what j sequential decode
    steps would produce — the bit-identity guarantee), scatters all k+1
    fresh K/V into the pool, and returns ``(kpool, vpool, greedy, sampled)``
    with ``greedy`` (B, k+1) argmax rows and ``sampled`` (B,) drawn from the
    j=0 logits at ``temps`` (sampling rows accept no drafts; their one
    token per step matches plain decode's behavior). The host accepts the
    longest prefix where ``greedy[:, j-1] == toks[:, j]`` and emits
    ``greedy[:, :m+1]`` — K/V written for rejected feeds is dead weight
    the next step's feeds overwrite before any read (position p only
    becomes attendable by a LATER feed, which re-writes slot p first)."""
    KV, D = arch["kv_heads"], arch["head_dim"]
    T = k + 1
    T_pad = block_size * max_blocks

    def step(params, kpool, vpool, tables, pos, toks, temps, key):
        layer_ws = params["layers"]
        posm = pos[:, None] + jnp.arange(T)[None, :]  # (B, k+1)
        x = arch["embed"](params, toks, posm)
        live = jnp.arange(T_pad)[None, None, :] <= posm[:, :, None]
        cols = posm // block_size
        bids = jnp.take_along_axis(
            tables, jnp.minimum(cols, max_blocks - 1), axis=1)
        bids = jnp.where(cols < max_blocks, bids, 0)  # 0 = trash block
        offs = posm % block_size
        # gathers hoisted above the scatter chain (see build_paged_decode):
        # avoids a whole-pool copy-on-write per layer
        with jax.named_scope("kv_gather"):
            ctx = [(kpool[li, tables].reshape(B, T_pad, KV, D),
                    vpool[li, tables].reshape(B, T_pad, KV, D))
                   for li in range(len(layer_ws))]
        for li, w in enumerate(layer_ws):
            x, k_new, v_new = _context_layer(arch, w, x, ctx[li][0],
                                             ctx[li][1], live, posm)
            kpool = kpool.at[li, bids, offs].set(k_new)
            vpool = vpool.at[li, bids, offs].set(v_new)
        with jax.named_scope("head"):
            logits = arch["head"](params, x)  # (B, k+1, V)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = (logits[:, 0]
                  / jnp.maximum(temps, 1e-6)[:, None]).astype(jnp.float32)
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
        return kpool, vpool, greedy, sampled

    return step


# ---------------------------------------------------------------------------
# Tensor-parallel serving programs (mesh-native engine)
# ---------------------------------------------------------------------------
# The serving engine shards attention heads, the FFN columns, the LM head,
# and the paged KV pool over a "tp" mesh axis via shard_map. The sharding is
# CONCAT-partitioned, never sum-partitioned: every weight matrix that is
# split is split by OUTPUT columns (heads / FFN features / vocab rows), each
# device computes its column slice of the activation, and the tp boundary is
# an all_gather that concatenates the slices back in order. A column slice
# of a matmul output is the same per-element dot products the single-chip
# program computes, and the post-gather matmuls (attention proj / FFN down /
# argmax) run replicated on identical inputs — so greedy decode is
# bit-identical to the single-chip engine, which a psum of partial products
# could never guarantee. Embeddings and norms stay replicated (tiny); GPT's
# tied head gets its OWN vocab-row-sharded copy of wte while the replicated
# wte keeps serving the embedding lookup. The host-side block tables,
# PagePool bookkeeping, and scheduler state stay replicated — only the
# device KV arrays are sharded (on the kv-heads axis), so pool conservation,
# prefix-cache chaining, snapshot/adopt, and preemption are unchanged.

_INT8_TAG = "__int8__"  # serving/int8.quantize_params leaf encoding


def _tp_dims(arch_key):
    """(kind, H, KV, D, L, theta, eps) from a decode-state ``arch_key``."""
    if arch_key[0] == "gpt":
        _, H, D, L = arch_key
        return "gpt", H, H, D, L, None, None
    _, H, KV, D, L, theta, eps = arch_key
    return "llama", H, KV, D, L, theta, eps


def _tp_leaf(leaf, fn):
    """Apply ``fn`` to a weight leaf, looking through the int8 tagged-dict
    encoding. The scale is per-TENSOR, so slice-then-dequantize is bitwise
    dequantize-then-slice — an int8 engine shards the int8 bytes and
    dequantizes inside the shard_map body."""
    if isinstance(leaf, dict) and _INT8_TAG in leaf:
        return {_INT8_TAG: fn(leaf[_INT8_TAG]), "scale": leaf["scale"]}
    return fn(leaf)


def _tp_shape(leaf):
    if isinstance(leaf, dict) and _INT8_TAG in leaf:
        return leaf[_INT8_TAG].shape
    return leaf.shape


def tp_validate(arch_key, params, tp):
    """Shard-divisibility requirements for a tp degree; returns
    ``(ffn_width, vocab)``. Heads, kv heads, and the FFN width must divide
    evenly (the vocab is zero-padded to a tp multiple instead — padded
    logits are sliced off after the gather, so they can never win argmax)."""
    kind, H, KV, D, L, _, _ = _tp_dims(arch_key)
    ffn = _tp_shape(params["layers"][0]["up_w"])[1]
    vocab = (_tp_shape(params["wte"])[0] if kind == "gpt"
             else _tp_shape(params["head_w"])[1])
    for name, n in (("attention heads", H), ("kv heads", KV),
                    ("ffn width", ffn)):
        if n % tp:
            raise ValueError(
                f"serving: tp={tp} must divide the model's {name} ({n})")
    return ffn, vocab


def tp_pack_params(arch_key, params, tp):
    """Host-side split of a decode weight tree (float or int8-tagged) into
    ``({"rep": replicated_tree, "shard": stacked_tree}, vocab)``.

    ``shard`` holds, per weight, the tp per-device column slices stacked on
    a NEW leading axis (tp, ...) — placed with ``P("tp")`` the leading axis
    shards one standard-layout slice per device, and the shard_map body
    squeezes it with ``leaf[0]``. GPT's fused qkv is sliced through its
    (H·D, 3, H, D) view so each device owns whole (q, k, v) triples for its
    heads; the head weight is vocab-sliced after zero-padding the vocab to a
    tp multiple."""
    kind, H, KV, D, L, _, _ = _tp_dims(arch_key)
    ffn, vocab = tp_validate(arch_key, params, tp)
    Hl, KVl, Fl = H // tp, KV // tp, ffn // tp
    HD = H * D
    vp = -(-vocab // tp) * tp
    Vl = vp // tp

    def pad_vocab(a, axis):
        if vp == vocab:
            return a
        width = [(0, 0)] * a.ndim
        width[axis] = (0, vp - vocab)
        return jnp.pad(a, width)

    def dev_tree(d):
        if kind == "gpt":
            head = _tp_leaf(params["wte"], lambda a: pad_vocab(a, 0)[
                d * Vl:(d + 1) * Vl])
            layers = [{
                "qkv_w": _tp_leaf(w["qkv_w"], lambda a: a.reshape(
                    HD, 3, H, D)[:, :, d * Hl:(d + 1) * Hl].reshape(
                        HD, 3 * Hl * D)),
                "qkv_b": w["qkv_b"].reshape(3, H, D)[
                    :, d * Hl:(d + 1) * Hl].reshape(-1),
                "up_w": _tp_leaf(w["up_w"], lambda a: a[:, d * Fl:(d + 1) * Fl]),
                "up_b": w["up_b"][d * Fl:(d + 1) * Fl],
            } for w in params["layers"]]
        else:
            head = _tp_leaf(params["head_w"], lambda a: pad_vocab(a, 1)[
                :, d * Vl:(d + 1) * Vl])
            layers = [{
                "q_w": _tp_leaf(w["q_w"], lambda a: a.reshape(HD, H, D)[
                    :, d * Hl:(d + 1) * Hl].reshape(HD, Hl * D)),
                "k_w": _tp_leaf(w["k_w"], lambda a: a.reshape(HD, KV, D)[
                    :, d * KVl:(d + 1) * KVl].reshape(HD, KVl * D)),
                "v_w": _tp_leaf(w["v_w"], lambda a: a.reshape(HD, KV, D)[
                    :, d * KVl:(d + 1) * KVl].reshape(HD, KVl * D)),
                "gate_w": _tp_leaf(w["gate_w"],
                                   lambda a: a[:, d * Fl:(d + 1) * Fl]),
                "up_w": _tp_leaf(w["up_w"], lambda a: a[:, d * Fl:(d + 1) * Fl]),
            } for w in params["layers"]]
        return {"head_w": head, "layers": layers}

    if kind == "gpt":
        rep = {k: params[k] for k in ("wte", "wpe", "lnf_w", "lnf_b")}
        rep_keys = ("ln1_w", "ln1_b", "proj_w", "proj_b", "ln2_w", "ln2_b",
                    "down_w", "down_b")
    else:
        rep = {k: params[k] for k in ("wte", "lnf_w")}
        rep_keys = ("ln1_w", "o_w", "ln2_w", "down_w")
    rep["layers"] = [{k: w[k] for k in rep_keys} for w in params["layers"]]
    devs = [dev_tree(d) for d in range(tp)]
    shard = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *devs)
    return {"rep": rep, "shard": shard}, vocab


def tp_collective_bytes(arch_key, params, B, tp):
    """Per-decode-step tensor-parallel all_gather wire bytes as
    ``(fp32_bytes, int8_bytes)`` — the payload crossing the tp boundary per
    step (attention output + FFN intermediate per layer, plus the padded
    logits), counted over all devices. The int8 figure includes the f32
    blockwise scales (one per 128 elements, per-device payload padded to a
    block multiple) — the wire cost the EQuARX-style quantized-collective
    flag actually pays."""
    kind, H, KV, D, L, _, _ = _tp_dims(arch_key)
    ffn, vocab = tp_validate(arch_key, params, tp)
    vp = -(-vocab // tp) * tp
    sizes = [B * H * D, B * ffn] * L + [B * vp]

    def wire(n, int8):
        if not int8:
            return n * 4
        blocks = -(-(n // tp) // 128)
        return tp * blocks * (128 * 1 + 4)

    return (sum(wire(n, False) for n in sizes),
            sum(wire(n, True) for n in sizes))


def _tp_gather(y, quantized):
    """Concat-partitioned tp boundary: all_gather the column shards along
    the last axis. Bitwise exact — every element of the gathered tensor is
    the very dot product the single-chip program computes, just computed on
    one device and copied. With ``quantized`` (FLAGS_serve_tp_int8) the
    payload crosses the wire as blockwise int8 + f32 scales (EQuARX-style,
    ~3.9x fewer bytes, LOSSY — greedy tokens may differ)."""
    if not quantized:
        return lax.all_gather(y, "tp", axis=y.ndim - 1, tiled=True)
    from ..distributed.collective import (blockwise_dequantize,
                                          blockwise_quantize)

    flat = y.reshape(-1)
    m = flat.shape[0]
    pad = -m % 128
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    q, s = blockwise_quantize(flat)
    qg = lax.all_gather(q, "tp")  # (tp, blocks, 128) int8
    sg = lax.all_gather(s, "tp")
    parts = [blockwise_dequantize(qg[i], sg[i], y.dtype)[:m].reshape(y.shape)
             for i in range(qg.shape[0])]
    return jnp.concatenate(parts, axis=y.ndim - 1)


def _tp_arch(arch_key, tp, vocab, int8_wire):
    """One device's share of a KV-per-head arch, for the four reads above: a
    layer's weights are the pair ``(replicated, local shard)``. ``qkv``:
    local column-sharded projections; ``finish``: an all_gather at the
    attention and FFN boundaries, replicated second matmuls. Mirrors the
    single-chip arch plugs op for op so the concat of the shards is bitwise
    the single-chip activation."""
    kind, H, KV, D, L, theta, eps = _tp_dims(arch_key)
    Hl, KVl = H // tp, KV // tp

    def embed(rw, ids, posm):
        if kind == "gpt":
            return rw["wte"][ids] + rw["wpe"][posm]
        return rw["wte"][ids]

    def qkv(w, x, posm):
        # local projections: x (B,T,H·D) replicated -> q (B,T,Hl,D),
        # k/v (B,T,KVl,D) — column slices of the single-chip projections
        rwl, swl = w
        B, T = x.shape[0], x.shape[1]
        if kind == "gpt":
            h = _ln(x, rwl["ln1_w"], rwl["ln1_b"])
            qkv_ = (h @ swl["qkv_w"] + swl["qkv_b"]).reshape(B, T, 3, Hl, D)
            return qkv_[:, :, 0], qkv_[:, :, 1], qkv_[:, :, 2]
        h = _rms(x, rwl["ln1_w"], eps)
        q = (h @ swl["q_w"]).reshape(B, T, Hl, D)
        k = (h @ swl["k_w"]).reshape(B, T, KVl, D)
        v = (h @ swl["v_w"]).reshape(B, T, KVl, D)
        return _rope_grid(q, posm, theta), _rope_grid(k, posm, theta), v

    def finish(w, x, o):
        # o (B,T,Hl·D) local attention read -> gathered full heads, then
        # the replicated proj/down matmuls (identical inputs everywhere)
        rwl, swl = w
        o = _tp_gather(o, int8_wire)
        if kind == "gpt":
            x = x + (o @ rwl["proj_w"] + rwl["proj_b"])
            h2 = _ln(x, rwl["ln2_w"], rwl["ln2_b"])
            ff = _tp_gather(jax.nn.gelu(h2 @ swl["up_w"] + swl["up_b"],
                                        approximate=True), int8_wire)
            return x + (ff @ rwl["down_w"] + rwl["down_b"])
        x = x + o @ rwl["o_w"]
        h2 = _rms(x, rwl["ln2_w"], eps)
        ff = _tp_gather(jax.nn.silu(h2 @ swl["gate_w"]) * (h2 @ swl["up_w"]),
                        int8_wire)
        return x + ff @ rwl["down_w"]

    def head(rw, sw, h):
        if kind == "gpt":
            h = _ln(h, rw["lnf_w"], rw["lnf_b"])
        else:
            h = _rms(h, rw["lnf_w"], eps)
        loc = h @ (sw["head_w"].T if kind == "gpt" else sw["head_w"])
        # padded vocab columns are sliced off post-gather (static slice)
        return _tp_gather(loc, int8_wire)[:, :vocab]

    return {"embed": embed, "qkv": qkv, "finish": finish, "head": head,
            # GQA group width is tp-invariant (both axes sharded)
            "rep": H // KV, "n_layers": L, "kv_local": KVl, "head_dim": D}


def _tp_pool_spec():
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, "tp", None)


def tp_pool_sharding(mesh):
    """NamedSharding splitting a (L, NB, BS, KV, D) pool on its kv-heads
    axis — each device owns heads/tp of EVERY block, so the replicated
    host-side block tables index every shard identically."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, _tp_pool_spec())


def tp_param_shardings(mesh):
    """(replicated, stacked-shard) NamedShardings for tp_pack_params trees."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P()), NamedSharding(mesh, P("tp"))


def _tp_shard_map(body, mesh, in_specs, out_specs):
    from ..core import compat

    return compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs,
                            **compat.shard_map_check_kwargs(False))


def _tp_local(shard_tree, dtype):
    """Squeeze the stacked (1, ...) local view and dequantize int8 leaves
    INSIDE the shard_map body (per-tensor scales make it bitwise equal to
    dequantize-then-slice)."""
    from ..serving.int8 import dequantize_tree

    sq = jax.tree_util.tree_map(lambda a: a[0], shard_tree)
    return dequantize_tree(sq, dtype)


def build_tp_paged_decode(arch_key, B, block_size, max_blocks, mesh, vocab,
                          dtype, use_kernel=False, int8_wire=False):
    """Tensor-parallel ``build_paged_decode`` (or ``_kernel`` with
    ``use_kernel``): same step signature with the packed param tree from
    :func:`tp_pack_params` in place of ``params``, kpool/vpool tp-sharded on
    the kv-heads axis, tables/pos/toks/temps/key replicated. Greedy tokens
    equal the single-chip builders' (see the section comment); the
    paged-attention kernel is a drop-in on the local shard: its block copies
    read the chip's (L, NB, BS, KVl, D) pools and H/KV keeps the same GQA
    ratio. The engine jits it inside :func:`feed_tokens_back` like the
    single-chip steps (operands ``(packed, kpool, vpool, ints, prev,
    key)``, all three replicated)."""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    arch = _tp_arch(arch_key, tp, vocab, int8_wire)
    L, KVl, D = arch["n_layers"], arch["kv_local"], arch["head_dim"]
    T_pad = block_size * max_blocks
    pool_s = _tp_pool_spec()

    def body(rep_tree, shard_tree, kpool, vpool, tables, pos, toks, temps,
             key):
        from ..serving.int8 import dequantize_tree

        rw = dequantize_tree(rep_tree, dtype)
        sw = _tp_local(shard_tree, dtype)
        layer_ws = list(zip(rw["layers"], sw["layers"]))
        posm = pos[:, None]
        x = arch["embed"](rw, toks, pos)[:, None]
        bids = jnp.take_along_axis(tables, (pos // block_size)[:, None],
                                   axis=1)[:, 0]
        offs = pos % block_size
        if use_kernel:
            for li, w in enumerate(layer_ws):
                x, (kpool, vpool) = _block_table_layer(
                    arch, w, x, (kpool, vpool), li, tables, pos, bids, offs)
        else:
            live = jnp.arange(T_pad)[None, None, :] <= posm[:, :, None]
            # gathers hoisted above the scatter chain (see build_paged_decode)
            with jax.named_scope("kv_gather"):
                ctx = [(kpool[li, tables].reshape(B, T_pad, KVl, D),
                        vpool[li, tables].reshape(B, T_pad, KVl, D))
                       for li in range(L)]
            for li, w in enumerate(layer_ws):
                x, k_new, v_new = _context_layer(arch, w, x, ctx[li][0],
                                                 ctx[li][1], live, posm)
                kpool = kpool.at[li, bids, offs].set(k_new[:, 0])
                vpool = vpool.at[li, bids, offs].set(v_new[:, 0])
        with jax.named_scope("head"):
            logits = arch["head"](rw, sw, x[:, -1])
        return kpool, vpool, _next_tokens(logits, temps, key)

    wrapped = _tp_shard_map(
        body, mesh,
        (P(), P("tp"), pool_s, pool_s, P(), P(), P(), P(), P()),
        (pool_s, pool_s, P()))

    def step(packed, kpool, vpool, tables, pos, toks, temps, key):
        return wrapped(packed["rep"], packed["shard"], kpool, vpool, tables,
                       pos, toks, temps, key)

    return step


def build_tp_paged_prefill(arch_key, B, T_bucket, block_size, max_blocks,
                           mesh, vocab, dtype, int8_wire=False):
    """Tensor-parallel ``build_paged_prefill``: same signature with the
    packed param tree; each device scatters its local (B, nb, BS, KVl, D)
    K/V shard into its pool shard at the REPLICATED block table."""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    if T_bucket % block_size:
        raise ValueError(
            f"prefill bucket {T_bucket} must be a multiple of block_size "
            f"{block_size}")
    nb = T_bucket // block_size
    if nb > max_blocks:
        raise ValueError("prefill bucket exceeds max sequence blocks")
    arch = _tp_arch(arch_key, tp, vocab, int8_wire)
    KVl, D = arch["kv_local"], arch["head_dim"]
    pool_s = _tp_pool_spec()

    def body(rep_tree, shard_tree, ids, lens, tables, kpool, vpool):
        from ..serving.int8 import dequantize_tree

        rw = dequantize_tree(rep_tree, dtype)
        sw = _tp_local(shard_tree, dtype)
        x = arch["embed"](rw, ids, jnp.arange(T_bucket)[None])
        tb = tables[:, :nb]
        for li, w in enumerate(zip(rw["layers"], sw["layers"])):
            x, k, v = _causal_layer(arch, w, x)
            kpool = kpool.at[li, tb].set(
                k.reshape(B, nb, block_size, KVl, D))
            vpool = vpool.at[li, tb].set(
                v.reshape(B, nb, block_size, KVl, D))
        with jax.named_scope("head"):
            logits = arch["head"](rw, sw, _last_rows(x, lens))
        return kpool, vpool, logits

    wrapped = _tp_shard_map(
        body, mesh, (P(), P("tp"), P(), P(), P(), pool_s, pool_s),
        (pool_s, pool_s, P()))

    def prefill(packed, ids, lens, tables, kpool, vpool):
        return wrapped(packed["rep"], packed["shard"], ids, lens, tables,
                       kpool, vpool)

    return prefill


def build_tp_paged_tail_prefill(arch_key, B, T_bucket, block_size, max_blocks,
                                mesh, vocab, dtype, int8_wire=False):
    """Tensor-parallel ``build_paged_tail_prefill`` — also the chunked-
    prefill workhorse: a chunk at a block-aligned offset IS a tail feed at
    absolute positions, reading the earlier chunks' K/V through the block
    table and writing its own through the same paged scatter."""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    if T_bucket % block_size:
        raise ValueError(
            f"tail-prefill bucket {T_bucket} must be a multiple of "
            f"block_size {block_size}")
    nb = T_bucket // block_size
    T_pad = block_size * max_blocks
    arch = _tp_arch(arch_key, tp, vocab, int8_wire)
    L, KVl, D = arch["n_layers"], arch["kv_local"], arch["head_dim"]
    pool_s = _tp_pool_spec()

    def body(rep_tree, shard_tree, ids, starts, lens, tables, kpool, vpool):
        from ..serving.int8 import dequantize_tree

        rw = dequantize_tree(rep_tree, dtype)
        sw = _tp_local(shard_tree, dtype)
        posm = starts[:, None] + jnp.arange(T_bucket)[None, :]
        x = arch["embed"](rw, ids, posm)
        live = jnp.arange(T_pad)[None, None, :] <= posm[:, :, None]
        cols = (starts // block_size)[:, None] + jnp.arange(nb)[None, :]
        bids = jnp.take_along_axis(
            tables, jnp.minimum(cols, max_blocks - 1), axis=1)
        bids = jnp.where(cols < max_blocks, bids, 0)  # 0 = trash block
        with jax.named_scope("kv_gather"):
            ctx = [(kpool[li, tables].reshape(B, T_pad, KVl, D),
                    vpool[li, tables].reshape(B, T_pad, KVl, D))
                   for li in range(L)]
        for li, w in enumerate(zip(rw["layers"], sw["layers"])):
            x, k_new, v_new = _context_layer(arch, w, x, ctx[li][0],
                                             ctx[li][1], live, posm)
            kpool = kpool.at[li, bids].set(
                k_new.reshape(B, nb, block_size, KVl, D))
            vpool = vpool.at[li, bids].set(
                v_new.reshape(B, nb, block_size, KVl, D))
        with jax.named_scope("head"):
            logits = arch["head"](rw, sw, _last_rows(x, lens))
        return kpool, vpool, logits

    wrapped = _tp_shard_map(
        body, mesh, (P(), P("tp"), P(), P(), P(), P(), pool_s, pool_s),
        (pool_s, pool_s, P()))

    def prefill(packed, ids, starts, lens, tables, kpool, vpool):
        return wrapped(packed["rep"], packed["shard"], ids, starts, lens,
                       tables, kpool, vpool)

    return prefill


def build_window_draft(arch, B, W, k):
    """Model drafter: k greedy proposals per row from a SMALL same-family
    model over a dense sliding window of the newest ``W`` tokens.

    The returned pure fn ``draft(params, ids, lens)`` prefills the window
    (``ids`` (B, W) left-aligned, ``lens`` real lengths in [1, W]) with
    window-relative positions — an approximation for position-embedding
    models once the stream outgrows the window, which only costs acceptance
    rate, never correctness: the target verifies every proposal — then runs
    k single-token greedy steps against a dense per-row cache and returns
    the proposals (B, k) int32."""
    T_max = W + k

    def draft(params, ids, lens):
        layer_ws = params["layers"]
        rows = jnp.arange(B)[:, None]
        x, caches = _prompt_pass(arch, params, ids, T_max)
        logits = arch["head"](params, _last_rows(x, lens))
        out = jnp.zeros((B, k), jnp.int32)
        for j in range(k):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = out.at[:, j].set(nxt)
            posm = (lens + j)[:, None]  # per-row write position of the new token
            x = arch["embed"](params, nxt, posm[:, 0])[:, None]
            live = jnp.arange(T_max)[None, None, :] <= posm[:, :, None]
            new_caches = []
            for w, (kc, vc) in zip(layer_ws, caches):
                x, k_new, v_new = _context_layer(arch, w, x, kc, vc, live, posm)
                new_caches.append((kc.at[rows, posm].set(k_new),
                                   vc.at[rows, posm].set(v_new)))
            caches = new_caches
            logits = arch["head"](params, x[:, -1])
        return out

    return draft
