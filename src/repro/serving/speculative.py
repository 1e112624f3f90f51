"""Speculative decoding (paper §IV-B): a small draft model proposes K
tokens autoregressively; the target model verifies all K+1 positions in one
pass; rejection sampling keeps the target distribution exact (Leviathan et
al.).

Two implementations live here:

**PackedSpeculator** — the engine-grade path.  Every decode slot of
``ServeEngine(unified=True, n_spec=K)`` contributes a K+1-token *verify
segment* to the packed ragged batch (its committed feed token followed by
K draft proposals, causal within the segment, reading the slot's own pages
through the per-segment page table), mixed freely with chunked prefill
segments.  The draft model runs as its own small packed step over the same
slot layout against a *mirrored* paged KV pool (same page ids, same
allocator — prefill writes both pools, so prefix-cache hits and
preemption recompute stay valid for the draft for free), the whole
draft-catch-up -> K-proposal loop -> target-verify -> accept/reject round
is ONE jitted dispatch, and the per-slot accepted tokens + counts come
back in the step's ONE device->host transfer.  Rollback of rejected
tokens is pure length bookkeeping: the host mirror and device
``cache.lengths`` drop to the accepted frontier and the stale K/V beyond
it is masked by kv_len until overwritten — exactly the engine's
preemption-recompute trick.

**SpeculativeDecoder** — the batch-1 verification oracle (kept for
token-identity tests and as the bench's single-stream reference).  The
legacy per-token-sync round (``batched_sync=False``) is retired: the
flag survives as a deprecation shim that routes to the batched round.

Note the hardware implication the paper quantifies: both models plus both
KV pools stay resident (§IV-B's 24-28% extra memory), and the target's
verify pass processes K+1 tokens per call — pushing decode toward the
compute-bound regime.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..models.attention import PackedSegs
from ..models.model import Model, ModelCache
from .sampling import sample_slots


@dataclass
class SpecDecodeStats:
    proposed: int = 0
    accepted: int = 0
    target_passes: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.proposed, 1)

    @property
    def tokens_per_pass(self) -> float:
        return (self.accepted + self.target_passes) / max(self.target_passes,
                                                          1)


def _truncate(cache: ModelCache, lengths) -> ModelCache:
    return ModelCache(layers=cache.layers,
                      lengths=jnp.asarray(lengths, jnp.int32))


def _inv_cdf(pdf: np.ndarray, u: float) -> int:
    """Inverse-CDF draw from an unnormalized host distribution using one
    pre-pulled uniform."""
    c = np.cumsum(pdf, dtype=np.float64)
    return int(min(np.searchsorted(c, u * c[-1], side="right"),
                   len(pdf) - 1))


# ---------------------------------------------------------------------------
# device-side rejection sampling (the verify step's accept/reject core)
# ---------------------------------------------------------------------------

def rejection_accept(dec_logits, d_probs, d_toks, temps, widths,
                     u_acc, u_fin):
    """Vectorized Leviathan accept/reject over a batch of verify windows.

    ``dec_logits``: (B, K+1, V) target logits at each window position
    (position i predicts the token after draft i; position K is the bonus
    position).  ``d_probs``: (B, K, V) the draft's proposal distributions;
    ``d_toks``: (B, K) its proposals.  ``temps``: (B,) per-slot sampling
    temperature — rows at temp <= 0 use the greedy rule (accept draft i
    iff it equals the target argmax; final token = target argmax at the
    rejection/bonus position), which makes greedy outputs token-identical
    to non-speculative decoding for *any* draft.  ``widths``: (B,) the
    usable window width w <= K+1 (w-1 drafts are eligible; 0 = inactive
    slot).  ``u_acc``: (B, K) accept uniforms; ``u_fin``: (B,) one
    residual/bonus draw per row.

    Returns ``(accepted (B,), out_toks (B, K+1), n_emit (B,))``:
    ``out_toks[:, :accepted]`` are the accepted drafts, position
    ``accepted`` holds the residual resample (or the bonus draw when every
    eligible draft was accepted), and ``n_emit = accepted + 1`` tokens are
    committed per active row.
    """
    b, k = d_toks.shape
    i32 = jnp.int32
    tt = jnp.maximum(temps, 1e-4)[:, None, None]
    greedy = temps <= 0.0
    p_t = jax.nn.softmax(dec_logits.astype(jnp.float32) / tt, -1)
    p_t_d = jnp.take_along_axis(p_t[:, :k], d_toks[..., None], -1)[..., 0]
    p_d_d = jnp.take_along_axis(d_probs, d_toks[..., None], -1)[..., 0]
    ratio_ok = u_acc < jnp.minimum(1.0, p_t_d / jnp.maximum(p_d_d, 1e-20))
    greedy_ok = d_toks == jnp.argmax(dec_logits[:, :k],
                                     -1).astype(d_toks.dtype)
    acc = jnp.where(greedy[:, None], greedy_ok, ratio_ok)
    acc = acc & (jnp.arange(k)[None, :] < (widths - 1)[:, None])
    # accepted count = length of the all-accepted prefix
    a = jnp.cumprod(acc.astype(i32), axis=1).sum(axis=1)
    p_t_a = jnp.take_along_axis(p_t, a[:, None, None], 1)[:, 0]
    p_d_a = jnp.take_along_axis(d_probs,
                                jnp.minimum(a, k - 1)[:, None, None],
                                1)[:, 0]
    # every eligible draft accepted -> bonus draw straight from the
    # target; otherwise resample the rejection position's residual
    full = a >= jnp.maximum(widths - 1, 0)
    resid = jnp.maximum(p_t_a - jnp.where(full[:, None], 0.0, p_d_a), 0.0)
    rsum = resid.sum(-1, keepdims=True)
    resid = jnp.where(rsum > 0, resid, p_t_a)
    cdf = jnp.cumsum(resid, -1)
    draw = jnp.argmax(cdf >= u_fin[:, None] * cdf[:, -1:], -1)
    logits_a = jnp.take_along_axis(dec_logits, a[:, None, None], 1)[:, 0]
    final = jnp.where(greedy, jnp.argmax(logits_a, -1), draw).astype(i32)
    out = jnp.concatenate([d_toks.astype(i32), jnp.zeros((b, 1), i32)], 1)
    out = out.at[jnp.arange(b), a].set(final)
    n_emit = jnp.where(widths > 0, a + 1, 0).astype(i32)
    return a.astype(i32), out, n_emit


# ---------------------------------------------------------------------------
# the engine's batched draft/verify component
# ---------------------------------------------------------------------------

class PackedSpeculator:
    """Batched draft/verify for the unified engine.

    Owns the draft model, its paged KV pool (page-id-mirrored with the
    target pool: the engine's one ``PageAllocator`` governs both, prefill
    and verify write both pools at the same page ids), the host mirror of
    per-slot draft-consumed lengths, and the two static jitted step
    profiles (mixed decode+prefill / decode-only).  The engine packs the
    host-side layout and calls :meth:`dispatch` — one jitted call, one
    ``device_get`` — then commits lengths via :meth:`commit_slot`.

    Packed layouts (all static — nothing retraces across accept churn):

    * draft catch-up: slot s's <= 2 unconsumed tokens at offset 2s
      (1 token steady-state; 2 after a fully-accepted round's bonus),
      prefill row r's chunk at ``2 * max_slots + r * chunk_size``;
    * draft proposals: K-1 single-token decode layouts (slot s at s);
    * target verify: slot s's K+1-token window (feed + K drafts) at
      offset ``s * (K+1)``, prefill row r's chunk at
      ``max_slots * (K+1) + r * chunk_size``.
    """

    def __init__(self, target: Model, draft: Model, draft_params, *,
                 n_spec: int, max_slots: int, max_seq: int, chunk_size: int,
                 prefill_rows: int, page_size: int, n_pages: int):
        if n_spec < 1:
            raise ValueError("PackedSpeculator needs n_spec >= 1")
        if draft.spec.vocab != target.spec.vocab:
            raise ValueError(
                f"draft vocab {draft.spec.vocab} != target vocab "
                f"{target.spec.vocab}: verification compares distributions "
                "over one shared vocabulary")
        if any(kind == "ssm" for kind in draft.spec.layer_kinds()):
            raise ValueError(
                "the packed draft step supports attention-only stacks; "
                f"{draft.spec.name!r} has SSM layers")
        if draft.spec.attn.kind == "swa":
            raise ValueError("the packed draft step has no sliding-window "
                             "masking in the ragged kernel yet")
        self.target = target
        self.k = n_spec
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.chunk = chunk_size
        self.rows = prefill_rows
        self.draft = dataclasses.replace(
            draft, ctx=draft.ctx.with_(cache_layout="paged",
                                       kv_page_size=page_size))
        self.d_params = draft_params
        # page-id-mirrored pool: same n_pages as the target, so the
        # engine's page table rows address both pools unchanged
        self.d_cache = self.draft.init_cache(max_slots, max_seq,
                                             layout="paged",
                                             n_pages=n_pages)
        # host mirror: tokens whose K/V the draft pool holds, per slot
        self.d_lens = np.zeros((max_slots,), np.int64)
        self._jit_mixed = jax.jit(
            functools.partial(self._step, mixed=True),
            donate_argnums=(2, 3))
        self._jit_decode = jax.jit(
            functools.partial(self._step, mixed=False),
            donate_argnums=(2, 3))
        self._jit_fork = jax.jit(self._fork_page, donate_argnums=(0, 1))

    # -- host bookkeeping ---------------------------------------------------
    def install_slot(self, slot: int, length: int) -> None:
        """A prompt promoted into ``slot``: the packed prefill ran through
        both models, so the draft pool holds exactly the first ``length``
        tokens."""
        self.d_lens[slot] = length

    def catch_up(self, slot: int, src: list[int]) -> tuple[int, list[int]]:
        """The slot's unconsumed draft feed: ``(g, tokens)`` with
        g in {1, 2} — the tokens of ``src`` past the draft frontier, ending
        with the committed feed token ``src[-1]``."""
        lo = int(self.d_lens[slot])
        tail = src[lo:]
        return len(tail), tail

    def commit_slot(self, slot: int, length: int, emitted: int,
                    proposal_steps: int) -> None:
        """Post-round rollback bookkeeping, mirroring the device update:
        the draft consumed its catch-up plus ``proposal_steps`` in-bounds
        proposals, then rolls back to the committed frontier
        ``length + emitted`` (stale K/V of rejected proposals is masked by
        kv_len until overwritten)."""
        consumed = length + 1 + proposal_steps
        self.d_lens[slot] = min(consumed, length + emitted)

    def release_slot(self, slot: int) -> None:
        self.d_lens[slot] = 0

    def proposal_steps(self, length: int) -> int:
        """How many of the K-1 proposal decode sub-steps stay in bounds
        for a slot at committed length ``length`` (position L+i must fit
        the page-table row)."""
        return sum(1 for i in range(1, self.k)
                   if length + i <= self.max_seq - 1)

    # -- device entry point -------------------------------------------------
    def dispatch(self, params, cache: ModelCache, feed, d_feed, lengths,
                 gaps, widths, ptab, pre_tokens, pre_positions, pre_q_len,
                 pre_kv_len, pre_ptab, step_key, temps, topks, topps, *,
                 mixed: bool):
        """One fused draft+verify round for the whole batch: ONE jitted
        dispatch and NO device->host sync — the returned ``(out_toks,
        n_emit, pre_sampled)`` stay on device for the caller's single
        ``device_get``.  Every array argument is already on the device
        (the engine uploads them); the ``pre_*`` ones are None on a
        decode-only step.  Returns ``(new_target_cache, that tuple)``."""
        fn = self._jit_mixed if mixed else self._jit_decode
        cache, self.d_cache, out_toks, n_emit, pre = fn(
            params, self.d_params, cache, self.d_cache, feed, d_feed,
            lengths, gaps, widths, ptab, pre_tokens, pre_positions,
            pre_q_len, pre_kv_len, pre_ptab, step_key, temps, topks, topps)
        return cache, (out_toks, n_emit, pre)

    def fork_page(self, cache: ModelCache, src, dst) -> ModelCache:
        """Copy-on-write fork of page ``src`` into ``dst`` across the
        target AND draft pools in one dispatch (the mirrored page ids mean
        a shared prefix page is shared in both)."""
        cache, self.d_cache = self._jit_fork(cache, self.d_cache, src, dst)
        return cache

    @staticmethod
    def _fork_page(cache: ModelCache, d_cache: ModelCache, src, dst):
        def cp(a):
            page = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(a, page, dst,
                                                       axis=1)

        def fork(c):
            return ModelCache(layers=jax.tree_util.tree_map(cp, c.layers),
                              lengths=c.lengths,
                              page_table=c.page_table)

        return fork(cache), fork(d_cache)

    # -- the fused draft/verify program --------------------------------------
    def _step(self, params, d_params, cache: ModelCache,
              d_cache: ModelCache, feed, d_feed, lengths, gaps, widths,
              ptab, pre_tokens, pre_positions, pre_q_len, pre_kv_len,
              pre_ptab, step_key, temps, topks, topps, *, mixed: bool):
        k, w1, b = self.k, self.k + 1, self.max_slots
        csize, rows = self.chunk, self.rows
        i32 = jnp.int32
        active = gaps > 0
        keys = jax.random.split(step_key, k + 3)
        tt = jnp.maximum(temps[:b], 1e-4)
        greedy = temps[:b] <= 0.0

        def propose(logits, key):
            """Per-slot draft proposal + its distribution (greedy rows
            propose the argmax; the distribution is only consulted by the
            stochastic accept rule)."""
            lg = logits.astype(jnp.float32)
            p = jax.nn.softmax(lg / tt[:, None], -1)
            tok = jnp.where(greedy, jnp.argmax(lg, -1),
                            jax.random.categorical(
                                key, lg / tt[:, None])).astype(i32)
            return tok, p

        # ---- draft phase 1: catch-up (+ the same prefill chunks) ----------
        # slot s consumes its <= 2 unconsumed tokens (ending with the
        # committed feed) at offset 2s; prefill rows ride along so the
        # draft pool holds every prompt the target pool holds
        cpos = (lengths[:, None] + (jnp.arange(2, dtype=i32)[None, :]
                                    - (gaps - 1)[:, None]))
        cpos = jnp.maximum(cpos, 0).reshape(-1)
        if mixed:
            d_tok = jnp.concatenate([d_feed.reshape(-1), pre_tokens])
            d_pos = jnp.concatenate([cpos, pre_positions])
            d_qs = jnp.concatenate(
                [jnp.arange(b, dtype=i32) * 2,
                 2 * b + jnp.arange(rows, dtype=i32) * csize])
            d_ql = jnp.concatenate([gaps, pre_q_len])
            d_kl = jnp.concatenate([lengths + jnp.where(active, 1, 0),
                                    pre_kv_len])
            d_pt = jnp.concatenate([ptab, pre_ptab], axis=0)
            d_packed = PackedSegs(d_qs, d_ql, d_kl, d_pt,
                                  max_q=max(csize, 2), n_decode=b,
                                  decode_q=2)
        else:
            d_tok, d_pos = d_feed.reshape(-1), cpos
            d_qs = jnp.arange(b, dtype=i32) * 2
            d_ql = gaps
            d_kl = lengths + jnp.where(active, 1, 0)
            d_packed = PackedSegs(d_qs, d_ql, d_kl, ptab, max_q=2,
                                  n_decode=0, decode_q=2)
        d_logits, d_cache = self.draft.unified_step(d_params, d_cache,
                                                    d_tok, d_pos, d_packed)
        d_toks, d_probs = [], []
        tok, p = propose(d_logits[:b], keys[0])
        d_toks.append(tok)
        d_probs.append(p)

        # ---- draft phase 2: K-1 single-token proposal sub-steps -----------
        # (unrolled in the one trace: the whole loop is still one dispatch)
        slot_qs = jnp.arange(b, dtype=i32)
        for i in range(1, k):
            pos_i = lengths + i
            ql_i = jnp.where(active & (pos_i < self.max_seq), 1,
                             0).astype(i32)
            packed_i = PackedSegs(slot_qs, ql_i,
                                  (pos_i + 1).astype(i32), ptab,
                                  max_q=1, n_decode=0, decode_q=1)
            lg, d_cache = self.draft.unified_step(
                d_params, d_cache, d_toks[-1], pos_i.astype(jnp.int32),
                packed_i)
            tok, p = propose(lg[:b], keys[i])
            d_toks.append(tok)
            d_probs.append(p)
        d_toks_a = jnp.stack(d_toks, axis=1)  # (B, K)
        d_probs_a = jnp.stack(d_probs, axis=1)  # (B, K, V)

        # ---- target verify: feed + K drafts per slot, causal in-window ----
        t_dec_tok = jnp.concatenate([feed[:, None], d_toks_a],
                                    axis=1).reshape(-1)
        t_dec_pos = (lengths[:, None]
                     + jnp.arange(w1, dtype=i32)[None, :]).reshape(-1)
        if mixed:
            t_tok = jnp.concatenate([t_dec_tok, pre_tokens])
            t_pos = jnp.concatenate([t_dec_pos, pre_positions])
            t_qs = jnp.concatenate(
                [jnp.arange(b, dtype=i32) * w1,
                 b * w1 + jnp.arange(rows, dtype=i32) * csize])
            t_ql = jnp.concatenate([widths, pre_q_len])
            t_kl = jnp.concatenate([lengths + widths, pre_kv_len])
            t_pt = jnp.concatenate([ptab, pre_ptab], axis=0)
            t_packed = PackedSegs(t_qs, t_ql, t_kl, t_pt,
                                  max_q=max(csize, w1), n_decode=b,
                                  decode_q=w1)
        else:
            t_tok, t_pos = t_dec_tok, t_dec_pos
            t_qs = jnp.arange(b, dtype=i32) * w1
            t_packed = PackedSegs(t_qs, widths, lengths + widths, ptab,
                                  max_q=w1, n_decode=0, decode_q=w1)
        dec_logits, seg_logits, cache = self.target.verify_step(
            params, cache, t_tok, t_pos, t_packed, n_decode=b, width=w1)

        # ---- device-side accept/reject ------------------------------------
        u_acc = jax.random.uniform(keys[k], (b, k))
        u_fin = jax.random.uniform(keys[k + 1], (b,))
        _, out_toks, n_emit = rejection_accept(
            dec_logits, d_probs_a, d_toks_a, temps[:b], widths, u_acc,
            u_fin)

        # ---- completing prefills sample their first token as usual --------
        if mixed:
            with jax.named_scope("sample"):
                pre_keys = jax.random.split(keys[k + 2], rows)
                pre_sampled = sample_slots(seg_logits[b:], pre_keys,
                                           temps[b:], topks[b:], topps[b:])
        else:
            pre_sampled = None

        # ---- rollback = length bookkeeping (device side of the mirror) ----
        # target frontier: committed + emitted; draft frontier: consumed
        # catch-up + in-bounds proposals, rolled back to the target's
        proposal_ok = sum(
            jnp.where(active & (lengths + i < self.max_seq), 1, 0)
            for i in range(1, k)) if k > 1 else jnp.zeros((b,), i32)
        d_fin = jnp.minimum(lengths + 1 + proposal_ok, lengths + n_emit)
        tl = cache.lengths
        dl = d_cache.lengths
        new_tl = jnp.where(active, (lengths + n_emit).astype(tl.dtype), tl)
        new_dl = jnp.where(active, d_fin.astype(dl.dtype), dl)
        cache = ModelCache(layers=cache.layers, lengths=new_tl,
                           page_table=cache.page_table)
        d_cache = ModelCache(layers=d_cache.layers, lengths=new_dl,
                             page_table=d_cache.page_table)
        return cache, d_cache, out_toks, n_emit, pre_sampled


# ---------------------------------------------------------------------------
# batch-1 oracle
# ---------------------------------------------------------------------------

class SpeculativeDecoder:
    """Speculative decoding for a single stream — the verification oracle
    the packed engine path is tested against."""

    def __init__(self, target: Model, target_params, draft: Model,
                 draft_params, n_spec: int = 4, max_seq: int = 512,
                 temperature: float = 1.0, rng=None,
                 batched_sync: bool = True):
        assert target.spec.vocab == draft.spec.vocab
        if not batched_sync:
            warnings.warn(
                "batched_sync=False is retired: the per-token-sync round "
                "was removed in favor of the batched round (and the "
                "engine-grade path is ServeEngine(unified=True, n_spec=K) "
                "via PackedSpeculator); decoding proceeds batched",
                DeprecationWarning, stacklevel=2)
        self.target, self.tp = target, target_params
        self.draft, self.dp = draft, draft_params
        self.n = n_spec
        self.temp = max(temperature, 1e-4)
        self.rng = rng if rng is not None else jax.random.key(0)
        self.t_cache = target.init_cache(1, max_seq)
        self.d_cache = draft.init_cache(1, max_seq)
        self._t_chunk = jax.jit(target.prefill_chunk)
        self._d_step = jax.jit(draft.decode_step)
        self._d_chunk = jax.jit(draft.prefill_chunk)
        self.stats = SpecDecodeStats()
        self.batched_sync = True
        # host mirrors of the cache lengths: stop conditions and feed
        # slicing never need a device sync
        self._t_len = 0
        self._d_len = 0

    def _probs(self, logits):
        return jax.nn.softmax(logits.astype(jnp.float32) / self.temp, -1)

    def prefill(self, prompt: list[int]) -> int:
        """Consume the prompt in both models; returns the first token.
        Invariant from here on: each cache holds exactly ``seq[:-1]`` —
        everything but the newest token, which the next round consumes."""
        toks = jnp.asarray(prompt, jnp.int32)[None, :]
        t_logits, self.t_cache = self._t_chunk(self.tp, self.t_cache, toks)
        _, self.d_cache = self._d_chunk(self.dp, self.d_cache, toks)
        self._t_len = self._d_len = len(prompt)
        self.rng, k = jax.random.split(self.rng)
        tok = int(jax.device_get(jax.random.categorical(
            k, jnp.log(self._probs(t_logits))[0])))
        self.seq = list(prompt) + [tok]
        return tok

    def decode_round(self) -> list[int]:
        """One draft-propose / target-verify cycle; returns >= 1 newly
        accepted tokens (appended to ``self.seq``).  ONE device->host
        transfer per round."""
        n = self.n
        seq = self.seq

        # --- draft catch-up + n autoregressive proposals ------------------
        # feed whatever the draft hasn't consumed yet (>= 1 token: the
        # newest; +1 more after a fully-accepted round with bonus token).
        # Sampling stays on device and each token feeds the next decode
        # step directly — the proposal loop issues zero host syncs.
        feed = jnp.asarray([seq[self._d_len:]], jnp.int32)
        logits, self.d_cache = self._d_chunk(self.dp, self.d_cache, feed)
        self._d_len = len(seq)
        self.rng, k = jax.random.split(self.rng)
        keys = jax.random.split(k, n + 1)  # n accept draws + 1 resample
        d_toks, d_probs = [], []
        for i in range(n):
            p = self._probs(logits)[0]
            tok = jax.random.categorical(keys[i], jnp.log(p))
            d_toks.append(tok)
            d_probs.append(p)
            if i < n - 1:
                logits, self.d_cache = self._d_step(
                    self.dp, self.d_cache,
                    tok[None, None].astype(jnp.int32))
                self._d_len += 1
        self.stats.proposed += n

        # --- target verifies [unconsumed seq suffix, d_1 .. d_n] ----------
        gap = seq[self._t_len:]  # >= 1 tokens, ends with seq[-1]
        verify = jnp.concatenate(
            [jnp.asarray(gap, jnp.int32),
             jnp.stack(d_toks).astype(jnp.int32)])[None, :]
        t_logits_all, new_t_cache = self._verify_logits(verify)
        self.stats.target_passes += 1
        base = len(gap) - 1  # logits index predicting d_1

        # --- the round's single device->host transfer ---------------------
        p_t_all = self._probs(t_logits_all[base:base + n + 1])
        us = jax.random.uniform(keys[n], (n + 1,))
        d_toks_h, d_probs_h, p_t_h, us_h = jax.device_get(
            (jnp.stack(d_toks), jnp.stack(d_probs), p_t_all, us))

        # --- accept/reject on the host copies -----------------------------
        accepted: list[int] = []
        for i in range(n):
            d_tok = int(d_toks_h[i])
            p_t, p_d = p_t_h[i], d_probs_h[i]
            if us_h[i] < min(1.0, float(p_t[d_tok])
                             / max(float(p_d[d_tok]), 1e-20)):
                accepted.append(d_tok)
                self.stats.accepted += 1
            else:
                # resample from the residual distribution with the spare
                # uniform (us_h[n] is spent on at most one draw per round)
                resid = np.maximum(p_t.astype(np.float64)
                                   - p_d.astype(np.float64), 0.0)
                if resid.sum() <= 0:
                    resid = p_t.astype(np.float64)
                accepted.append(_inv_cdf(resid, float(us_h[n])))
                break
        else:
            # all n accepted: bonus token from the target's last position
            accepted.append(_inv_cdf(p_t_h[n].astype(np.float64),
                                     float(us_h[n])))

        self._commit(seq, accepted, new_t_cache)
        return accepted

    def _commit(self, seq, accepted, new_t_cache) -> None:
        """Roll back to the accepted frontier: caches hold ``seq[:-1]``
        (accepted[:-1] were consumed and match seq; positions beyond are
        stale K/V of rejected proposals, masked off by the truncation)."""
        self.seq = seq + accepted
        frontier = len(self.seq) - 1
        self.t_cache = _truncate(new_t_cache, [frontier])
        self._t_len = frontier
        self._d_len = min(self._d_len, frontier)
        self.d_cache = _truncate(self.d_cache, [self._d_len])

    def _verify_logits(self, tokens):
        """Target logits for every position of the verify chunk."""
        model, params = self.target, self.tp

        def fn(params, cache, toks):
            x = model._embed_in(params, toks)
            b, s, _ = x.shape
            positions = cache.lengths[:, None] + jnp.arange(s)[None, :]
            from ..models import transformer as T
            from ..models.common import rms_norm
            x, new_layers = T.apply_stack(model.spec, model.ctx,
                                          params["layers"], x, positions,
                                          cache=cache.layers,
                                          lengths=cache.lengths)
            h = rms_norm(x, params["final_norm"])
            logits = h @ model._head_w(params)
            return logits[0], ModelCache(layers=new_layers,
                                         lengths=cache.lengths + s)

        if not hasattr(self, "_verify_jit"):
            self._verify_jit = jax.jit(fn)
        return self._verify_jit(params, self.t_cache, tokens)

    def generate(self, prompt: list[int], max_new_tokens: int) -> list[int]:
        out = [self.prefill(prompt)]
        while len(out) < max_new_tokens:
            out.extend(self.decode_round())
        return out[:max_new_tokens]
