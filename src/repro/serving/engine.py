"""Slot-indexed generation engine: explicit prefill/decode over a
persistent slot cache, plus the retained sequential reference path.

The serving core is split into the two phases a continuous-batching
scheduler needs (paper App. E.3 — feedback-as-it-completes):

  prefill(prompts) -> (next-token logits, cache_slice)
      One full-sequence forward (`models.model.prefill`) whose per-layer
      K/V / SSD-state / cross-attention caches come back as a batch-shaped
      slice, ready to be written into free slots. No token-by-token replay.

  decode_chunk(state, steps) -> state
      Advances ALL occupied slots of the replica in one jitted step,
      regardless of which tenant/request owns each slot: every slot carries
      its own position (`models.model.decode_step` takes (B,) pos), its own
      RNG key/step and its own token budget, so requests admitted at
      different times decode together in a single fixed-shape program.

  admit / release
      The slot manager. `admit` scatters a prefill slice into free slot
      indices (`leaf.at[:, slots].set` — a full-length overwrite, so slot
      reuse needs no explicit clearing); `release` just frees the slots.

Sampling policy (shared by both paths, and what makes continuous batching
bit-equal to the sequential reference on row-deterministic families): each
request row i samples step j with key fold_in(fold_in(PRNGKey(seed), i), j)
via a per-row categorical — never a batch-level key split — so a row's
token stream depends only on (seed, i, its own logits), not on which other
rows share the decode batch.

`Engine.generate` remains the blocking per-request reference (now also
prefill-based) that `router.cloud.SchedulingCloud.dispatch` and the
equivalence tests use.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import model as M


@dataclasses.dataclass
class GenResult:
    tokens: np.ndarray        # (B, max_new)
    out_lens: np.ndarray      # (B,) tokens generated incl. EOS
    logprobs: np.ndarray      # (B,) mean chosen-token logprob (quality proxy)


class SlotState(NamedTuple):
    """Per-replica serving state: a slot-indexed cache plus per-slot
    decode bookkeeping. Cache leaves are (layers, slots, ...) — the slot
    axis is the model batch axis, so `decode_step` advances every slot in
    one call."""
    cache: Any                 # pytree, leaves (layers, S, ...)
    last: jnp.ndarray          # (S, V) f32 next-token logits
    out: jnp.ndarray           # (S, max_out) i32 generated tokens (eos-filled)
    pos: jnp.ndarray           # (S,) i32 next decode position
    step: jnp.ndarray          # (S,) i32 decode steps taken (RNG index)
    max_new: jnp.ndarray       # (S,) i32 per-slot token budget
    key: jnp.ndarray           # (S, 2) u32 per-row sampling keys
    active: jnp.ndarray        # (S,) bool slot occupied
    finished: jnp.ndarray      # (S,) bool EOS emitted
    lp_sum: jnp.ndarray        # (S,) f32 chosen-logprob sum
    n_out: jnp.ndarray         # (S,) i32 tokens generated incl. EOS


def _row_keys(base_key, b: int):
    """Per-row sampling keys: fold_in(base, row). (b, 2) uint32."""
    return jax.vmap(lambda i: jax.random.fold_in(base_key, i))(jnp.arange(b))


def _sample(keys, last, temperature, eos_id):
    """One sampling step for a batch of rows; per-row categorical so the
    result for row i depends only on (keys[i], last[i])."""
    logits = last.astype(jnp.float32) / jnp.maximum(temperature, 1e-4)
    tok = jax.vmap(jax.random.categorical)(keys, logits)       # (B,)
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
    return tok.astype(jnp.int32), chosen


class Engine:
    """One replica's generation engine over any ArchConfig model."""

    def __init__(self, cfg: ArchConfig, params, *, max_len: int = 512,
                 eos_id: int = 1, temperature: float = 1.0,
                 dtype=jnp.float32, enc_frames: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.dtype = dtype
        # audio: encoder length is an engine property (tests use short stubs)
        self.enc_frames = enc_frames or M.WHISPER_ENC_FRAMES
        # params are an argument of every jitted program, never a closure
        # constant: at published widths a captured copy would be compiled
        # into each executable (GBs of host memory per program)
        self._gen = jax.jit(self._generate, static_argnames=("max_new",))
        self._prefill_jit = jax.jit(self._prefill)
        # the slot state is threaded linearly through admit/decode/release,
        # so its buffers (the whole slot cache included) are donated — the
        # scatter updates happen in place instead of copying the cache on
        # every scheduler tick
        self._admit_jit = jax.jit(self._admit, donate_argnums=0)
        self._decode_jit = jax.jit(self._decode_chunk,
                                   static_argnames=("steps",),
                                   donate_argnums=1)
        self._release_jit = jax.jit(self._release, donate_argnums=0)

    # ------------------------------------------------------------- internals
    def _inputs(self, prompts):
        cfg = self.cfg
        b, s = prompts.shape
        inputs = {"tokens": prompts}
        if cfg.family == "vlm":
            inputs["vision_embeds"] = jnp.zeros(
                (b, max(s // M.VLM_VISION_FRACTION, 1), cfg.d_model),
                self.dtype)
        if cfg.family == "audio":
            inputs["frames"] = jnp.zeros((b, self.enc_frames, cfg.d_model),
                                         self.dtype)
        return inputs

    def _prefill(self, params, prompts):
        return M.prefill(self.cfg, params, self._inputs(prompts),
                         self.max_len, cache_dtype=self.dtype)

    def _generate(self, params, prompts, base_key, *, max_new: int):
        cfg = self.cfg
        b, s = prompts.shape
        last, cache = self._prefill(params, prompts)
        pos0 = M.prefill_len(cfg, s)
        rkeys = _row_keys(base_key, b)

        def step(carry, j):
            cache, last, finished, lp_sum, n_out = carry
            keys = jax.vmap(jax.random.fold_in)(rkeys, jnp.full((b,), j))
            tok, chosen = _sample(keys, last, self.temperature, self.eos_id)
            tok = jnp.where(finished, self.eos_id, tok)
            lp_sum = lp_sum + jnp.where(finished, 0.0, chosen)
            n_out = n_out + (~finished).astype(jnp.int32)
            finished = finished | (tok == self.eos_id)
            lg, cache = M.decode_step(cfg, params, tok[:, None],
                                      cache, pos0 + j)
            return (cache, lg[:, 0], finished, lp_sum, n_out), tok

        init = (cache, last, jnp.zeros((b,), bool),
                jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32))
        carry, toks = jax.lax.scan(step, init, jnp.arange(max_new))
        _, _, _, lp_sum, n_out = carry
        return toks.T, n_out, lp_sum / jnp.maximum(n_out, 1)

    # ------------------------------------------------------------- slot API
    def init_slots(self, n_slots: int,
                   max_out: Optional[int] = None) -> SlotState:
        """Allocate the persistent slot cache. The cache structure is taken
        from `prefill`'s own output (eval_shape on a 1-token prompt), so it
        matches every family exactly — including audio cross caches at this
        engine's ``enc_frames``."""
        max_out = max_out or self.max_len
        dummy = jnp.zeros((1, 1), jnp.int32)
        _, abs_cache = jax.eval_shape(self._prefill, self.params, dummy)
        cache = jax.tree.map(
            lambda a: jnp.zeros((a.shape[0], n_slots) + a.shape[2:], a.dtype),
            abs_cache)
        def z_i():
            # distinct buffers per field: the state is donated into the
            # admit/decode jits, and donation rejects aliased leaves
            return jnp.zeros((n_slots,), jnp.int32)

        return SlotState(
            cache=cache,
            last=jnp.zeros((n_slots, self.cfg.vocab), jnp.float32),
            out=jnp.full((n_slots, max_out), self.eos_id, jnp.int32),
            pos=z_i(), step=z_i(), max_new=z_i(),
            key=jnp.zeros((n_slots, 2), jnp.uint32),
            active=jnp.zeros((n_slots,), bool),
            finished=jnp.zeros((n_slots,), bool),
            lp_sum=jnp.zeros((n_slots,), jnp.float32), n_out=z_i())

    def prefill(self, prompts) -> Tuple[jnp.ndarray, Any]:
        """Prompt phase: (next-token logits (B, V), cache_slice) — the slice
        `admit` writes into free slots."""
        return self._prefill_jit(self.params, jnp.asarray(prompts, jnp.int32))

    def _admit(self, state: SlotState, slot_ix, lg, cache_slice,
               rkeys, pos0, max_new):
        cache = jax.tree.map(
            lambda big, sl: big.at[:, slot_ix].set(sl.astype(big.dtype)),
            state.cache, cache_slice)
        b = slot_ix.shape[0]
        eos_row = jnp.full((b, state.out.shape[1]), self.eos_id, jnp.int32)
        return state._replace(
            cache=cache,
            last=state.last.at[slot_ix].set(lg.astype(state.last.dtype)),
            out=state.out.at[slot_ix].set(eos_row),
            pos=state.pos.at[slot_ix].set(pos0),
            step=state.step.at[slot_ix].set(0),
            max_new=state.max_new.at[slot_ix].set(max_new),
            key=state.key.at[slot_ix].set(rkeys),
            active=state.active.at[slot_ix].set(True),
            finished=state.finished.at[slot_ix].set(False),
            lp_sum=state.lp_sum.at[slot_ix].set(0.0),
            n_out=state.n_out.at[slot_ix].set(0))

    def admit(self, state: SlotState, slot_ix, lg, cache_slice, *,
              prompt_len: int, max_new, seed: Optional[int] = None,
              rkeys=None) -> SlotState:
        """Write a prefilled slice into free slots ``slot_ix`` (host list or
        array of B slot indices). Row i gets sampling key
        fold_in(PRNGKey(seed), i) — the same keys the sequential reference
        uses, which is what makes the two paths emit identical tokens.

        For a prefill *bucket* (several stacked requests sharing one prompt
        length) pass ``rkeys`` (B, 2) — each request's own per-row keys,
        concatenated — and ``max_new`` as a (B,) per-slot budget instead of
        a scalar. The donated `state` must not be reused by the caller."""
        slot_ix = jnp.asarray(slot_ix, jnp.int32)
        pos0 = M.prefill_len(self.cfg, prompt_len)
        mn = np.broadcast_to(np.asarray(max_new, np.int32),
                             (slot_ix.shape[0],))
        # real exceptions, not asserts: these guard serving control flow
        # and must keep firing under `python -O`
        if mn.max() > state.out.shape[1]:
            raise ValueError(f"max_new {max_new} exceeds the slot out "
                             f"buffer {state.out.shape}")
        if self.cfg.sliding_window is None and self.cfg.family != "ssm":
            if pos0 + int(mn.max()) > self.max_len:
                raise ValueError(
                    f"prompt_len {prompt_len} + max_new {max_new} exceeds "
                    f"the engine's max_len {self.max_len}")
        if rkeys is None:
            rkeys = _row_keys(jax.random.PRNGKey(seed), slot_ix.shape[0])
        return self._admit_jit(state, slot_ix, lg, cache_slice, rkeys,
                               jnp.int32(pos0), jnp.asarray(mn))

    def _decode_chunk(self, params, state: SlotState, *, steps: int):
        n_slots = state.pos.shape[0]
        rows = jnp.arange(n_slots)
        max_out = state.out.shape[1]

        def one(state, _):
            # a slot is live while occupied, un-finished and within budget;
            # finished slots are frozen (their remaining tokens are forced
            # EOS, which the eos-filled `out` buffer already encodes — the
            # sequential path emits exactly the same suffix)
            alive = state.active & ~state.finished & \
                (state.step < state.max_new)
            keys = jax.vmap(jax.random.fold_in)(state.key, state.step)
            tok, chosen = _sample(keys, state.last, self.temperature,
                                  self.eos_id)
            tok = jnp.where(alive, tok, self.eos_id)
            lp_sum = state.lp_sum + jnp.where(alive, chosen, 0.0)
            n_out = state.n_out + alive.astype(jnp.int32)
            finished = state.finished | (alive & (tok == self.eos_id))
            out_ix = jnp.where(alive, state.step, max_out)   # OOB -> drop
            out = state.out.at[rows, out_ix].set(tok, mode="drop")
            # decode runs over ALL slots (fixed shape, one compiled program);
            # non-live rows feed EOS at a frozen pos — their cache rows may
            # rot, but results are already in `out` and admit overwrites the
            # full slice on reuse, so no gating of the cache is needed
            lg, cache = M.decode_step(self.cfg, params, tok[:, None],
                                      state.cache, state.pos)
            return state._replace(
                cache=cache, last=lg[:, 0].astype(state.last.dtype),
                out=out,
                pos=jnp.where(alive, state.pos + 1, state.pos),
                step=jnp.where(alive, state.step + 1, state.step),
                finished=finished, lp_sum=lp_sum, n_out=n_out), None

        state, _ = jax.lax.scan(one, state, None, length=steps)
        return state

    def decode_chunk(self, state: SlotState, steps: int) -> SlotState:
        """Advance every occupied slot ``steps`` tokens in one jitted scan.
        `state` is donated (updated in place) — use the returned state."""
        return self._decode_jit(self.params, state, steps=steps)

    def _release(self, state: SlotState, slot_ix):
        return state._replace(active=state.active.at[slot_ix].set(False))

    def release(self, state: SlotState, slot_ix) -> SlotState:
        """Free slots (admit fully overwrites, so this is just the flag)."""
        return self._release_jit(state, jnp.asarray(slot_ix, jnp.int32))

    # ------------------------------------------------------------- public
    def generate(self, prompts: np.ndarray, max_new: int,
                 seed: int = 0) -> GenResult:
        """Blocking per-request reference path (prefill + jitted decode)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        toks, n_out, lp = self._gen(self.params, prompts,
                                    jax.random.PRNGKey(seed), max_new=max_new)
        return GenResult(np.asarray(toks), np.asarray(n_out), np.asarray(lp))
