"""Slot-based serving engine: prefill + decode over rotating request slots.

The decode cache is a fixed bank of ``num_slots`` request slots; requests
enter a slot mid-flight (continuous batching — from a local prefill or from
a migrated paged-KV hand-off, see ``serve/scheduler.py``) and leave it the
step they finish, freeing the slot for the next admission.  One decode step
always runs the full slot bank; inactive slots carry ``pos=0, tok=0``
padding whose cache writes are either masked by the per-slot validity rules
or overwritten at the next admission, so rotation never perturbs the active
slots' numerics.

``Engine.generate`` (the lockstep API the tests and examples drive) is a
thin orbit of the same machinery: admit the whole batch at once, decode
until done.  Disaggregated serving gets bitwise-identical decode because
both paths share ``decode_slots``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import kvcache, model
from repro.obs import prof


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0       # 0 = greedy
    eos_id: int = -1               # -1 = never stop early
    seed: int = 0


@dataclasses.dataclass
class SlotBatch:
    """State of one decode slot bank (functional: steps return a new one)."""
    cache: dict                    # batched decode cache, B = num_slots
    pos: jnp.ndarray               # (B,) int32 — next decode position
    tok: jnp.ndarray               # (B,) int32 — last sampled token
    active: np.ndarray             # (B,) bool, host-side occupancy mask

    @property
    def num_slots(self) -> int:
        return int(self.pos.shape[0])

    def free_slots(self) -> list:
        return [i for i in range(self.num_slots) if not self.active[i]]


class Engine:
    def __init__(self, cfg_arch, params, *, max_len: int):
        self.cfg = cfg_arch
        self.params = params
        self.max_len = max_len

        # named functions: the device trace finds the programs as
        # ``jit_prefill`` and ``jit_decode_step``
        def prefill(p, b, c):
            return model.prefill(p, cfg_arch, b, c)

        def decode_step(p, t, pos, c):
            return model.decode_step(p, cfg_arch, t, pos, c)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step)

    def _sample(self, logits, key, temperature):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1) \
            .astype(jnp.int32)

    # ------------------------------------------------------------ slot API
    def init_slots(self, num_slots: int) -> SlotBatch:
        return SlotBatch(
            cache=kvcache.init_cache(self.cfg, num_slots, self.max_len),
            pos=jnp.zeros((num_slots,), jnp.int32),
            tok=jnp.zeros((num_slots,), jnp.int32),
            active=np.zeros((num_slots,), bool))

    def prefill_request(self, request: dict, key, temperature: float = 0.0):
        """Prefill ONE request (batch axis 1).  Returns
        ``(first_token, logits, cache1)`` — the B=1 cache a migration packs
        from, and the first generated token sampled from the last-position
        logits (the token that travels in the migration header)."""
        S = request["tokens"].shape[1]
        assert S <= self.max_len, "prompt exceeds cache"
        cache = kvcache.init_cache(self.cfg, 1, self.max_len)
        logits, cache = self._prefill(self.params, request, cache)
        tok = self._sample(logits, key, temperature)
        return int(tok[0]), logits, cache

    def activate_slot(self, slots: SlotBatch, slot: int, *, pos: int,
                      token: int) -> SlotBatch:
        """Mark a slot occupied with its decode cursor and pending token.
        The slot's cache contents must already be in place (batched prefill,
        or `kvpool.insert_blocks`/`insert_tail` after a migration)."""
        active = slots.active.copy()
        active[slot] = True
        return SlotBatch(cache=slots.cache,
                         pos=slots.pos.at[slot].set(pos),
                         tok=slots.tok.at[slot].set(token),
                         active=active)

    def evict_slot(self, slots: SlotBatch, slot: int) -> SlotBatch:
        """Release a slot.  The cache rows keep their bytes (stale data is
        masked by pos-validity and fully overwritten on the next admission);
        pos/tok return to the inactive padding values."""
        active = slots.active.copy()
        active[slot] = False
        return SlotBatch(cache=slots.cache,
                         pos=slots.pos.at[slot].set(0),
                         tok=slots.tok.at[slot].set(0),
                         active=active)

    def decode_slots(self, slots: SlotBatch, key, temperature: float = 0.0):
        """ONE decode step over the whole slot bank.  Active slots advance
        their cursor; inactive slots hold at (pos=0, tok=0) padding.
        Returns ``(new_slots, tokens)`` with tokens the per-slot samples."""
        logits, cache = self._decode(self.params, slots.tok[:, None],
                                     slots.pos, slots.cache)
        tok = self._sample(logits, key, temperature)
        mask = jnp.asarray(slots.active)
        return SlotBatch(
            cache=cache,
            pos=jnp.where(mask, slots.pos + 1, 0).astype(jnp.int32),
            tok=jnp.where(mask, tok, 0).astype(jnp.int32),
            active=slots.active.copy()), tok

    def decode_slots_paged(self, slots: SlotBatch, key, ctx, heap, view,
                           temperature: float = 0.0):
        """ONE decode step consuming K/V straight from the symmetric-heap
        block pool: the view assembles every paged leaf through the slot
        block tables (byte-identical to what the dense rehydrate would have
        built, so the step itself is bitwise-identical to
        :meth:`decode_slots`), the exact same jitted decode runs, and each
        active slot's new K/V token is written back to its owning pool
        block — with copy-on-write if that block is shared.  The returned
        bank cache keeps only non-paged state; its paged leaves stay zero.
        Returns ``(new_slots, tokens, heap)``."""
        with prof.scope(ctx, "kv_assemble"):
            cache = view.assemble(heap, slots.cache)
        # the paged-attention kernel region proper: assembled K/V in,
        # next-token logits out.  nbytes = assembled cache footprint
        # (static .nbytes attrs — no device sync to compute the label)
        with prof.scope(ctx, "paged_attn", lambda: dict(
                nbytes=sum(leaf.nbytes
                           for leaf in jax.tree_util.tree_leaves(cache)),
                path="engine", tier="local",
                work_items=int(slots.active.sum()))) as ps:
            logits, new_cache = self._decode(
                self.params, slots.tok[:, None], slots.pos, cache)
            logits = ps(logits)
        tok = self._sample(logits, key, temperature)
        with prof.scope(ctx, "kv_writeback"):
            heap = view.writeback(ctx, heap, new_cache, slots.pos,
                                  slots.active)
        mask = jnp.asarray(slots.active)
        return SlotBatch(
            cache=view.strip(new_cache),
            pos=jnp.where(mask, slots.pos + 1, 0).astype(jnp.int32),
            tok=jnp.where(mask, tok, 0).astype(jnp.int32),
            active=slots.active.copy()), tok, heap

    # ------------------------------------------------------- lockstep API
    def generate(self, batch, scfg: ServeConfig = ServeConfig(), *,
                 num_slots: int = 0):
        """batch: {tokens: (B, S_prompt) [+ frontend embeds]}.
        Returns (B, max_new_tokens) generated ids.

        Lockstep special case of the slot machinery: every request admitted
        at step 0 (one shared batched prefill), decoded until max_new.

        ``num_slots`` > B decodes in a bank of that many slots, the batch in
        the first B and the rest idle, as a server with that bank does.  A
        decode step's slots never affect each other's bits, but on the TPU
        the bank's size does: XLA picks another program for another number
        of rows (a one-row dot becomes a reduction), which rounds
        differently.
        """
        tokens = batch["tokens"]
        B, S = tokens.shape
        assert S + scfg.max_new_tokens <= self.max_len + 1, \
            "cache too small for prompt + generation"
        rows = max(B, num_slots)
        logits, cache = self._prefill(self.params, batch,
                                      kvcache.init_cache(self.cfg, B,
                                                         self.max_len))
        key = jax.random.key(scfg.seed)
        tok = self._sample(logits, key, scfg.temperature)
        if rows > B:
            idle = kvcache.init_cache(self.cfg, rows - B, self.max_len)
            cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 1),
                                 cache, idle)
        active = np.arange(rows) < B
        slots = SlotBatch(cache=cache,
                          pos=jnp.where(active, S, 0).astype(jnp.int32),
                          tok=jnp.zeros((rows,), jnp.int32).at[:B].set(tok),
                          active=active)
        out = []
        done = jnp.zeros((B,), bool)
        for i in range(scfg.max_new_tokens):
            out.append(jnp.where(done, 0, slots.tok[:B]))
            done = done | (slots.tok[:B] == scfg.eos_id)
            key = jax.random.fold_in(key, i)
            slots, _ = self.decode_slots(slots, key, scfg.temperature)
        return jnp.stack(out, axis=1)
