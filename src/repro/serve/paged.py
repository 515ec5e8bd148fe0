"""Paged KV serving: block-table cache, COW prefix sharing, chunked prefill.

The serving translation of AraXL's VRF decoupling: instead of one dense
``max_seq``-long KV region per slot (capacity paid at worst case, like a
monolithic VRF), K/V live in a shared pool of fixed-size token *blocks* —
the VRF chunk map applied to serving.  Each request holds a table of block
ids; attention gathers through the table; a free-list allocator hands
blocks out on demand.  Block 0 is a reserved, permanently-zero block:
unallocated table entries gather exact zeros, which is precisely what the
dense cache's unwritten rows hold — the invariant that keeps paged decode
**bit-identical** to :class:`repro.serve.engine.ServingEngine` for the
same admission order.

Prefix sharing (PR 4's prefix-affinity turned into block *reuse*): full
prompt blocks are registered under their token-content key and retained by
later requests with the same prefix; a partially-filled last block is
keyed by the whole prompt.  Shared blocks are copy-on-write — the first
decode write into a refcount>1 block copies it — so sharers never observe
each other's generated tokens.

Chunked prefill (``PagedServeConfig.chunk``): prompts are prefilled in
fixed-size chunks interleaved with decode steps, so admitting a long
prompt never stalls the running batch, and the prefill executable
compiles once per *chunk shape* instead of once per prompt length.
Chunked streams are exact per the chunked-attention math but are not
claimed bit-identical to the dense engine (the attention view is the
padded ``max_seq`` window rather than the prompt length).

Block sizing: ``block_tokens`` must tile ``max_seq`` (and the prefill
chunk).  Nothing else bounds it — decode gathers the pool with XLA, so a
block is any size the pool's memory can hold.

Tracing: the three programs are named (``jit_decode_step_paged``,
``jit_prefill_chunk``, ``jit_prefill`` in a device trace), and every
:meth:`PagedServingEngine.step` is a ``serve.step`` span holding
``serve.admit`` (``admitted``, ``blocked``), ``serve.prefill_chunk``
(``start``, ``valid``), ``serve.decode.prepare``, ``serve.decode.dispatch``
(``rows``), ``serve.decode.readback`` and ``serve.retire``.  A span costs
a flag check when no profiler runs.  Each request is stamped on
:func:`repro.testing.timing.now`: ``t_submit``, ``t_admit``,
``t_prefill_start`` (dispatch of its first chunk, or of its whole-prompt
prefill) and ``t_first``, which split its time to the first token; at
admission it also gets ``shared_blocks``, the count of its prompt's
blocks found in the prefix registry, and under a MoE model
``chunk_experts``, each chunk's dispatch time with the count of experts
its rows chose in each MoE layer (a device array).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.models.layers import SUBLAYERS
from repro.parallel.sharding import ShardingRules
from repro.testing.timing import now
from .engine import Request, validate_prompt

# chunked-prefill slot states
PREFILL, DECODE = 0, 1


def kv_token_bytes(cfg: ModelConfig) -> int:
    """Cache bytes per token across the whole model: each sublayer
    instance's ``token_values`` (:data:`repro.models.layers.SUBLAYERS`;
    k and v of an attention sublayer, the latent row of a latent-attention
    one) — the unit both engines' resident-bytes metrics are denominated
    in.  It counts the values a token's cache holds, not the lanes past
    them that pad a paged pool row to whole vector registers
    (:func:`lm.pool_defs`: the latent's 576 values in 640 lanes), so the
    dense and paged engines compare on the same data."""
    values = sum(SUBLAYERS[kind].token_values(cfg) * n
                 for _, kinds, n in lm.stacks(cfg)
                 for layer in kinds for kind in layer)
    return values * jnp.dtype(cfg.dtype).itemsize


def prefix_keys(prompt, block_tokens: int) -> list[tuple]:
    """Registry keys of a prompt's blocks, in order: ``("full", tokens up
    to the block's end)`` for each full block, then ``("part", the whole
    prompt)`` for a trailing partial block.  Tokens are held as the bytes
    of their int64 values, which hash and compare in C; a tuple of Python
    ints per key cost O(plen² / block_tokens) interpreter work a prompt."""
    flat = np.asarray(prompt, np.int64)
    plen = len(flat)
    keys = [("full", flat[:end].tobytes())
            for end in range(block_tokens, plen + 1, block_tokens)]
    if plen % block_tokens:
        keys.append(("part", flat.tobytes()))
    return keys


@dataclasses.dataclass(frozen=True)
class PagedServeConfig:
    """``n_blocks`` counts *allocatable* blocks; the pool holds one more
    (the reserved zero block).  Equal-device-memory comparisons against the
    dense engine equate ``n_blocks * block_tokens`` with the dense
    ``max_batch * max_seq`` token-slots."""
    max_batch: int = 8
    max_seq: int = 256
    eos_id: int = 0
    block_tokens: int = 16
    n_blocks: int = 128
    chunk: int = 0          # 0 = whole-prompt prefill; else chunk length


class BlockAllocator:
    """Free-list allocator over fixed-size KV token blocks with refcounts
    and a shared-prefix registry.

    Block ids index the pool; id 0 is the reserved zero block — never
    allocated, never written by a live slot.  ``alloc`` optionally
    registers the block under a content key so later requests with the
    same prefix can ``lookup`` + ``retain`` it; the *engine* implements
    copy-on-write above this class and must ``forget_key`` a block before
    writing into it exclusively (the content diverges from the key)."""

    def __init__(self, n_blocks: int, block_tokens: int):
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self._free = list(range(self.n_blocks, 0, -1))   # pop() -> lowest id
        self.refcount = np.zeros(self.n_blocks + 1, np.int64)
        self._prefix: dict[tuple, int] = {}
        self._key_of: dict[int, tuple] = {}
        self.peak_allocated = 0
        self.shared_hits = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return self.n_blocks - len(self._free)

    def alloc(self, key: tuple | None = None) -> int:
        if not self._free:
            raise RuntimeError("block pool exhausted (reservation bug: "
                               "admission must cover worst-case growth)")
        bid = self._free.pop()
        self.refcount[bid] = 1
        if key is not None:
            self.register(bid, key)
        self.peak_allocated = max(self.peak_allocated, self.n_allocated)
        return bid

    def lookup(self, key: tuple) -> int | None:
        return self._prefix.get(key)

    def retain(self, bid: int) -> int:
        assert self.refcount[bid] > 0, bid
        self.refcount[bid] += 1
        self.shared_hits += 1
        return bid

    def release(self, bid: int) -> None:
        assert self.refcount[bid] > 0, bid
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            self.forget_key(bid)
            self._free.append(bid)

    def register(self, bid: int, key: tuple) -> None:
        """Publish a block's content key (no-op if the key is taken —
        first writer wins; the duplicate block just stays private)."""
        if key in self._prefix:
            return
        self._prefix[key] = bid
        self._key_of[bid] = key

    def forget_key(self, bid: int) -> None:
        """Drop a block's registry entry before its content diverges."""
        key = self._key_of.pop(bid, None)
        if key is not None and self._prefix.get(key) == bid:
            del self._prefix[key]

    def assert_quiescent(self) -> None:
        """Shutdown hygiene gate: with no work in flight, every block must
        be back on the free list, every refcount zero (including the
        reserved zero block, which nothing may ever retain), and the
        shared-prefix registry empty.  A violation is a leaked reservation
        — the paged engine's equivalent of an fd leak: invisible to
        correctness checks, fatal to a long-running server as the pool
        quietly shrinks.  Raises :class:`BlockLeakError` naming the
        leaked block ids."""
        problems = []
        live = [int(b) for b in np.nonzero(self.refcount)[0]]
        if live:
            counts = {b: int(self.refcount[b]) for b in live[:8]}
            problems.append(f"{len(live)} blocks with live refcounts "
                            f"(id -> count, first 8: {counts})")
        if self.n_free != self.n_blocks:
            problems.append(f"free list holds {self.n_free} of "
                            f"{self.n_blocks} blocks")
        if self._prefix or self._key_of:
            problems.append(f"prefix registry not empty "
                            f"({len(self._prefix)} keys, "
                            f"{len(self._key_of)} reverse entries)")
        if problems:
            raise BlockLeakError("; ".join(problems))


class BlockLeakError(RuntimeError):
    """A shutdown-time block-accounting violation — see
    :meth:`BlockAllocator.assert_quiescent`."""


class PagedServingEngine:
    """Continuous batching over a paged KV pool.

    Same loop as :class:`ServingEngine` (admit -> step -> retire) with
    three changes: (1) admission allocates block-table entries instead of
    a dense slot region, sharing full prefix blocks COW; (2) admission is
    *reservation-based* — a request is admitted only if the pool can cover
    its worst-case future growth plus every outstanding reservation, so a
    decode-time ``alloc`` can never fail; (3) with ``chunk`` set, prefill
    runs one fixed-size chunk per engine step, interleaved with the decode
    batch, instead of blocking on the whole prompt."""

    def __init__(self, cfg: ModelConfig, params, rules: ShardingRules,
                 scfg: PagedServeConfig):
        B, S, bt = scfg.max_batch, scfg.max_seq, scfg.block_tokens
        if S % bt:
            raise ValueError(f"max_seq {S} not a multiple of "
                             f"block_tokens {bt}")
        if scfg.chunk and (scfg.chunk % bt or S % scfg.chunk):
            raise ValueError(f"chunk {scfg.chunk} must be a multiple of "
                             f"block_tokens {bt} and divide max_seq {S}")
        self.cfg = cfg
        self.params = params
        self.rules = rules
        self.scfg = scfg
        self.max_blocks = S // bt
        pool_defs = lm.pool_defs(cfg, scfg.n_blocks + 1, bt)
        self.pool = jax.tree.map(
            lambda pv: jnp.zeros(pv.shape, pv.dtype), pool_defs,
            is_leaf=lambda x: hasattr(x, "logical"))
        self.alloc = BlockAllocator(scfg.n_blocks, bt)
        self.tables = np.zeros((B, self.max_blocks), np.int32)
        self.slots: list[Request | None] = [None] * B
        self.slot_pos = np.zeros(B, np.int32)
        self.slot_state = np.full(B, DECODE, np.int32)
        self.slot_fill = np.zeros(B, np.int32)      # chunked-prefill progress
        self.slot_reserve = np.zeros(B, np.int64)   # worst-case future allocs
        # per slot, (bid, registry key) of the owned prompt blocks that
        # chunked prefill publishes once their content is written
        self._slot_new: list[list[tuple[int, tuple]]] = [[] for _ in range(B)]
        self.waiting: list[Request] = []
        self.finished: list[Request] = []
        self.peak_live = 0
        self.cow_copies = 0
        self.decode_steps = 0
        self.prefill_chunks = 0

        # named, so a device trace shows which program ran; ``lm`` is
        # looked up when each is traced, not here
        def prefill(p, t):
            return lm.prefill(p, t, cfg, rules, S)

        def decode_step_paged(p, t, pool, tab, pos, lv):
            return lm.decode_step_paged(p, t, pool, tab, pos, lv, cfg, rules)

        def prefill_chunk(p, t, pool, row, start, valid):
            return lm.prefill_chunk(p, t, pool, row, start, valid, cfg,
                                    rules)

        # the pool is donated: each program writes its new rows into the
        # buffers it was given, and the engine keeps only what it returns
        self._prefill = jax.jit(prefill)
        self._step = jax.jit(decode_step_paged, donate_argnums=(2,))
        self._chunk = jax.jit(prefill_chunk, donate_argnums=(2,))

    # -- observability -------------------------------------------------------
    @property
    def n_live(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)

    @property
    def capacity(self) -> int:
        return self.scfg.max_batch

    def kv_bytes_resident(self) -> int:
        return self.alloc.n_allocated * self.scfg.block_tokens \
            * kv_token_bytes(self.cfg)

    def kv_bytes_resident_peak(self) -> int:
        return self.alloc.peak_allocated * self.scfg.block_tokens \
            * kv_token_bytes(self.cfg)

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request):
        plen = validate_prompt(req.prompt, self.scfg.max_seq)
        bt = self.scfg.block_tokens
        worst = min(math.ceil((plen + req.max_new_tokens) / bt),
                    self.max_blocks)
        if worst > self.scfg.n_blocks:
            raise ValueError(
                f"request needs up to {worst} blocks but the pool holds "
                f"{self.scfg.n_blocks}")
        req.t_submit = now()
        self.waiting.append(req)

    def _plan(self, req: Request):
        """Admission plan: (table row, owned (blk_idx, key-or-None) list,
        shared bids, reservation).  None if the pool cannot cover this
        request's worst case plus every outstanding reservation."""
        bt = self.scfg.block_tokens
        plen = len(req.prompt)
        row: list[int] = []
        own: list[tuple[int, tuple]] = []          # (blk_idx, registry key)
        shared: list[int] = []
        partial_shared = False
        for j, key in enumerate(prefix_keys(req.prompt, bt)):
            bid = self.alloc.lookup(key)
            if bid is not None:
                row.append(bid)
                shared.append(bid)
                partial_shared = key[0] == "part"
            else:
                row.append(-1)
                own.append((j, key))
        prompt_blocks = len(row)
        total = min(math.ceil((plen + req.max_new_tokens) / bt),
                    self.max_blocks)
        growth = total - prompt_blocks
        # reservation: decode-time growth blocks, plus one COW copy if the
        # partial block is shared (full shared blocks are never written)
        reserve = growth + (1 if partial_shared else 0)
        need_now = len(own)
        if self.alloc.n_free < need_now + reserve + int(self.slot_reserve.sum()):
            return None
        return row, own, shared, reserve

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _admit(self) -> int:
        """Admit from the head of the queue while a slot is free and the
        pool covers the head's plan; returns how many were admitted."""
        free = self._free_slots()
        admitted = 0
        while free and self.waiting:
            plan = self._plan(self.waiting[0])
            if plan is None:
                break                       # head-of-line waits for blocks
            row, own, shared, reserve = plan
            req = self.waiting.pop(0)
            req.t_admit = now()
            req.shared_blocks = len(shared)
            admitted += 1
            slot = free.pop(0)
            req.slot = slot
            for bid in shared:
                self.alloc.retain(bid)
            new_bids = []
            chunked = bool(self.scfg.chunk)
            for j, key in own:
                # chunked prefill registers keys only once the content is
                # fully written (prefill completion), so a concurrent
                # admit never shares a half-filled block
                bid = self.alloc.alloc(None if chunked else key)
                row[j] = bid
                new_bids.append((j, bid))
            if chunked:
                self._slot_new[slot] = [(bid, key) for (_, key), (_, bid)
                                        in zip(own, new_bids)]
            self.tables[slot] = 0
            self.tables[slot, :len(row)] = row
            self.slot_reserve[slot] = reserve
            self.slots[slot] = req
            self.peak_live = max(self.peak_live, self.n_live)
            if chunked:
                self.slot_state[slot] = PREFILL
                self.slot_fill[slot] = 0
                self.slot_pos[slot] = 0
            else:
                self._prefill_whole(slot, req, new_bids)
        return admitted

    def _prefill_whole(self, slot: int, req: Request,
                       new_bids: list[tuple[int, int]]):
        """Non-chunked admission: run the *same* jitted prefill as the
        dense engine (identical first token and cache values), then
        scatter the newly-owned blocks of the dense cache into the pool —
        shared blocks already hold identical content and are skipped."""
        toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
        req.t_prefill_start = now()
        cache, logits = self._prefill(self.params, toks)
        req.out.append(int(jnp.argmax(logits[0, -1])))
        req.t_first = now()
        if new_bids:
            js = jnp.asarray([j for j, _ in new_bids])
            bids = jnp.asarray([b for _, b in new_bids])
            self.pool = lm.cache_into_pool(self.pool, cache, js, bids)
        self.slot_state[slot] = DECODE
        self.slot_pos[slot] = len(req.prompt)

    # -- chunked prefill -----------------------------------------------------
    def _prefill_step(self) -> bool:
        """Run ONE prefill chunk for the lowest-index PREFILL slot (the
        interleave: at most one chunk of prefill work per engine step, so
        the decode batch never waits on a whole long prompt)."""
        pf = [i for i, s in enumerate(self.slots)
              if s is not None and self.slot_state[i] == PREFILL]
        if not pf:
            return False
        i = pf[0]
        req = self.slots[i]
        c = self.scfg.chunk
        prompt = np.asarray(req.prompt)
        plen = len(prompt)
        start = int(self.slot_fill[i])
        valid = min(c, plen - start)
        with TraceAnnotation("serve.prefill_chunk", start=start,
                             valid=valid):
            chunk = np.zeros((1, c), np.int32)
            chunk[0, :valid] = prompt[start:start + valid]
            t = now()
            if start == 0:
                req.t_prefill_start = t
            logits, self.pool, *reached = self._chunk(
                self.params, jnp.asarray(chunk), self.pool,
                jnp.asarray(self.tables[i]), jnp.int32(start),
                jnp.int32(valid))
            if reached:
                # left on the device: read after serving, not per chunk
                req.chunk_experts.append((t, reached[0]))
            self.prefill_chunks += 1
            self.slot_fill[i] = start + valid
            if self.slot_fill[i] >= plen:
                req.out.append(int(jnp.argmax(logits[0, valid - 1])))
                req.t_first = now()
                self.slot_state[i] = DECODE
                self.slot_pos[i] = plen
                # content now complete: publish the owned prompt blocks
                for bid, key in self._slot_new[i]:
                    self.alloc.register(bid, key)
                self._slot_new[i] = []
        return True

    # -- decode --------------------------------------------------------------
    def _ensure_writable(self, i: int):
        """Pre-step guarantee for slot i: the block holding position
        ``slot_pos[i]`` exists, is exclusively owned, and carries no
        registry key — so the jitted step's scatter is a plain write.
        On-demand alloc and COW both draw on the slot's reservation."""
        bt = self.scfg.block_tokens
        j = int(self.slot_pos[i]) // bt
        bid = int(self.tables[i, j])
        if bid == 0:
            self.tables[i, j] = self.alloc.alloc()
            self.slot_reserve[i] = max(0, self.slot_reserve[i] - 1)
        elif self.alloc.refcount[bid] > 1:
            nb = self.alloc.alloc()
            self.pool = jax.tree.map(
                lambda pl: pl.at[:, nb].set(pl[:, bid]), self.pool)
            self.alloc.release(bid)
            self.tables[i, j] = nb
            self.cow_copies += 1
            self.slot_reserve[i] = max(0, self.slot_reserve[i] - 1)
        else:
            self.alloc.forget_key(bid)

    def _decode_live(self) -> list[int]:
        return [i for i, s in enumerate(self.slots)
                if s is not None and self.slot_state[i] == DECODE]

    def _retire(self, i: int):
        req = self.slots[i]
        req.done = True
        self.finished.append(req)
        for j in range(self.max_blocks):
            bid = int(self.tables[i, j])
            if bid:
                self.alloc.release(bid)
        self.tables[i] = 0
        self.slot_pos[i] = 0
        self.slot_fill[i] = 0
        self.slot_reserve[i] = 0
        self.slot_state[i] = DECODE
        self._slot_new[i] = []
        self.slots[i] = None

    def step(self) -> bool:
        with TraceAnnotation("serve.step"):
            with TraceAnnotation("serve.admit") as span:
                admitted = self._admit()
                span.set_metadata(admitted=admitted, blocked=len(self.waiting))
            worked = False
            if self.scfg.chunk:
                worked |= self._prefill_step()
            live = self._decode_live()
            if live:
                self._decode(live)
                worked = True
        return worked

    def _decode(self, live: list[int]):
        """One decode step over the live slots: make each slot's next
        position writable, run the step, read the tokens back, retire the
        finished."""
        with TraceAnnotation("serve.decode.prepare"):
            for i in live:
                self._ensure_writable(i)
            B = self.scfg.max_batch
            tok = np.zeros((B, 1), np.int32)
            lv = np.zeros(B, bool)
            for i in live:
                tok[i, 0] = self.slots[i].out[-1]
                lv[i] = True
        with TraceAnnotation("serve.decode.dispatch", rows=len(live)):
            logits, self.pool = self._step(
                self.params, jnp.asarray(tok), self.pool,
                jnp.asarray(self.tables), jnp.asarray(self.slot_pos),
                jnp.asarray(lv))
            self.decode_steps += 1
        with TraceAnnotation("serve.decode.readback"):
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for i in live:
            req = self.slots[i]
            t = int(nxt[i])
            req.out.append(t)
            self.slot_pos[i] += 1
            if t == self.scfg.eos_id or \
                    len(req.out) >= req.max_new_tokens or \
                    self.slot_pos[i] >= self.scfg.max_seq - 1:
                with TraceAnnotation("serve.retire"):
                    self._retire(i)

    def run(self, max_steps: int = 10_000):
        for _ in range(max_steps):
            if not self.step() and not self.waiting:
                break
        return self.finished

    def shutdown(self) -> None:
        """End-of-life hygiene: refuse to shut down over live work, then
        require the allocator quiescent (:class:`BlockLeakError` names any
        leaked blocks).  Callers that drain to completion (the traffic
        generator, the acceptance checks) call this so a refcount bug
        fails the run loudly instead of surviving as a slow pool leak."""
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if live or self.waiting:
            raise BlockLeakError(
                f"shutdown with work in flight: live slots {live}, "
                f"{len(self.waiting)} waiting requests")
        self.alloc.assert_quiescent()
