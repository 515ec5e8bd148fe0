"""Batched serving engine: continuous batching over prefill + decode steps.

The long-vector reading of serving: a decode batch is a vector register —
requests are elements, the engine keeps the register full (slot reuse on
completion), the KV/state caches are the per-lane VRF chunks.

Engine loop:
  1. admit: pack waiting requests into free slots (up to ``max_batch``),
     prefill them (left-padded to a common length bucket) and merge their
     caches into the live batch cache at their slots;
  2. step: one fused decode_step for the whole batch;
  3. retire: slots whose request hit EOS/max_tokens free up.

Topology-aware serving (``ServingEngine(..., topology=t)``): the KV cache
is placed *pod-locally* — its sharding rules are derived from the inner
topology levels only (:func:`pod_local_cache_rules`), so the outermost
(pod) ring never shards cache reads and each pod decodes from a full local
replica.  Slots are conceptually partitioned into per-pod blocks and the
admit loop prefers a slot whose pod has already served the request's prompt
prefix (prefix-cache affinity), falling back to the first free slot.  Both
policies only move *where* a request lands: admission order and per-slot
compute are unchanged, so the token streams are bit-identical to the
topology-blind engine (asserted by ``repro.testing.check_serve_topology``).

This container runs it at smoke scale on CPU; the same engine drives the
dry-run decode shapes on the production mesh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.parallel.sharding import ShardingRules, param_shardings
from repro.topology import Topology

#: tokens of the prompt head that key the pod prefix-affinity cache
PREFIX_TOKENS = 16


def pod_local_cache_rules(rules: ShardingRules,
                          topology: Topology) -> ShardingRules:
    """Cache sharding from the *inner* topology levels only: strip the
    outermost level's mesh axes from every rule value, so no cache dim is
    ever sharded across the pod ring — each pod holds (and reads) a full
    local KV replica, the serving analogue of the paper's claim that the
    long wires must never carry inner-level traffic."""
    if rules.mesh is None or rules.rules is None or topology.n_levels < 2:
        return rules
    outer = set(topology.levels[0].axes)

    def strip(v):
        if v is None:
            return None
        axes = (v,) if isinstance(v, str) else tuple(v)
        kept = tuple(a for a in axes if a not in outer)
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else kept

    return ShardingRules(rules.mesh, {k: strip(v)
                                      for k, v in rules.rules.items()})


def prefix_key(prompt: np.ndarray) -> tuple:
    """Hashable key of the prompt head (the prefix a pod's cache can reuse)."""
    return tuple(int(t) for t in np.asarray(prompt)[:PREFIX_TOKENS])


class PromptTooLongError(ValueError):
    """Prompt does not fit the engine's cache: the cache holds ``max_seq``
    positions and the first decode writes at position ``len(prompt)``, so
    admissible prompts satisfy ``len(prompt) <= max_seq - 1``."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (len,) int32
    max_new_tokens: int = 32
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    slot: int | None = None             # set at admit (observability)
    # stamps on repro.testing.timing.now(), set by the paged engine
    t_submit: float | None = None       # submit() accepted it
    t_admit: float | None = None        # it left the queue for a slot
    t_prefill_start: float | None = None  # its first prefill dispatched
    t_first: float | None = None        # its first token read back
    shared_blocks: int | None = None    # prompt blocks found shared at admit
    # (dispatch time, experts chosen per MoE layer) of each prefill chunk
    chunk_experts: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 256
    eos_id: int = 0


def validate_prompt(prompt, max_seq: int) -> int:
    """Shared submit()-time gate: returns the prompt length or raises
    :class:`PromptTooLongError` (a cache overflow waiting to happen) /
    ``ValueError`` (empty prompt)."""
    plen = int(np.asarray(prompt).shape[0])
    if plen < 1:
        raise ValueError("empty prompt")
    if plen >= max_seq:
        raise PromptTooLongError(
            f"prompt length {plen} >= max_seq {max_seq}: decode would "
            f"write position {plen} into a {max_seq}-position cache")
    return plen


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, rules: ShardingRules,
                 scfg: ServeConfig, topology: Topology | None = None):
        self.cfg = cfg
        self.params = params
        self.rules = rules
        self.scfg = scfg
        self.topology = topology
        B, S = scfg.max_batch, scfg.max_seq
        cache_defs = lm.cache_defs(cfg, B, S)
        self.cache = jax.tree.map(
            lambda pv: jnp.zeros(pv.shape, pv.dtype), cache_defs,
            is_leaf=lambda x: hasattr(x, "logical"))
        self._cache_sh = None
        self.n_pods = 1
        if topology is not None:
            self.n_pods = (topology.levels[0].size
                           if topology.n_levels > 1 else 1)
            cache_rules = pod_local_cache_rules(rules, topology)
            if cache_rules.mesh is not None:
                rr = dict(cache_rules.rules)
                if rr.get("batch") is None:
                    # serving rules keep activations batch-unsharded (the
                    # admit loop prefills one request at a time); the cache
                    # *slot* dim still shards over the inner dp levels when
                    # the slot count divides them — pod stays replicated
                    inner_dp = tuple(
                        a for lvl in topology.levels[1:-1] for a in lvl.axes
                        if a in cache_rules.mesh.shape)
                    dp_size = 1
                    for a in inner_dp:
                        dp_size *= cache_rules.mesh.shape[a]
                    if inner_dp and B % dp_size == 0:
                        rr["batch"] = inner_dp
                cache_rules = ShardingRules(cache_rules.mesh, rr)
                self._cache_sh = param_shardings(cache_defs, cache_rules)
                self.cache = jax.tree.map(jax.device_put, self.cache,
                                          self._cache_sh)
        # per-pod recently-served prompt prefixes (insertion-ordered dicts
        # used as bounded FIFO sets: old prefixes' KV gets overwritten as a
        # pod's slots recycle, so affinity beyond a few slot generations is
        # stale — and the history must not grow with distinct prompts)
        self._prefix_cap = max(1, 4 * B // self.n_pods)
        self.pod_prefixes: list[dict] = [{} for _ in range(self.n_pods)]
        self.slots: list[Request | None] = [None] * B
        self.slot_pos = np.zeros(B, np.int32)       # per-slot next position
        self.waiting: list[Request] = []
        self.finished: list[Request] = []
        self.peak_live = 0                  # high-water mark of live slots

        self._prefill = jax.jit(
            lambda p, t: lm.prefill(p, t, cfg, rules, S))
        self._step = jax.jit(
            lambda p, t, c, pos: lm.decode_step(p, t, c, pos, cfg, rules),
            out_shardings=(None, self._cache_sh)
            if self._cache_sh is not None else None)
        self._ctx = None

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request):
        validate_prompt(req.prompt, self.scfg.max_seq)
        self.waiting.append(req)

    # -- observability (shared with the paged engine / router / traffic) -----
    @property
    def n_live(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)

    @property
    def capacity(self) -> int:
        return self.scfg.max_batch

    def slot_pod(self, slot: int) -> int:
        """Home pod of a slot: slots are partitioned into contiguous
        per-pod blocks (pod p serves slots [p*B/P, (p+1)*B/P))."""
        return slot * self.n_pods // self.scfg.max_batch

    def _remember_prefix(self, pod: int, key: tuple) -> None:
        seen = self.pod_prefixes[pod]
        seen.pop(key, None)                 # refresh recency
        seen[key] = True
        while len(seen) > self._prefix_cap:
            seen.pop(next(iter(seen)))      # FIFO-evict the oldest

    def _pick_slot(self, free: list[int], req: Request) -> int:
        """First free slot, preferring pods that already hold the request's
        prompt prefix (pod-local KV reuse).  Topology-blind engines keep
        the historical first-free order bit for bit."""
        if self.topology is None or self.n_pods == 1:
            return free[0]
        key = prefix_key(req.prompt)
        for slot in free:
            if key in self.pod_prefixes[self.slot_pod(slot)]:
                return slot
        return free[0]

    def _admit(self):
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted = False
        while free and self.waiting:
            admitted = True
            req = self.waiting.pop(0)
            slot = self._pick_slot(free, req)
            free.remove(slot)
            self._remember_prefix(self.slot_pod(slot), prefix_key(req.prompt))
            req.slot = slot
            # prefill this request alone (bucketed batch prefill is the
            # batch>1 path; slot-merge is identical)
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            cache, logits = self._prefill(self.params, toks)
            nxt = int(jnp.argmax(logits[0, -1]))
            req.out.append(nxt)
            # merge this request's cache rows into the live batch cache
            self.cache = jax.tree.map(
                lambda big, small: big.at[:, slot].set(small[:, 0])
                if big.ndim >= 2 else big, self.cache, cache)
            self.slots[slot] = req
            self.slot_pos[slot] = len(req.prompt)
            self.peak_live = max(self.peak_live, self.n_live)
        if admitted and self._cache_sh is not None:
            # keep the merged cache pinned pod-locally (the .at[].set above
            # follows sharding propagation, which may drift); steps with no
            # admission skip this — _step's out_shardings already pins
            self.cache = jax.tree.map(jax.device_put, self.cache,
                                      self._cache_sh)

    # -- decode --------------------------------------------------------------
    def _live(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def step(self):
        self._admit()
        live = self._live()
        if not live:
            return False
        B = self.scfg.max_batch
        tok = np.zeros((B, 1), np.int32)
        for i in live:
            tok[i, 0] = self.slots[i].out[-1]
        # per-slot true positions: each slot writes its own ring slot and
        # masks at its own depth (dead slots carry a stale position and
        # write into their own retired rows — overwritten at next admit)
        pos = jnp.asarray(self.slot_pos, jnp.int32)
        logits, self.cache = self._step(self.params, jnp.asarray(tok),
                                        self.cache, pos)
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for i in live:
            req = self.slots[i]
            t = int(nxt[i])
            req.out.append(t)
            self.slot_pos[i] += 1
            if t == self.scfg.eos_id or \
                    len(req.out) >= req.max_new_tokens or \
                    self.slot_pos[i] >= self.scfg.max_seq - 1:
                req.done = True
                self.finished.append(req)
                self.slots[i] = None
        return True

    def run(self, max_steps: int = 10_000):
        for _ in range(max_steps):
            if not self.step() and not self.waiting:
                break
        return self.finished
