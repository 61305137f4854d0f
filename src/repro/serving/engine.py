"""Continuous-batching decode engine.

A fixed number of batch *slots* decode in lock-step (one fused
``decode_step`` per tick — the TPU-friendly formulation: all slots share
the program; dead slots carry a pad token and are masked out). Requests
arrive in a queue; a freed slot triggers a single-sequence prefill whose
cache is spliced into the batch cache at the slot index.

Fault tolerance: ``simulate_failure(frac)`` drains the ``ceil(frac ·
n_slots)`` batch slots that stand in for the failed fraction of the fleet
— their in-flight requests re-queue (keeping their original arrival and
start timestamps so TTFT accounting spans the outage) and only their cache
positions are zeroed — then triggers a re-plan through the AFD planner's
discrete rescale (§3.3 as a live policy).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model

PAD = 0


def failure_drain_count(frac_nodes_lost: float, n_slots: int) -> int:
    """Slots to drain when ``frac_nodes_lost`` of capacity fails.

    Exactly ``ceil(frac · n_slots)`` (clamped to ``n_slots``): the lowest
    slot indices stand in for the failed nodes, survivors keep decoding.
    Shared by ``DecodeEngine`` and ``AFDServeEngine`` so both engines (and
    the fleet layer built on them) agree on partial-drain semantics.
    """
    if not 0.0 <= frac_nodes_lost <= 1.0:
        raise ValueError(
            f"frac_nodes_lost must be in [0, 1], got {frac_nodes_lost}")
    return min(n_slots, math.ceil(frac_nodes_lost * n_slots - 1e-12))


def splice_batch_slot(dst_tree, src_tree, slot: int, n_slots: int,
                      t_offset: int = 0):
    """Write a 1-sequence cache pytree into batch position ``slot``.

    The batch axis is identified explicitly: the axis where ``dst`` has
    size ``n_slots``, ``src`` has size 1, and every other dimension agrees.
    Matching on whole-shape inequality is wrong at ``n_slots == 1`` (the
    two shapes coincide and the splice silently becomes a no-op, leaving
    decode running on a stale/zero cache).

    Token slabs: a ``src`` leaf may additionally be *shorter* than ``dst``
    along exactly one further axis — it is written as a contiguous slab
    starting at ``t_offset`` on that axis, in one fused update instead of a
    Python loop of single-position writes. Equal-shape leaves keep the
    original whole-slot semantics, so every existing caller is unchanged.
    """
    def splice(dst, src):
        if dst.ndim == 0:
            return dst
        for ax in range(dst.ndim):
            if not (dst.shape[ax] == n_slots and src.shape[ax] == 1):
                continue
            rest_dst = dst.shape[:ax] + dst.shape[ax + 1:]
            rest_src = src.shape[:ax] + src.shape[ax + 1:]
            idx = [slice(None)] * dst.ndim
            idx[ax] = slot
            src_idx = [slice(None)] * src.ndim
            src_idx[ax] = 0
            if rest_dst == rest_src:
                return dst.at[tuple(idx)].set(
                    src[tuple(src_idx)].astype(dst.dtype))
            diff = [i for i, (a, b) in enumerate(zip(rest_dst, rest_src))
                    if a != b]
            if len(diff) == 1:
                tax = diff[0] + (1 if diff[0] >= ax else 0)  # dst axis id
                n = src.shape[tax]
                if n < dst.shape[tax] and t_offset + n <= dst.shape[tax]:
                    idx[tax] = slice(t_offset, t_offset + n)
                    return dst.at[tuple(idx)].set(
                        src[tuple(src_idx)].astype(dst.dtype))
        return dst
    return jax.tree_util.tree_map(splice, dst_tree, src_tree)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # (S,) int32
    max_new_tokens: int
    arrived: float = 0.0
    started: float = 0.0
    finished: float = 0.0
    output: List[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new_tokens


@dataclasses.dataclass
class EngineStats:
    ticks: int = 0
    tokens_out: int = 0
    prefills: int = 0
    requeued: int = 0

    def throughput(self, wall: float) -> float:
        return self.tokens_out / wall if wall > 0 else 0.0


class DecodeEngine:
    """Lock-step continuous batching over ``n_slots`` sequences."""

    def __init__(self, model: Model, params, n_slots: int, max_len: int,
                 greedy: bool = True, seed: int = 0):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.greedy = greedy
        self.rng = np.random.RandomState(seed)

        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.cache = model.init_cache(n_slots, max_len)
        self.cur_tokens = np.zeros((n_slots,), np.int32)
        self.stats = EngineStats()

        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=max_len))

    # ---- request management --------------------------------------------------

    def submit(self, req: Request) -> None:
        req.arrived = time.time()
        self.queue.append(req)

    def _splice_cache(self, slot: int, single_cache) -> None:
        """Insert a 1-sequence prefill cache into batch position ``slot``."""
        self.cache = splice_batch_slot(self.cache, single_cache, slot,
                                       self.n_slots)

    def _select(self, logits_row) -> int:
        """Greedy or seeded-softmax token selection (shared by prefill and
        the decode tick, so ``greedy=False`` applies to every token)."""
        if self.greedy:
            return int(jnp.argmax(logits_row))
        p = np.asarray(jax.nn.softmax(
            jnp.asarray(logits_row).astype(jnp.float32)))
        return int(self.rng.choice(p.shape[0], p=p / p.sum()))

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            if req.started == 0.0:       # re-admissions keep the original
                req.started = time.time()    # timestamp: TTFT spans outages
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            logits, cache1 = self._prefill(self.params, batch)
            self._splice_cache(slot, cache1)
            first = self._select(logits[0])
            req.output.append(first)
            self.slots[slot] = req
            self.cur_tokens[slot] = first
            self.stats.prefills += 1
            self.stats.tokens_out += 1   # the prefill-produced first token

    # ---- the decode tick -------------------------------------------------------

    def tick(self) -> int:
        """One lock-step decode over all live slots. Returns live count."""
        self._admit()
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return 0
        tokens = jnp.asarray(self.cur_tokens)
        logits, self.cache = self._decode(self.params, self.cache, tokens)
        nxt = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
        if not self.greedy:
            for i in live:
                nxt[i] = self._select(logits[i])
        for i in live:
            req = self.slots[i]
            req.output.append(int(nxt[i]))
            self.cur_tokens[i] = nxt[i]
            self.stats.tokens_out += 1
            if req.done or int(self.cache["pos"][i]) >= self.max_len - 1:
                req.finished = time.time()
                self.slots[i] = None
        self.stats.ticks += 1
        return len(live)

    def run(self, max_ticks: int = 10_000) -> None:
        while (self.queue or any(s is not None for s in self.slots)) \
                and self.stats.ticks < max_ticks:
            self.tick()

    # ---- fault tolerance ---------------------------------------------------------

    def simulate_failure(self, frac_nodes_lost: float,
                         replan: Optional[Callable[[float], None]] = None
                         ) -> int:
        """Fail ``frac_nodes_lost`` of capacity: drain the affected slots.

        ``ceil(frac · n_slots)`` slots (the lowest indices stand in for the
        failed nodes) drain their in-flight requests back to the queue for
        a fresh generation attempt; surviving slots keep decoding. Drained
        requests keep their original ``arrived``/``started`` timestamps so
        TTFT accounting spans the outage. Returns the number of requeued
        requests. ``replan`` receives the surviving-capacity fraction (the
        scheduler hooks the AFD planner's discrete rescale here).
        """
        n_drain = failure_drain_count(frac_nodes_lost, self.n_slots)
        requeued = 0
        for i in range(n_drain):
            req = self.slots[i]
            if req is not None:
                req.output.clear()       # restart generation after recovery
                self.queue.appendleft(req)
                self.slots[i] = None
                requeued += 1
        if n_drain:
            # only the drained slots' caches are stale; zero their positions
            # so the next admit overwrites them — survivors keep decoding.
            drained = jnp.arange(n_drain)
            self.cache["pos"] = self.cache["pos"].at[drained].set(0)
        self.stats.requeued += requeued
        if replan is not None:
            replan(1.0 - frac_nodes_lost)
        return requeued
