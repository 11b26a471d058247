"""Batched fixed-point engine: coalesced, sharded, per-sample-masked solves.

Serving traffic is ragged: requests arrive one at a time, differ in
difficulty (iterations to converge) and leave at different times.  Running
one solve per request wastes the accelerator; running a naive batch makes
every request pay for the slowest sample.  This module is the middle path —
the batched solve mode of the tentpole engine:

  * ``coalesce_states`` packs a ragged list of per-request states into one
    fixed-slot batch (padding slots repeat the first request and are masked
    invalid), so one jitted solve serves the whole wave.
  * ``batched_solve`` runs the registered forward solver ONCE over the
    batch with per-sample convergence masking: converged and invalid
    samples freeze (their updates are masked out, they consume no
    quasi-Newton memory), and the whole-batch ``all(converged)`` reduction
    — the step-count collective — drives early exit, so the batch stops as
    soon as the last *live* sample converges.
  * under a mesh, the solver state and the low-rank (U, V) memory are
    pinned batch-sharded via ``solve_sharding``; each device then solves
    its batch shard fully locally and the only cross-device chatter is the
    per-step convergence reduction (plus the coefficient-block reduce when
    the feature axes are TP-sharded).

This is the *inference* engine: no ``custom_vjp``, no saved residuals.
Training (always a full, valid batch) goes through
``implicit_fixed_point``, which shares all the machinery below except the
freeze mask.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solvers import SolveCarry, reset_carry_rows
from repro.implicit.config import ImplicitConfig
from repro.implicit.fixed_point import (
    ImplicitStats,
    kernel_scope,
    prepare_flat_problem,
)
from repro.implicit.registry import SOLVERS
from repro.obs import metrics as obs_metrics

# populate the registry on import (mirrors fixed_point.py)
from repro.implicit import solvers as _builtin_solvers  # noqa: F401

Array = jax.Array
Pytree = Any


class CoalescedBatch(NamedTuple):
    """A wave of requests packed into one fixed-slot solver batch."""

    z0: Pytree        # (slots, ...) stacked initial states
    valid: Array      # (slots,) bool — False for padding slots
    unbatch: Callable[[Pytree], list[Pytree]]  # batch -> per-request states


def coalesce_states(states: list[Pytree], slots: int | None = None) -> CoalescedBatch:
    """Stack per-request state pytrees (no leading batch dim) into one batch.

    ``slots`` pads the batch to a fixed size (keeping the jitted solve's
    shape stable across waves); padding repeats request 0 and is marked
    invalid, so the solver freezes it at entry — padding costs no
    iterations and no quasi-Newton memory.
    """
    if not states:
        raise ValueError("coalesce_states needs at least one request")
    n = len(states)
    slots = n if slots is None else slots
    if slots < n:
        raise ValueError(f"{n} requests do not fit {slots} slots")
    padded = list(states) + [states[0]] * (slots - n)
    z0 = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *padded)
    valid = jnp.arange(slots) < n

    def unbatch(z: Pytree) -> list[Pytree]:
        return [jax.tree_util.tree_map(lambda a: a[i], z) for i in range(n)]

    return CoalescedBatch(z0=z0, valid=valid, unbatch=unbatch)


def batched_solve(
    f: Callable[[Any, Any, Pytree], Pytree],
    params: Any,
    x: Any,
    z0: Pytree,
    cfg: ImplicitConfig,
    *,
    valid: Array | None = None,
    ctx=None,
    state_axes: tuple[str | None, ...] | None = None,
    carry: SolveCarry | None = None,
) -> tuple[Pytree, ImplicitStats] | tuple[Pytree, ImplicitStats, SolveCarry]:
    """One batched forward solve of ``z = f(params, x, z)`` (inference only).

    ``valid: (B,) bool`` marks live samples; the rest are frozen at ``z0``
    (returned untouched, reported converged).  ``ctx``/``state_axes`` pin
    the solve to the model's SPMD layout exactly as in
    ``implicit_fixed_point``.  Jit-able; differentiating through it unrolls
    the solver loop — use ``implicit_fixed_point`` for training.

    ``carry`` warm-starts per slot and turns the return into ``(z, stats,
    new_carry)``.  Frozen slots (``valid=False``) keep their carry rows
    BIT-FOR-BIT — they neither move nor age — so a slot can sit idle across
    waves without its state drifting.
    """
    z0_flat, unravel, f_flat, sharding = prepare_flat_problem(
        f, z0, ctx, state_axes)
    freeze = None if valid is None else ~valid

    solver = SOLVERS.get(cfg.forward.solver)
    with kernel_scope(sharding):
        res = _builtin_solvers.call_solver(
            solver, lambda z: f_flat(params, x, z), z0_flat,
            cfg.solver_cfg(), sharding=sharding, freeze_mask=freeze,
            carry=carry)
    z = res.z
    if valid is not None:
        # padding/finished slots return their input state bit-for-bit
        mask = valid.reshape(valid.shape + (1,) * (z.ndim - 1))
        z = jnp.where(mask, z, z0_flat)
    stats = ImplicitStats(res.residual, res.n_steps, res.converged, res.trace,
                          res.tape, res.status)
    obs_metrics.record_solve("serve", res, carry=carry)
    if carry is None:
        return unravel(z), stats
    return unravel(z), stats, res.carry


# ---------------------------------------------------------------------------
# Per-slot carry cache (the serving engine's persistent solve state)
# ---------------------------------------------------------------------------


def write_carry_rows(dst: SolveCarry, src: SolveCarry,
                     slots: Sequence[int], rows: Sequence[int]) -> SolveCarry:
    """Copy batch-rows ``rows`` of ``src`` into batch-slots ``slots`` of
    ``dst`` in ONE scatter per buffer (all SolveCarry fields; the qN ring
    buffers scatter along their batch axis 1).  Used to place a coalesced
    wave's seeded carries into the serving loop's slot layout — one call
    per wave, not one full-buffer copy per request.  ``slots``/``rows``
    may be traced index arrays, so the scatter can live inside a jitted
    serving program."""
    sl = jnp.asarray(slots, jnp.int32)
    rw = jnp.asarray(rows, jnp.int32)
    lr_d, lr_s = dst.lowrank, src.lowrank
    return SolveCarry(
        z=dst.z.at[sl].set(src.z[rw].astype(dst.z.dtype)),
        lowrank=type(lr_d)(
            alpha=lr_d.alpha,
            u=lr_d.u.at[:, sl].set(lr_s.u[:, rw].astype(lr_d.u.dtype)),
            v=lr_d.v.at[:, sl].set(lr_s.v[:, rw].astype(lr_d.v.dtype)),
            count=lr_d.count.at[sl].set(lr_s.count[rw]),
        ),
        warm=dst.warm.at[sl].set(src.warm[rw]),
        age=dst.age.at[sl].set(src.age[rw]),
    )


def write_carry_slot(dst: SolveCarry, src: SolveCarry, slot: int,
                     row: int) -> SolveCarry:
    """Single-request view of :func:`write_carry_rows`."""
    return write_carry_rows(dst, src, (slot,), (row,))


class CarryCache:
    """Host-side per-slot :class:`SolveCarry` store for the serving engine.

    Each of the engine's fixed batch slots owns one carry row, keyed by the
    request id currently leased to the slot.  ``lease`` binds a slot to a
    request and EVICTS the previous occupant's state (per-row cold reset —
    a recycled slot must never warm-start from a stranger's equilibrium);
    ``release`` evicts explicitly when a request completes.  The batched
    carry itself is device data: pass ``.carry`` into the jitted solve and
    hand the updated pytree back via ``update``.

    Staleness policy: ``max_age`` bounds how many solves a row may
    accumulate before it is auto-reset to cold on the next ``update`` —
    a long-lived request's carry drifts as its equilibrium moves token by
    token, and past the bound a cold restart beats a stale chain.  ``None``
    (the default) keeps the legacy purely ownership-driven eviction.

    Every eviction increments ``evictions`` and a per-reason counter
    (``evictions_by_reason`` plus the registry counter
    ``carry_evictions_total{reason=ownership|release|stale}``).
    """

    def __init__(self, make_cold: Callable[[], SolveCarry], slots: int, *,
                 max_age: int | None = None):
        self.slots = slots
        self.max_age = max_age
        self._owner: list[Any] = [None] * slots
        self.carry: SolveCarry = make_cold()
        self.evictions = 0
        self.evictions_by_reason = {"ownership": 0, "release": 0, "stale": 0}
        if self.carry.z.shape[0] != slots:
            raise ValueError(
                f"cold carry has batch {self.carry.z.shape[0]} for "
                f"{slots} slots")
        if max_age is not None and max_age < 1:
            raise ValueError(f"max_age must be >= 1, got {max_age}")

    def _count(self, reason: str, n: int = 1) -> None:
        self.evictions += n
        self.evictions_by_reason[reason] += n
        obs_metrics.default_registry().counter(
            "carry_evictions_total", {"reason": reason}).inc(n)

    def _reset(self, slot: int, reason: str = "ownership") -> None:
        mask = jnp.arange(self.slots) == slot
        self.carry = reset_carry_rows(self.carry, mask)
        self._count(reason)

    def lease(self, slot: int, request_id: Any, *,
              reset: bool = True) -> None:
        """Bind ``slot`` to ``request_id``; evicts any previous occupant.

        ``reset=False`` skips the device-side cold reset (ownership
        bookkeeping and the eviction count only) — for callers about to
        overwrite EVERY field of the row anyway (e.g. the admission path,
        which scatters a freshly seeded carry right after leasing).
        """
        if self._owner[slot] == request_id and request_id is not None:
            return
        self._owner[slot] = request_id
        if reset:
            self._reset(slot)
        else:
            self._count("ownership")

    def release(self, slot: int) -> None:
        """Request finished: free the slot and evict its carry."""
        self._owner[slot] = None
        self._reset(slot, reason="release")

    def owner(self, slot: int) -> Any:
        return self._owner[slot]

    def update(self, carry: SolveCarry) -> None:
        """Adopt the post-solve carry returned by the jitted step, then
        apply the staleness policy: rows whose ``age`` exceeds ``max_age``
        are reset to cold (warm flag cleared, ring count zeroed) so the
        next solve for that slot cold-starts from its caller's ``z0``."""
        self.carry = carry
        if self.max_age is None:
            return
        # age is a small (slots,) vector; the host round-trip is trivial
        # next to the solve that produced the carry
        stale = np.asarray(carry.age) > self.max_age
        n = int(stale.sum())
        if n:
            self.carry = reset_carry_rows(self.carry, jnp.asarray(stale))
            self._count("stale", n)


# ---------------------------------------------------------------------------
# Cross-request prefix carry cache (the prefix-cache analogue of CarryCache)
# ---------------------------------------------------------------------------


_PREFIX_HASH_MOD = (1 << 61) - 1
_PREFIX_HASH_MUL = 1_000_003
_PREFIX_HASH_SEED = 7919


def prefix_hashes(tokens: Sequence[int]) -> list[int]:
    """Rolling (polynomial) hashes of every prefix of ``tokens``.

    ``out[k]`` covers ``tokens[:k]`` (``out[0]`` is the empty-prefix seed).
    One O(len) pass per lookup; index entries are keyed by ``out[L]`` so a
    longest-prefix-match probes exactly one dict slot per stored length.
    """
    out = [_PREFIX_HASH_SEED]
    acc = _PREFIX_HASH_SEED
    for t in tokens:
        acc = (acc * _PREFIX_HASH_MUL + int(t) + 1) % _PREFIX_HASH_MOD
        out.append(acc)
    return out


@dataclasses.dataclass
class PrefixEntry:
    """One cached prefix: the solve carry snapshot at a token boundary.

    ``z`` is the (L, *feat) equilibrium slice over the prefix positions;
    ``u``/``v`` the donor's quasi-Newton ring restricted to the same
    positions (``(m, L, *feat)``; zero-padded pairs act as identity on any
    suffix subspace a consumer appends) with ``count`` valid slots.  Host
    arrays — the index never holds device memory alive.
    """

    tokens: tuple[int, ...]
    z: Any
    u: Any
    v: Any
    count: int
    born: int        # index clock at (re)publication — staleness anchor
    last_used: int   # index clock at last lease/publication — LRU anchor
    refs: int = 0    # in-flight leases; ref'd entries are never evicted
    hits: int = 0

    @property
    def length(self) -> int:
        return len(self.tokens)


class PrefixMatch(NamedTuple):
    """A leased lookup result: release via ``PrefixCarryIndex.release``."""

    entry: PrefixEntry
    length: int   # matched prefix length (== entry.length)
    exact: bool   # the whole prompt matched (full hit vs partial hit)


class PrefixCarryIndex:
    """Host-side cross-request prefix cache of solve-carry snapshots.

    SHINE's reuse move — share the forward pass's inverse estimate instead
    of recomputing it — applied ACROSS requests: two prompts sharing a
    token prefix converge (causally) to the same prefix equilibrium, so the
    carry computed for one prefill (iterate + qN ring at the divergence
    point) is a valid warm start for the other.  The serving loop publishes
    every completed prefill's carry here and consults the index at
    admission; see ``runtime/serving.ServeLoop``.

    Keying: entries are keyed by a rolling hash of the token prefix and
    stored at ``block``-aligned boundaries plus the full prompt length, so
    a lookup finds the longest stored prefix of the query (full prompt
    match = exact hit, shorter boundary = partial hit).  Hash collisions
    are excluded by comparing the stored token tuple.  Publishing a prefix
    that is already stored refreshes the entry (dedup: shared prefixes
    across prompts are stored once).

    Eviction reuses the PR 6 staleness machinery's shape: ``slots`` bounds
    capacity with LRU eviction, ``max_age`` bounds how many index
    operations (≈ admitted requests) an entry may survive without being
    republished.  Entries with a live ref (leased to an in-flight prefill)
    are never evicted — capacity may transiently overflow until release.
    Every eviction lands in ``evictions_by_reason`` and the registry
    counter ``prefix_cache_evictions_total{reason=lru|stale}``; occupancy
    is mirrored to the ``prefix_cache_entries`` / ``prefix_cache_tokens``
    gauges.
    """

    def __init__(self, slots: int = 32, *, block: int = 4,
                 max_age: int | None = None):
        if slots < 0:
            raise ValueError(f"slots must be >= 0, got {slots}")
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if max_age is not None and max_age < 1:
            raise ValueError(f"max_age must be >= 1, got {max_age}")
        self.slots = slots
        self.block = block
        self.max_age = max_age
        self._entries: dict[int, PrefixEntry] = {}
        self._clock = 0
        self.published = 0
        self.lookups = 0
        self.hits = 0
        self.evictions_by_reason = {"lru": 0, "stale": 0, "poisoned": 0}

    # -- bookkeeping ---------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def tokens_held(self) -> int:
        return sum(e.length for e in self._entries.values())

    def stats(self) -> dict:
        return {"entries": len(self), "tokens": self.tokens_held(),
                "published": self.published, "lookups": self.lookups,
                "hits": self.hits, "evictions": dict(self.evictions_by_reason)}

    def _publish_gauges(self) -> None:
        obs_metrics.record_prefix_occupancy(len(self), self.tokens_held())

    def _evict(self, key: int, reason: str) -> None:
        del self._entries[key]
        self.evictions_by_reason[reason] += 1
        obs_metrics.default_registry().counter(
            "prefix_cache_evictions_total", {"reason": reason}).inc()

    def _sweep_stale(self) -> None:
        if self.max_age is None:
            return
        stale = [k for k, e in self._entries.items()
                 if e.refs == 0 and self._clock - e.born > self.max_age]
        for k in stale:
            self._evict(k, "stale")

    def _evict_lru(self) -> None:
        while len(self._entries) > self.slots:
            victims = [(e.last_used, k) for k, e in self._entries.items()
                       if e.refs == 0]
            if not victims:
                return  # everything leased: transient overflow until release
            self._evict(min(victims)[1], "lru")

    # -- the cache interface -------------------------------------------

    def publish(self, tokens: Sequence[int], z, u=None, v=None,
                count: int = 0) -> int:
        """Store a completed prefill's carry snapshot for ``tokens``.

        ``z``: the (L, *feat) converged equilibrium over the prompt;
        ``u``/``v``: the donor's (m, L, *feat) quasi-Newton ring buffers
        with ``count`` valid slots (``None`` stores an iterate-only entry).
        The snapshot is sliced at ``block``-aligned boundaries plus the full
        length so shorter overlaps remain matchable; returns the number of
        NEW entries created (0 = the whole prefix chain was already cached).
        """
        self._clock += 1
        self._sweep_stale()
        n = len(tokens)
        if n == 0:
            return 0
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        lengths = sorted({min(self.block * k, n)
                          for k in range(1, n // self.block + 2)} | {n})
        created = 0
        for L in lengths:
            key = hashes[L]
            e = self._entries.get(key)
            if e is not None and e.tokens == toks[:L]:
                # dedup: refresh the existing entry instead of re-slicing
                e.born = e.last_used = self._clock
                continue
            ring = u is not None and v is not None and count > 0
            self._entries[key] = PrefixEntry(
                tokens=toks[:L],
                z=np.ascontiguousarray(np.asarray(z)[:L]),
                u=np.ascontiguousarray(np.asarray(u)[:, :L]) if ring else None,
                v=np.ascontiguousarray(np.asarray(v)[:, :L]) if ring else None,
                count=int(count) if ring else 0,
                born=self._clock, last_used=self._clock,
            )
            created += 1
        self.published += 1
        self._evict_lru()
        self._publish_gauges()
        return created

    def lookup(self, tokens: Sequence[int]) -> PrefixMatch | None:
        """Longest-prefix-match for ``tokens``; leases the entry (its ref
        count protects it from eviction) until ``release`` is called."""
        self._clock += 1
        self._sweep_stale()
        self.lookups += 1
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        present = sorted({e.length for e in self._entries.values()},
                         reverse=True)
        for L in present:
            if L > len(toks):
                continue
            e = self._entries.get(hashes[L])
            if e is not None and e.tokens == toks[:L]:
                e.refs += 1
                e.hits += 1
                e.last_used = self._clock
                self.hits += 1
                return PrefixMatch(entry=e, length=L, exact=L == len(toks))
        return None

    def release(self, match: PrefixMatch | PrefixEntry) -> None:
        """Return a lease taken by ``lookup`` (idempotence NOT provided —
        release exactly once per successful lookup)."""
        e = match.entry if isinstance(match, PrefixMatch) else match
        if e.refs <= 0:
            raise ValueError("release without a matching lookup lease")
        e.refs -= 1
        self._evict_lru()
        self._publish_gauges()

    def evict_poisoned(self, tokens: Sequence[int]) -> int:
        """Drop every cached entry on ``tokens``'s prefix chain — the
        containment response when a solve seeded from this prompt's prefix
        diverged or went non-finite.  Counts under
        ``prefix_cache_evictions_total{reason="poisoned"}``; returns the
        number of entries dropped.  Live leases do not protect an entry:
        the poison verdict outranks in-flight readers (their own guard
        layer contains the fault per sample)."""
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        dropped = 0
        for L in sorted({e.length for e in self._entries.values()}):
            if L > len(toks):
                continue
            e = self._entries.get(hashes[L])
            if e is not None and e.tokens == toks[:L]:
                self._evict(hashes[L], "poisoned")
                dropped += 1
        self._publish_gauges()
        return dropped


# ---------------------------------------------------------------------------
# Device-resident prefix carry store (the zero-host-sync serving cache)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DevEntry:
    """Host bookkeeping for one stored prefix: WHICH device slot holds the
    donor row and how many leading tokens of it this entry covers.  No
    array data lives here — the equilibrium/ring snapshots stay on device."""

    tokens: tuple[int, ...]
    slot: int
    born: int
    last_used: int
    hits: int = 0

    @property
    def length(self) -> int:
        return len(self.tokens)


class DevPrefixMatch(NamedTuple):
    """A device-store lookup result: gather ``store.z[slot, :length]`` (and
    the ring rows) inside the jitted prefill — the host only learns ints."""

    slot: int
    length: int
    exact: bool


class DevicePrefixStore:
    """Cross-request prefix carry cache with DEVICE-RESIDENT entries.

    The host-array :class:`PrefixCarryIndex` round-trips every snapshot
    through ``device_get`` at publish and ``jnp.asarray`` at lookup — one
    blocking host sync per wave each way, serializing dispatch.  This store
    keeps the payload on device the whole time:

      * **Layout** — preallocated slot arrays ``z: (slots+1, S, *F)``,
        ``u/v: (m, slots+1, S, *F)``, ``count: (slots+1,)``.  Row ``slots``
        is a scratch row: publishes the host decides to skip (dedup
        refreshes) scatter there, so the jitted program's shape never
        depends on the publish decision.
      * **Publish** — an on-device scatter (``.at[slots].set``, lowered to
        ``dynamic_update_slice``/scatter) INSIDE the jitted prefill: the
        converged wave carry lands in its assigned rows without ever
        materializing on host.  The host picks target slots *before* the
        call (:meth:`plan_publish` — pure int bookkeeping).
      * **Lookup** — a gather by traced slot id inside the same program.
        Stale tail data past an entry's length is masked by the traced
        ``prefix_len`` (``where(pos < L, ...)``), so one donor row serves
        every block-boundary length at once — device-level dedup.
      * **Ordering** — the slot arrays are threaded VALUES through every
        jitted call (``arrays`` in, updated arrays out, :meth:`adopt`
        back); XLA's data dependencies serialize producer and consumer
        programs, so no leases are needed: a consumer dispatched before an
        overwriting publish reads the old row by construction, and a
        same-program lookup+publish gathers before it scatters.

    Only the rolling-hash / longest-prefix-match / LRU bookkeeping stays on
    host — dict ops over tiny ints, never device memory.  Eviction mirrors
    :class:`PrefixCarryIndex`: LRU over slots when capacity is exceeded,
    ``max_age`` staleness sweeps by the operation clock, per-reason counters
    on ``prefix_cache_evictions_total`` and occupancy gauges.
    """

    def __init__(self, slots: int, seq: int, feat: tuple[int, ...] | int,
                 memory: int, *, block: int = 4, max_age: int | None = None,
                 dtype=jnp.float32, qn_dtype="bfloat16"):
        if slots < 0:
            raise ValueError(f"slots must be >= 0, got {slots}")
        if seq < 1:
            raise ValueError(f"seq must be >= 1, got {seq}")
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if max_age is not None and max_age < 1:
            raise ValueError(f"max_age must be >= 1, got {max_age}")
        feat = (feat,) if isinstance(feat, int) else tuple(feat)
        ring_dtype = jnp.dtype(qn_dtype) if qn_dtype is not None else dtype
        self.slots, self.seq, self.block = slots, seq, block
        self.memory = memory
        self.max_age = max_age
        self.scratch = slots  # the throw-away row
        n = slots + 1
        self.z = jnp.zeros((n, seq) + feat, dtype)
        self.u = jnp.zeros((memory, n, seq) + feat, ring_dtype)
        self.v = jnp.zeros((memory, n, seq) + feat, ring_dtype)
        self.count = jnp.zeros((n,), jnp.int32)
        # host bookkeeping: hash -> entry, per-slot reverse index + LRU clock
        self._entries: dict[int, DevEntry] = {}
        self._slot_keys: list[set[int]] = [set() for _ in range(slots)]
        self._slot_used: list[int] = [0] * slots
        self._free: list[int] = list(range(slots))
        self._clock = 0
        self.published = 0
        self.lookups = 0
        self.hits = 0
        self.evictions_by_reason = {"lru": 0, "stale": 0, "poisoned": 0}

    # -- device side ----------------------------------------------------

    @property
    def arrays(self) -> tuple[Array, Array, Array, Array]:
        """The slot arrays as a flat tuple — thread them through jit."""
        return (self.z, self.u, self.v, self.count)

    def adopt(self, arrays) -> None:
        """Adopt the updated slot arrays a jitted publish returned."""
        self.z, self.u, self.v, self.count = arrays

    # -- bookkeeping ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def tokens_held(self) -> int:
        return sum(e.length for e in self._entries.values())

    def stats(self) -> dict:
        return {"entries": len(self), "tokens": self.tokens_held(),
                "published": self.published, "lookups": self.lookups,
                "hits": self.hits, "evictions": dict(self.evictions_by_reason)}

    def _publish_gauges(self) -> None:
        obs_metrics.record_prefix_occupancy(len(self), self.tokens_held())

    def _drop_key(self, key: int, reason: str) -> None:
        e = self._entries.pop(key)
        self.evictions_by_reason[reason] += 1
        obs_metrics.default_registry().counter(
            "prefix_cache_evictions_total", {"reason": reason}).inc()
        ks = self._slot_keys[e.slot]
        ks.discard(key)
        if not ks:
            self._free.append(e.slot)

    def _sweep_stale(self) -> None:
        if self.max_age is None:
            return
        stale = [k for k, e in self._entries.items()
                 if self._clock - e.born > self.max_age]
        for k in stale:
            self._drop_key(k, "stale")

    def _take_slot(self) -> int:
        """A free device row, evicting the LRU slot's entries if needed."""
        if self._free:
            return self._free.pop()
        victim = min((u, s) for s, u in enumerate(self._slot_used)
                     if self._slot_keys[s])[1]
        for k in list(self._slot_keys[victim]):
            self._drop_key(k, "lru")
        return self._free.pop()

    def _boundaries(self, n: int) -> list[int]:
        return sorted({min(self.block * k, n)
                       for k in range(1, n // self.block + 2)} | {n})

    # -- the cache interface ----------------------------------------------

    def peek(self, tokens: Sequence[int]) -> tuple[int, int] | None:
        """Side-effect-free longest-prefix probe: ``(hash_key, length)`` of
        the longest stored prefix, or None.  Used by admission reordering to
        group requests without perturbing clocks or hit counters."""
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        for L in sorted({e.length for e in self._entries.values()},
                        reverse=True):
            if L > len(toks):
                continue
            e = self._entries.get(hashes[L])
            if e is not None and e.tokens == toks[:L]:
                return hashes[L], L
        return None

    def lookup(self, tokens: Sequence[int]) -> DevPrefixMatch | None:
        """Longest-prefix-match; returns the donor SLOT ID for a traced
        gather.  No lease — program dispatch order protects in-flight
        consumers (see class docstring)."""
        self._clock += 1
        self._sweep_stale()
        self.lookups += 1
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        for L in sorted({e.length for e in self._entries.values()},
                        reverse=True):
            if L > len(toks):
                continue
            e = self._entries.get(hashes[L])
            if e is not None and e.tokens == toks[:L]:
                e.hits += 1
                e.last_used = self._clock
                self._slot_used[e.slot] = self._clock
                self.hits += 1
                return DevPrefixMatch(slot=e.slot, length=L,
                                      exact=L == len(toks))
        return None

    def plan_publish(self, tokens: Sequence[int]) -> int:
        """Pick the device row the wave's jitted prefill will scatter this
        prompt's converged carry into; creates/refreshes the host entries at
        every block boundary.  Returns the scratch row when nothing new
        needs storing (dedup refresh, empty/oversized prompt, no capacity).
        """
        self._clock += 1
        self._sweep_stale()
        n = len(tokens)
        if n == 0 or n > self.seq or self.slots == 0:
            return self.scratch
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        full = self._entries.get(hashes[n])
        if full is not None and full.tokens == toks:
            # dedup: the whole prefix chain is already on device — refresh
            # the host clocks, scatter to scratch (no device write needed)
            for L in self._boundaries(n):
                e = self._entries.get(hashes[L])
                if e is not None and e.tokens == toks[:L]:
                    e.born = e.last_used = self._clock
                    self._slot_used[e.slot] = self._clock
            self.published += 1
            return self.scratch
        slot = self._take_slot()
        self._slot_used[slot] = self._clock
        created = False
        for L in self._boundaries(n):
            key = hashes[L]
            e = self._entries.get(key)
            if e is not None and e.tokens == toks[:L]:
                e.born = e.last_used = self._clock
                continue
            if e is not None:
                # hash collision with different tokens: replace
                self._drop_key(key, "lru")
            self._entries[key] = DevEntry(tokens=toks[:L], slot=slot,
                                          born=self._clock,
                                          last_used=self._clock)
            self._slot_keys[slot].add(key)
            created = True
        if not created:
            # every boundary was already covered by other donors
            self._free.append(slot)
            slot = self.scratch
        self.published += 1
        self._publish_gauges()
        return slot

    def evict_poisoned(self, tokens: Sequence[int]) -> int:
        """Drop every host entry on ``tokens``'s prefix chain (the device
        rows become unreachable and are recycled through ``_take_slot``).
        Containment response to a solve that diverged after seeding from
        this prefix; counts under
        ``prefix_cache_evictions_total{reason="poisoned"}``."""
        toks = tuple(int(t) for t in tokens)
        hashes = prefix_hashes(toks)
        dropped = 0
        for L in sorted({e.length for e in self._entries.values()}):
            if L > len(toks):
                continue
            e = self._entries.get(hashes[L])
            if e is not None and e.tokens == toks[:L]:
                self._drop_key(hashes[L], "poisoned")
                dropped += 1
        self._publish_gauges()
        return dropped


def prefix_store_scatter(arrays, carry: SolveCarry, slot_ids: Array):
    """On-device publish-back: scatter a converged prefill wave's carry rows
    into the store's slot arrays (one ``.at[].set`` per buffer — lowered to
    a scatter/dynamic_update_slice inside the jitted prefill program).
    ``slot_ids: (B,) int32`` may point rows at the scratch slot to skip
    publication without changing the program shape."""
    z_s, u_s, v_s, c_s = arrays
    seq = carry.z.shape[1]
    lr = carry.lowrank
    return (
        z_s.at[slot_ids, :seq].set(carry.z.astype(z_s.dtype)),
        u_s.at[:, slot_ids, :seq].set(lr.u.astype(u_s.dtype)),
        v_s.at[:, slot_ids, :seq].set(lr.v.astype(v_s.dtype)),
        c_s.at[slot_ids].set(lr.count.astype(c_s.dtype)),
    )
