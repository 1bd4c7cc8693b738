"""Pluggable aggregation pipeline: every aggregate is an :class:`Aggregator`.

The result path of FlashQL used to special-case ``Agg.COUNT`` / ``Agg.MASK``
by hand in both schedulers; this module replaces those ladders with one
interface.  An :class:`Aggregator` declares

* **extra sensed planes** (:meth:`Aggregator.extra_pages`) — the BSI slices
  and/or equality bitmaps of its target column, fetched through
  :func:`repro.query.bitmap.fetch_pages`;
* **a batched device-side reduce** (:meth:`Aggregator.batch_reduce`) — one
  jit'd (weighted-)popcount over the stacked result bitmaps of a flush:
  ``SUM = Σ_b 2^b · popcount(mask ∧ slice_b)`` (Pinatubo/DrAcc-style
  bit-slice arithmetic), ``MIN``/``MAX`` walk slices MSB→LSB narrowing a
  candidate mask, ``AVG = SUM / COUNT``, and ``TOP-K`` / ``GROUP BY``
  reduce per-group masks from the equality bitmaps;
* **a shard-merge rule** (:meth:`Aggregator.merge`) — sum partials, take
  the min/max, merge count vectors, or un-stripe bitmaps — so
  ``ShardedFlashQL`` gathers any aggregate without per-kind branches.

``COUNT`` and ``MASK`` are trivial instances of the same interface.
:func:`reduce_flush` is the shared driver both schedulers call: it groups a
flush's members by *reduce signature* (aggregator kind + static shapes), so
a flush mixing every aggregate kind still costs O(distinct signatures)
kernel dispatches and ONE host transfer per group.

Exact-integer guarantee: device kernels only ever produce popcounts
(int32); the 2^b weighting happens host-side in Python integers, so SUM
and the AVG numerator are exact at any bit width.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitops import BitVector
from repro.kernels.popcount import popcount
from repro.query.ast import (
    AggSpec,
    Avg,
    Count,
    GroupBy,
    Mask,
    Max,
    Min,
    Query,
    Sum,
    TopK,
    columns_of,
    normalize_agg,
)
from repro.query.bitmap import BitmapStore, bsi_pages, eq_pages, fetch_pages

# -- jitted batched reduces --------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def sliced_counts(
    masks: jax.Array, extras: jax.Array, *, interpret: bool | None
) -> jax.Array:
    """``(B, P)`` popcounts of ``mask ∧ page`` for every member × page.

    The weighted-popcount workhorse: one fused intersect + ONE batched
    popcount dispatch covers every (member, slice) pair of a flush group.
    """
    b, p, w = extras.shape
    inter = masks[:, None, :] & extras
    return popcount(inter.reshape(b * p, w), interpret=interpret).reshape(
        b, p
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def sliced_counts_with_total(
    masks: jax.Array, extras: jax.Array, *, interpret: bool | None
) -> jax.Array:
    """``(B, P+1)``: per-slice popcounts plus the plain mask popcount in
    the last column (AVG's numerator slices + denominator, one dispatch)."""
    aug = jnp.concatenate([extras, masks[:, None, :]], axis=1)
    return sliced_counts(masks, aug, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("maximize",))
def bsi_extreme(
    masks: jax.Array, extras: jax.Array, *, maximize: bool
) -> tuple[jax.Array, jax.Array]:
    """Bit-sliced MIN/MAX walk over ``(B, bits, W)`` BSI slices.

    MSB→LSB, a candidate mask narrows to the rows still extremal: for MAX,
    if any candidate has bit b set, the extremum does too and candidates
    without it drop out; MIN walks the complemented slice.  Returns the
    per-bit decisions ``(B, bits)`` (LSB first) and a per-member non-empty
    flag — the host assembles the exact integer, so any bit width works.
    """
    bits = extras.shape[1]
    cand = masks
    decisions = []
    for b in range(bits - 1, -1, -1):
        sl = extras[:, b, :]
        # cand has no padding bits (masks are validity-masked), so the
        # complement's padding ones never enter the candidate set
        t = cand & (sl if maximize else ~sl)
        nz = (t != 0).any(axis=-1)
        cand = jnp.where(nz[:, None], t, cand)
        decisions.append(nz if maximize else ~nz)
    dec = jnp.stack(decisions[::-1], axis=1)
    return dec, (masks != 0).any(axis=-1)


@functools.partial(jax.jit, static_argnames=("groups", "bits", "interpret"))
def pervalue_counts(
    masks: jax.Array,
    extras: jax.Array,
    *,
    groups: int,
    bits: int,
    interpret: bool | None,
):
    """Per-group popcounts for TOP-K / GROUP BY.

    ``extras`` stacks ``groups`` equality bitmaps then ``bits`` BSI slices
    of the inner-aggregate column (``bits == 0`` for plain counts).  Group
    counts and per-(group, slice) counts run as ONE batched popcount.
    """
    b, _, w = extras.shape
    gm = masks[:, None, :] & extras[:, :groups, :]  # (B, G, W)
    if not bits:
        return popcount(
            gm.reshape(b * groups, w), interpret=interpret
        ).reshape(b, groups)
    sl = extras[:, groups:, :]
    inter = gm[:, :, None, :] & sl[:, None, :, :]  # (B, G, bits, W)
    rows = jnp.concatenate(
        [gm.reshape(b * groups, w), inter.reshape(b * groups * bits, w)]
    )
    counts = popcount(rows, interpret=interpret)
    return (
        counts[: b * groups].reshape(b, groups),
        counts[b * groups :].reshape(b, groups, bits),
    )


def _weighted(counts: Iterable) -> int:
    """Exact Σ 2^b · count_b in Python integers (LSB first)."""
    return sum(int(c) << b for b, c in enumerate(counts))


# -- the interface -----------------------------------------------------------


@dataclass(frozen=True)
class Aggregator:
    """One aggregate's execution semantics (see module docstring).

    Stateless and cached per spec (:func:`get_aggregator`); everything a
    flush needs is parameterized on the :class:`BitmapStore` whose pages
    the member's predicate was evaluated against.
    """

    spec: AggSpec
    kind = "abstract"
    # does the SSD projection model host-side postprocessing (popcounts /
    # arithmetic) for this aggregate, or does the bitmap stream out raw?
    host_postprocess = True

    # -- admission-time validation
    def validate(self, columns: Mapping[str, object]) -> None:
        """Raise before a bad query can enter a flush (queue poisoning)."""

    # -- extra sensed planes
    def extra_pages(self, store: BitmapStore) -> tuple[str, ...]:
        return ()

    def reduce_sig(self, store: BitmapStore) -> tuple:
        """Static part of the batched reduce: members of one flush with
        equal ``(kind,) + reduce_sig`` reduce together in one dispatch."""
        return ()

    # -- batched device-side reduce
    def batch_reduce(self, masks, extras, sig: tuple, *, interpret: bool | None):
        """Reduce ``(B, W)`` result bitmaps (+ ``(B, P, W)`` extra planes)
        to per-member device values; one jit'd dispatch per group.  ``sig``
        is the group's :meth:`reduce_sig` (static shape info).

        Implementations depend only on ``(masks, extras, sig, interpret)``
        — never on ``self.spec`` — so the fused flush program can dispatch
        a reduce from ``(kind, sig)`` alone (:func:`kind_reduce`).
        """
        raise NotImplementedError

    def payload_leaves(
        self, sig: tuple, b: int, w: int
    ) -> tuple[tuple[tuple[int, ...], object], ...]:
        """``(shape, dtype)`` of every :meth:`batch_reduce` output leaf for
        a ``b``-member group over ``w``-word bitmaps — the static layout of
        this group's slice of a fused flush's single ``uint32`` payload
        (:func:`unpack_group` re-assembles the host structure from it)."""
        raise NotImplementedError

    def member_partial(self, host, j: int):
        """Slice member ``j``'s partial out of the host-transferred reduce
        output (the per-shard unit :meth:`merge` combines)."""
        raise NotImplementedError

    def empty_partial(self, store: BitmapStore):
        """The partial of a shard whose stripe provably cannot match (range
        routing prunes it before scatter, no sensing at all)."""
        raise NotImplementedError

    # -- gather
    def finalize(self, partial, store: BitmapStore):
        """Partial -> final value on a single (unsharded) store."""
        raise NotImplementedError

    def merge(self, parts: dict[int, object], sstore) -> object:
        """Shard partials -> final value (``sstore``: ShardedBitmapStore)."""
        raise NotImplementedError

    # -- helpers
    def _column(self) -> str:
        return self.spec.column

    def _require(self, columns: Mapping[str, object], name: str) -> None:
        if name not in columns:
            raise KeyError(
                f"unknown aggregate column {name!r} for {self.kind.upper()}"
            )

    def _first_store(self, parts: dict[int, object], sstore) -> BitmapStore:
        return sstore.shards[next(iter(parts))]


class CountAggregator(Aggregator):
    kind = "count"

    def batch_reduce(self, masks, extras, sig, *, interpret):
        return popcount(masks, interpret=interpret)

    def payload_leaves(self, sig, b, w):
        return (((b,), np.int64),)

    def member_partial(self, host, j):
        return int(host[j])

    def empty_partial(self, store):
        return 0

    def finalize(self, partial, store):
        return partial

    def merge(self, parts, sstore):
        return sum(parts.values())


class MaskAggregator(Aggregator):
    kind = "mask"
    host_postprocess = False  # the bitmap streams out as-is

    def batch_reduce(self, masks, extras, sig, *, interpret):
        return masks

    def payload_leaves(self, sig, b, w):
        return (((b, w), np.uint32),)

    def member_partial(self, host, j):
        return host[j]  # (W,) uint32 words

    def empty_partial(self, store):
        return np.zeros((store.words,), np.uint32)

    def finalize(self, partial, store):
        # keep the host words as-is: BitVector's jnp ops auto-convert on
        # use, so no eager host->device re-upload on the serving path
        return BitVector(partial, store.num_rows)

    def merge(self, parts, sstore):
        # un-stripe per-shard bitmaps back into global row order — pure
        # numpy: partials arrive host-side (payload words), and the jnp
        # unpack/pack round-trip cost ~a dispatch per shard per MASK here
        bits = np.zeros((sstore.num_rows,), dtype=np.uint8)
        for s, words in parts.items():
            n_s = sstore.shards[s].num_rows
            w = np.ascontiguousarray(np.asarray(words))
            bits[sstore.row_maps[s]] = np.unpackbits(
                w.view(np.uint8), bitorder="little"
            )[:n_s]
        span = np.zeros((len(bits) + 31) // 32 * 32, dtype=np.uint8)
        span[: len(bits)] = bits
        packed = np.packbits(span, bitorder="little").view(np.uint32)
        return BitVector(packed, sstore.num_rows)


def merge_mask_batch(parts_list, sstore) -> list:
    """Un-stripe a whole flush's MASK tickets in one fused numpy pass.

    ``parts_list`` holds each MASK ticket's shard partials (the dicts
    :meth:`MaskAggregator.merge` takes).  The per-ticket merge pays an
    unpack/scatter pass per (ticket x shard) plus a packbits per ticket;
    here every shard's words stack across tickets first, so the flush
    costs ONE unpackbits + scatter per shard and ONE packbits total —
    the dominant host-side cost of MASK-heavy sharded flushes.
    Returns one :class:`BitVector` per ticket, in ``parts_list`` order.
    """
    t_count = len(parts_list)
    bits = np.zeros((t_count, sstore.num_rows), dtype=np.uint8)
    shards = sorted({s for parts in parts_list for s in parts})
    for s in shards:
        n_s = sstore.shards[s].num_rows
        rows = [t for t in range(t_count) if s in parts_list[t]]
        words = np.ascontiguousarray(
            np.stack([np.asarray(parts_list[t][s]) for t in rows])
        )
        unpacked = np.unpackbits(
            words.view(np.uint8), axis=1, bitorder="little"
        )[:, :n_s]
        bits[
            np.asarray(rows, np.intp)[:, None],
            sstore.row_maps[s][None, :],
        ] = unpacked
    pad = (sstore.num_rows + 31) // 32 * 32
    span = np.zeros((t_count, pad), dtype=np.uint8)
    span[:, : sstore.num_rows] = bits
    packed = np.packbits(span, axis=1, bitorder="little").view(np.uint32)
    return [BitVector(packed[t], sstore.num_rows) for t in range(t_count)]


class SumAggregator(Aggregator):
    kind = "sum"

    def validate(self, columns):
        self._require(columns, self._column())

    def extra_pages(self, store):
        return bsi_pages(store, self._column())

    def reduce_sig(self, store):
        return (store.columns[self._column()].bits,)

    def batch_reduce(self, masks, extras, sig, *, interpret):
        return sliced_counts(masks, extras, interpret=interpret)

    def payload_leaves(self, sig, b, w):
        return (((b, sig[0]), np.int64),)

    def member_partial(self, host, j):
        return host[j]  # (bits,) per-slice popcounts

    def empty_partial(self, store):
        return np.zeros((store.columns[self._column()].bits,), np.int64)

    def finalize(self, partial, store):
        return _weighted(partial)

    def merge(self, parts, sstore):
        return sum(_weighted(p) for p in parts.values())


class AvgAggregator(SumAggregator):
    kind = "avg"

    def batch_reduce(self, masks, extras, sig, *, interpret):
        return sliced_counts_with_total(masks, extras, interpret=interpret)

    def payload_leaves(self, sig, b, w):
        return (((b, sig[0] + 1), np.int64),)

    def member_partial(self, host, j):
        return host[j]  # (bits + 1,): slice popcounts + row count

    def empty_partial(self, store):
        return np.zeros(
            (store.columns[self._column()].bits + 1,), np.int64
        )

    def finalize(self, partial, store):
        count = int(partial[-1])
        if not count:
            return None
        return _weighted(partial[:-1]) / count

    def merge(self, parts, sstore):
        total = np.sum(
            np.stack([np.asarray(p) for p in parts.values()]),
            axis=0,
            dtype=np.int64,
        )
        return self.finalize(total, self._first_store(parts, sstore))


class ExtremeAggregator(Aggregator):
    """Shared MIN/MAX implementation (the walk differs by one flag)."""

    maximize = False

    def validate(self, columns):
        self._require(columns, self._column())

    def extra_pages(self, store):
        return bsi_pages(store, self._column())

    def reduce_sig(self, store):
        return (store.columns[self._column()].bits, self.maximize)

    def batch_reduce(self, masks, extras, sig, *, interpret):
        # maximize comes from sig (not self.spec) so the fused flush
        # program can run this reduce from the group key alone
        return bsi_extreme(masks, extras, maximize=sig[1])

    def payload_leaves(self, sig, b, w):
        return (((b, sig[0]), np.bool_), ((b,), np.bool_))

    def member_partial(self, host, j):
        dec, nonempty = host
        return (np.asarray(dec[j]), bool(nonempty[j]))

    def empty_partial(self, store):
        bits = store.columns[self._column()].bits
        return (np.zeros((bits,), bool), False)

    def finalize(self, partial, store):
        dec, nonempty = partial
        if not nonempty:
            return None
        return _weighted(dec)

    def merge(self, parts, sstore):
        store = self._first_store(parts, sstore)
        vals = [
            v
            for v in (self.finalize(p, store) for p in parts.values())
            if v is not None
        ]
        if not vals:
            return None
        return max(vals) if self.maximize else min(vals)


class MinAggregator(ExtremeAggregator):
    kind = "min"
    maximize = False


class MaxAggregator(ExtremeAggregator):
    kind = "max"
    maximize = True


class PerValueAggregator(Aggregator):
    """Shared TOP-K / GROUP BY machinery: per-group masks from the target
    column's equality bitmaps, reduced to per-group (count[, slice-count])
    vectors that merge across shards by elementwise sum — the global
    schema aligns value order on every shard."""

    def _key_column(self) -> str:
        raise NotImplementedError

    def _inner_bits_column(self) -> str | None:
        return None  # BSI slices of the inner SUM/AVG column, if any

    def extra_pages(self, store):
        pages = eq_pages(store, self._key_column())
        inner = self._inner_bits_column()
        if inner is not None:
            pages += bsi_pages(store, inner)
        return pages

    def reduce_sig(self, store):
        groups = len(store.columns[self._key_column()].values)
        inner = self._inner_bits_column()
        bits = store.columns[inner].bits if inner is not None else 0
        return (groups, bits)

    def batch_reduce(self, masks, extras, sig, *, interpret):
        groups, bits = sig
        return pervalue_counts(
            masks, extras, groups=groups, bits=bits, interpret=interpret
        )

    def payload_leaves(self, sig, b, w):
        groups, bits = sig
        if not bits:
            return (((b, groups), np.int64),)
        return (((b, groups), np.int64), ((b, groups, bits), np.int64))

    def member_partial(self, host, j):
        if isinstance(host, tuple):
            return (host[0][j], host[1][j])
        return host[j]

    def empty_partial(self, store):
        groups = len(store.columns[self._key_column()].values)
        inner = self._inner_bits_column()
        if inner is None:
            return np.zeros((groups,), np.int64)
        bits = store.columns[inner].bits
        return (
            np.zeros((groups,), np.int64),
            np.zeros((groups, bits), np.int64),
        )

    def merge(self, parts, sstore):
        vals = list(parts.values())
        if isinstance(vals[0], tuple):
            total = tuple(
                np.sum(
                    np.stack([np.asarray(v[i]) for v in vals]),
                    axis=0,
                    dtype=np.int64,
                )
                for i in range(2)
            )
        else:
            total = np.sum(
                np.stack([np.asarray(v) for v in vals]),
                axis=0,
                dtype=np.int64,
            )
        return self.finalize(total, self._first_store(parts, sstore))


class TopKAggregator(PerValueAggregator):
    kind = "topk"

    def validate(self, columns):
        self._require(columns, self.spec.column)
        if self.spec.k < 1:
            raise ValueError(f"TopK needs k >= 1, got {self.spec.k}")

    def _key_column(self):
        return self.spec.column

    def finalize(self, partial, store):
        values = store.columns[self.spec.column].values
        ranked = sorted(
            ((v, int(c)) for v, c in zip(values, partial)),
            key=lambda vc: (-vc[1], vc[0]),
        )
        return tuple((v, c) for v, c in ranked if c > 0)[: self.spec.k]


class GroupByAggregator(PerValueAggregator):
    kind = "groupby"

    def validate(self, columns):
        self._require(columns, self.spec.key)
        inner = self.spec.value
        if not isinstance(inner, (Count, Sum, Avg)):
            raise TypeError(
                f"GroupBy value must be Count/Sum/Avg, got {inner!r}"
            )
        if isinstance(inner, (Sum, Avg)):
            self._require(columns, inner.column)

    def _key_column(self):
        return self.spec.key

    def _inner_bits_column(self):
        inner = self.spec.value
        return inner.column if isinstance(inner, (Sum, Avg)) else None

    def finalize(self, partial, store):
        values = store.columns[self.spec.key].values
        inner = self.spec.value
        if isinstance(inner, Count):
            return {
                v: int(c) for v, c in zip(values, partial) if int(c) > 0
            }
        counts, slices = partial
        out = {}
        for g, v in enumerate(values):
            c = int(counts[g])
            if not c:
                continue
            num = _weighted(slices[g])
            out[v] = num / c if isinstance(inner, Avg) else num
        return out


_AGGREGATORS: dict[type, type[Aggregator]] = {
    Count: CountAggregator,
    Mask: MaskAggregator,
    Sum: SumAggregator,
    Avg: AvgAggregator,
    Min: MinAggregator,
    Max: MaxAggregator,
    TopK: TopKAggregator,
    GroupBy: GroupByAggregator,
}

# spec-less instances for kind-keyed dispatch: batch_reduce and
# payload_leaves are functions of (kind, sig) only, which is what lets a
# fused flush program be compiled from its static flush signature
_BY_KIND: dict[str, Aggregator] = {
    cls.kind: cls(spec=None) for cls in _AGGREGATORS.values()
}


def kind_reduce(kind: str, masks, extras, sig: tuple, *, interpret: bool | None):
    """Run one reduce group's device computation from its static group key
    — the traced body :func:`repro.query.device.make_flush_runner` inlines
    per reduce group of a fused flush program."""
    return _BY_KIND[kind].batch_reduce(
        masks, extras, sig, interpret=interpret
    )


def payload_spec(
    kind: str, sig: tuple, b: int, w: int
) -> tuple[tuple[tuple[int, ...], object], ...]:
    """Static ``(shape, dtype)`` leaves of one group's payload slice."""
    return _BY_KIND[kind].payload_leaves(sig, b, w)


def payload_size(leaves) -> int:
    """Flat ``uint32`` words one group contributes to the fused payload."""
    return sum(int(np.prod(shape)) for shape, _ in leaves)


def unpack_group(flat: np.ndarray, leaves):
    """Re-assemble one reduce group's host structure from its payload slice.

    Inverse of the fused runner's ``ravel().astype(uint32)`` flattening:
    counts come back as exact ``int64`` (device popcounts are int32, so the
    uint32 round-trip is lossless), MASK words stay ``uint32``, and the
    MIN/MAX decision/non-empty flags come back as booleans.  Returns the
    same structure ``jax.device_get(batch_reduce(...))`` would have — a
    single array or a tuple — so :meth:`Aggregator.member_partial` applies
    unchanged.
    """
    out = []
    off = 0
    for shape, dtype in leaves:
        n = int(np.prod(shape))
        out.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return out[0] if len(out) == 1 else tuple(out)


@functools.lru_cache(maxsize=1024)
def get_aggregator(agg) -> Aggregator:
    """Aggregator for a spec (or legacy ``Agg`` enum member); cached."""
    spec = normalize_agg(agg)
    cls = _AGGREGATORS.get(type(spec))
    if cls is None:
        raise TypeError(f"no aggregator registered for {spec!r}")
    return cls(spec)


def validate_query(query: Query, columns: Mapping[str, object]) -> Aggregator:
    """Admission-time validation shared by both schedulers.

    Checks every predicate column and the aggregate's target columns
    against ``columns`` (any mapping keyed on column name) so a bad query
    raises at ``submit()`` — never mid-flush, where a sharded deployment
    would have already popped some shard queues (a poisoned ticket).
    Returns the query's aggregator.
    """
    for col in columns_of(query.where):
        if col not in columns:
            raise KeyError(f"unknown column {col!r}")
    agg = get_aggregator(query.agg)
    agg.validate(columns)
    return agg


# -- the shared flush driver -------------------------------------------------


def _cached_pages(
    agg: Aggregator, store: BitmapStore, store_key, cache: dict, cap: int
) -> tuple[str, ...]:
    """Memoized :meth:`Aggregator.extra_pages`: TopK/GroupBy page tuples
    are O(column cardinality) f-strings, too hot to rebuild per flush."""
    pkey = ("pages", agg.spec, store_key)
    pages = cache.get(pkey)
    if pages is None:
        _evict_one(cache, cap)
        pages = agg.extra_pages(store)
        cache[pkey] = pages
    return pages


def _evict_one(cache: dict, cap: int) -> None:
    """Bound the shared extras cache by evicting the oldest entry —
    wholesale clears would dump every namespace (page tuples, per-member
    stacks, group stacks) mid-flush and force a re-fetch cliff."""
    if len(cache) >= cap:
        cache.pop(next(iter(cache)))


def group_members(
    specs: list, stores: list[BitmapStore]
) -> tuple[list[Aggregator], dict[tuple, list[int]]]:
    """Group a flush's members by reduce signature ``(kind,) + reduce_sig``.

    The shared first step of both reduce drivers: the per-group transfer
    path (:func:`reduce_flush`) and the single-payload fused flush program
    (:func:`repro.query.compile.compile_flush`).
    """
    aggs = [get_aggregator(sp) for sp in specs]
    groups: dict[tuple, list[int]] = {}
    for i, a in enumerate(aggs):
        groups.setdefault((a.kind,) + a.reduce_sig(stores[i]), []).append(i)
    return aggs, groups


def group_extras(
    aggs: list[Aggregator],
    members: list[int],
    stores: list[BitmapStore],
    store_keys: list,
    extras_cache: dict,
    cache_cap: int,
):
    """Stacked ``(B_g, P, W)`` extra sensed planes of one reduce group.

    Returns ``(extras, counts, uploaded)`` where ``extras`` is the device
    stack (or None when the group's aggregate senses no extra planes),
    ``counts`` maps member index -> planes sensed (the caller's
    projected-traffic accounting) and ``uploaded`` is the bytes of host
    pages this call copied to the device.  The group stack is memoized per
    member composition: recurring flush compositions — steady-state
    serving — skip the per-member fetches AND the device concat.
    """
    member_pages = [
        _cached_pages(aggs[i], stores[i], store_keys[i], extras_cache, cache_cap)
        for i in members
    ]
    counts: dict[int, int] = {}
    uploaded = 0
    if not member_pages[0]:
        return None, counts, uploaded
    cks = []
    for i, pages in zip(members, member_pages):
        counts[i] = len(pages)
        cks.append((store_keys[i], pages))
    gk = ("stack",) + tuple(cks)
    extras = extras_cache.get(gk)
    if extras is None:
        stacks = []
        for i, ck in zip(members, cks):
            stack = extras_cache.get(ck)
            if stack is None:
                _evict_one(extras_cache, cache_cap)
                stack = fetch_pages(stores[i], ck[1])
                uploaded += stack.nbytes
                extras_cache[ck] = stack
            stacks.append(stack)
        extras = jnp.stack(stacks)  # (B_g, P, W)
        _evict_one(extras_cache, cache_cap)
        extras_cache[gk] = extras
    return extras, counts, uploaded


def reduce_flush(
    masked: jax.Array,
    specs: list,
    stores: list[BitmapStore],
    store_keys: list,
    *,
    interpret: bool | None,
    extras_cache: dict,
    cache_cap: int = 128,
) -> tuple[list, list[int], int]:
    """Batched aggregation of one flush (per-group transfer path).

    Returns ``(partials, extra_counts, n_groups)``: the per-member
    partials, how many extra planes each member sensed (for the caller's
    projected-traffic accounting), and the number of reduce groups — i.e.
    device->host transfers — the flush cost.  The fused flush program
    (:func:`repro.query.compile.compile_flush`) replaces this driver on
    the hot path with ONE transfer for the whole flush; this per-group
    path remains for devices holding non-ESP pages (whose reads may
    inject errors) and as the lockstep oracle.

    ``masked``: the flush's ``(B, W)`` validity-masked result bitmaps in
    member order; ``stores[i]`` / ``store_keys[i]``: the store member ``i``'s
    pages live in and a hashable identity for it (shard id + ingest epoch)
    under which page tuples and stacked extra planes are memoized in
    ``extras_cache``.

    Members group by ``(kind,) + reduce_sig``: each group runs ONE jit'd
    batched reduce and ONE device->host transfer regardless of group size,
    so a flush mixing every aggregate kind stays O(distinct kinds) extra
    dispatches on top of the predicate execution.  MASK groups transfer
    too — deliberately: results are consumed host-side (un-striping,
    ``to_bits``, numpy asserts), and one batched copy beats the per-row
    lazy transfers (and per-row ``__getitem__`` dispatches) the
    pre-pipeline path paid at consumption time.
    """
    n = len(specs)
    aggs, groups = group_members(specs, stores)
    partials: list = [None] * n
    extra_counts: list[int] = [0] * n
    for group_key, members in groups.items():
        a0 = aggs[members[0]]
        sig = group_key[1:]
        sub = (
            masked
            if len(members) == n
            else masked[jnp.asarray(np.asarray(members, np.int32))]
        )
        extras, counts, _ = group_extras(
            aggs, members, stores, store_keys, extras_cache, cache_cap
        )
        for i, c in counts.items():
            extra_counts[i] = c
        host = jax.device_get(
            a0.batch_reduce(sub, extras, sig, interpret=interpret)
        )
        for j, i in enumerate(members):
            partials[i] = aggs[i].member_partial(host, j)
    return partials, extra_counts, len(groups)
