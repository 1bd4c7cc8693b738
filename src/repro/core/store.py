"""Packed page store: the contiguous array behind the Flash-Cosmos engine.

The seed engine kept page data in a ``dict[str, Array]`` and sensed with a
Python loop over pages.  :class:`PackedStore` packs every programmed page
into one contiguous ``(planes, slots, words_per_plane)`` buffer — the layout
analogue of a multi-plane NAND die where a logical bit vector is striped
across planes and every plane holds the same (block, wordline) grid.  An
MWS command then becomes one fused kernel dispatch over the whole word axis
(= all planes at once) that reads its operand slots in place, instead of
one Python-level reduce per page.

Slot 0 is reserved for an all-ones row: the AND identity used to pad the
ragged per-block wordline sets of an inter-block MWS to a rectangle, so a
whole command batch reduces in a single Pallas call.  Slot 1 is the dual
all-zeros row: a block whose first wordline gathers slot 1 ANDs to zero and
is therefore OR-neutral across blocks — plan-aware batching uses it to pad
a plan with fewer target blocks into a wider signature's shape.

Writes append to a host-side ``numpy`` buffer (amortized doubling); the
device-side ``jax`` snapshot is materialized lazily and invalidated on
write, so steady-state query serving reads one cached array.  Each slot
row is padded to whole sensing tiles and the snapshot is shaped
``(slots, rows, 128)``: on a TPU every page is then one contiguous run of
``(8, 128)`` tiles, which the in-place sensing kernel reads by slot.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.kernels.mws.mws import sense_tiles

IDENTITY_SLOT = 0  # all-ones row (AND identity / pad row), always present
ZERO_SLOT = 1  # all-zeros row (OR identity: pads whole blocks), always present

_ONES = np.uint32(0xFFFFFFFF)
_SCRATCH_PREFIX = "__scratch"


def page_region(name: str) -> str | None:
    """Epoch region of a page name, or ``None`` for planner scratch pages.

    The naming convention shared with :mod:`repro.query.bitmap`: equality
    bitmaps are ``column=value`` and BSI slices ``column#bit``, so the
    prefix before the first ``=`` / ``#`` groups every page of one column
    into one region; constant pages (``__all`` / ``__none``) are their own
    single-page regions.  Plan caches invalidate per region, so
    reprogramming one column's pages leaves plans over other columns warm.
    """
    if name.startswith(_SCRATCH_PREFIX):
        return None
    return name.split("=", 1)[0].split("#", 1)[0]


@dataclass
class PackedStore:
    """Name-addressed packed page store striped over ``planes`` planes.

    All pages share one word count ``W`` (fixed by the first write); each
    page occupies one *slot* of ``planes * words_per_plane`` words, where
    ``words_per_plane = ceil(W / planes)`` (tail padding is sliced off on
    read).  A slot row holds :attr:`page_rows` ``* 128`` words: the planes
    padded on to whole sensing tiles.
    """

    planes: int = 1
    # the accelerator the device snapshot lives on; None leaves it to
    # JAX's default device (a pipelined fleet puts stripe s on device s)
    device: jax.Device | None = None
    _slots: dict[str, int] = field(default_factory=dict)
    _buf: np.ndarray | None = None  # (capacity, planes * wpp) uint32
    _n: int = 0
    _words: int | None = None  # logical words per page (pre-padding)
    _snapshot: jax.Array | None = None
    # Content version: bumped whenever page *content* changes (new page,
    # reprogram, or delta append), except planner scratch pages — those are
    # plan-internal temporaries rewritten on every execution of a spilling
    # plan.  Snapshot-level caches (stacked fleet arrays, aggregate extras)
    # key on this.
    epoch: int = 0
    # Region-granular mutation epochs (see :func:`page_region`): bumped on
    # a full (re)program of a page in the region, but NOT by
    # :meth:`append_words` — an append extends a page's erased tail, so
    # compiled plans (which gather by slot) remain valid.  Plan caches key
    # on the regions their leaves touch, so reprogramming column A's pages
    # recompiles only plans that sense column A.
    region_epochs: dict[str, int] = field(default_factory=dict)
    # Device-upload instrumentation: how many times the packed buffer was
    # re-materialized as a device array.  Steady-state serving must hold
    # this flat — in particular, spilling plans keep their scratch values
    # device-resident (latch scratch, never store writes), so a flush full
    # of deep-range queries re-uploads nothing (asserted in tests).
    snapshot_uploads: int = 0

    # -- geometry ----------------------------------------------------------
    @property
    def num_words(self) -> int | None:
        """Logical words per page (None until the first write)."""
        return self._words

    @property
    def padded_words(self) -> int:
        assert self._words is not None
        return -(-self._words // self.planes) * self.planes

    @property
    def words_per_plane(self) -> int:
        return self.padded_words // self.planes

    @property
    def page_rows(self) -> int:
        """Rows of 128 words per slot in the snapshot (whole sensing tiles)."""
        tile, tiles = sense_tiles(self.padded_words)
        return tile * tiles

    @property
    def num_slots(self) -> int:
        return self._n

    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, name: str) -> bool:
        return name in self._slots

    # -- writes ------------------------------------------------------------
    def _ensure_buf(self, words: int) -> None:
        if self._buf is not None:
            return
        self._words = words
        self._buf = np.empty((16, self.page_rows * 128), dtype=np.uint32)
        self._buf[IDENTITY_SLOT] = _ONES  # AND identity row
        self._buf[ZERO_SLOT] = 0  # OR identity row (block padding)
        self._n = 2

    def __setitem__(self, name: str, words) -> None:
        w = np.asarray(words, dtype=np.uint32).reshape(-1)
        self._ensure_buf(w.shape[0])
        if w.shape[0] != self._words:
            raise ValueError(
                f"page {name!r} has {w.shape[0]} words, store fixed at "
                f"{self._words}"
            )
        row = np.zeros((self._buf.shape[1],), dtype=np.uint32)
        row[: self._words] = w
        slot = self._slots.get(name)
        if slot is None:
            if self._n == self._buf.shape[0]:
                grown = np.empty(
                    (2 * self._buf.shape[0], self._buf.shape[1]),
                    dtype=np.uint32,
                )
                grown[: self._n] = self._buf[: self._n]
                self._buf = grown
            slot = self._n
            self._n += 1
            self._slots[name] = slot
        self._buf[slot] = row
        self._snapshot = None
        region = page_region(name)
        if region is not None:
            self.epoch += 1
            self.region_epochs[region] = self.region_epochs.get(region, 0) + 1

    def append_words(self, name: str, words, start: int) -> None:
        """Delta-page programming: overwrite only ``words`` at ``start``.

        The incremental-ingest write path.  The caller guarantees the
        written range covers only the page's tail beyond previously-valid
        rows (an *append*), so compiled plans — which gather by slot —
        remain valid: the page's region epoch is left alone and only the
        content ``epoch`` is bumped (snapshot-level caches must refresh).
        """
        w = np.asarray(words, dtype=np.uint32).reshape(-1)
        slot = self._slots[name]
        assert self._words is not None
        if start < 0 or start + w.shape[0] > self._words:
            raise ValueError(
                f"delta [{start}, {start + w.shape[0]}) out of range for "
                f"page {name!r} with {self._words} words"
            )
        self._buf[slot, start : start + w.shape[0]] = w
        self._snapshot = None
        if page_region(name) is not None:
            self.epoch += 1

    # -- reads -------------------------------------------------------------
    def slot(self, name: str) -> int:
        return self._slots[name]

    def __getitem__(self, name: str) -> jax.Array:
        slot = self._slots[name]
        return jax.device_put(self._buf[slot, : self._words], self.device)

    def snapshot(self) -> jax.Array:
        """Device-side ``(slots, page_rows, 128)`` packed array.

        Cached until the next write; one sensing over this array covers
        every plane in one kernel dispatch because planes are word-axis
        shards of each slot row.  Row-major, so the host buffer reshapes
        for free and is uploaded as it is.
        """
        if self._snapshot is None:
            assert self._buf is not None, "empty store has no snapshot"
            rows = self._buf[: self._n].reshape(self._n, self.page_rows, 128)
            self._snapshot = jax.device_put(rows, self.device)
            self.snapshot_uploads += 1
        return self._snapshot

    def plane_view(self) -> jax.Array:
        """The data as ``(planes, slots, words_per_plane)``."""
        snap = self.snapshot().reshape(self._n, -1)[:, : self.padded_words]
        return snap.reshape(self._n, self.planes, self.words_per_plane).swapaxes(
            0, 1
        )
