"""Flash-Cosmos execution engine.

Executes a :class:`CommandPlan` with bit-exact latch semantics (paper
Figs. 3/4/6 and §6.2):

* MWS sensing: per target block, the NAND string conducts only if **all**
  selected cells conduct ⇒ AND of the block's selected wordlines; blocks
  share bitlines ⇒ OR across blocks; inverse read complements.
* S-latch: ``S = raw`` when initialized, else ``S & raw`` (ParaBit-AND).
* move-S-to-C: ``C = S`` when C initialized, else ``C | S`` (ParaBit-OR).
* XOR command: ``C = S ^ C``.
* Spill: ESP-program a latch into a scratch page.
* Transfer: DMA out, optional controller-side inversion.

The engine stores *logical* page data; physical cell data is complemented
for pages placed ``inverted`` (De Morgan storage).  Reads of non-ESP pages
can inject modelled bit errors (``repro.core.reliability``); ESP pages are
error-free — the paper's headline reliability result.

Page data lives in a :class:`repro.core.store.PackedStore` — one contiguous
``(slots, rows, 128)`` array — so sensing is at most two fused MWS kernel
dispatches (AND within blocks, OR across blocks) over the command's
wordline rows, never a Python loop over pages.  The ragged per-block
wordline sets are padded to a rectangle with the store's all-ones identity
row, letting one kernel call cover every target block.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commands import (
    CommandPlan,
    ESPCommand,
    MWSCommand,
    SpillCommand,
    ThresholdCommand,
    TransferCommand,
    XORCommand,
)
from repro.core.expr import Expr, Node, Page, Threshold
from repro.core.placement import Layout
from repro.core.planner import Planner
from repro.core.reliability import (
    CellMode,
    ProgramConfig,
    inject_bit_errors,
    rber,
)
from repro.core.store import IDENTITY_SLOT, PackedStore
from repro.kernels.mws.mws import mws_block_reduce_pallas, mws_sense_pallas
from repro.kernels.platform import resolve_interpret
from repro.kernels.threshold import bitslice_threshold, threshold_reduce


def _stable_seed(name: str) -> int:
    """Deterministic per-page seed component.

    ``hash(str)`` varies with ``PYTHONHASHSEED``, which made reliability
    simulations irreproducible across interpreter runs; CRC32 is stable.
    """
    return zlib.crc32(name.encode("utf-8")) & 0xFFFF


def _and_blocks(cube: jax.Array) -> jax.Array:
    """``(blocks, words)``: AND over each block's wordlines (XLA fold)."""
    anded = cube[:, 0]
    for i in range(1, cube.shape[1]):
        anded = anded & cube[:, i]
    return anded


def _or_rows(rows: jax.Array) -> jax.Array:
    raw = rows[0]
    for b in range(1, rows.shape[0]):
        raw = raw | rows[b]
    return raw


def fused_block_reduce(
    cube: jax.Array, inverse: bool, *, interpret: bool | None = None
) -> jax.Array:
    """MWS semantics on a gathered ``(blocks, wordlines, words)`` cube.

    AND over the wordline axis of every block, then OR across blocks;
    ``inverse`` complements the result (inverse-read mode).  Rows padded
    with the all-ones identity are AND-neutral.

    On a TPU both stages run in ONE Pallas dispatch over the cube
    (:func:`mws_block_reduce_pallas`); elsewhere (see
    :mod:`repro.kernels.platform`) the emulation folds with plain XLA ops —
    bit-identical to the kernel (the kernel tests assert exactly that) and
    cheap under ``jax.vmap``, where the Pallas interpreter's per-call cost
    would dominate query serving.
    """
    if resolve_interpret(interpret):
        raw = _or_rows(_and_blocks(cube))
    else:
        raw = mws_block_reduce_pallas(cube, or_blocks=True)
    return ~raw if inverse else raw


def take_pages(data: jax.Array, idx) -> jax.Array:
    """Gather slot pages of a ``(slots, rows, 128)`` snapshot as flat word
    rows: ``idx.shape + (rows * 128,)``.  Free on the CPU; on a TPU the
    flattening relays out only the gathered pages."""
    pages = jnp.take(data, idx, axis=0, mode="clip")
    return pages.reshape(pages.shape[: -2] + (-1,))


def mws_sense(
    idx: jax.Array,
    data: jax.Array,
    inverse: bool,
    latch: jax.Array | None = None,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """MWS of every member of a ``(members, blocks, wordlines)`` slot table,
    read in place from a ``(slots, rows, 128)`` snapshot; returns
    ``(members, rows, 128)``, ANDed into ``latch`` when given (the S latch
    of a sensing that does not initialize it).

    On a TPU one Pallas dispatch reads each operand page straight from the
    store by slot (:func:`mws_sense_pallas`): no cube is gathered.  The
    emulation gathers each member's cube and folds it with
    :func:`fused_block_reduce` — bit-identical (the kernel tests assert
    exactly that).
    """
    if not resolve_interpret(interpret):
        return mws_sense_pallas(idx, data, latch, inverse=inverse)
    raw = jax.vmap(
        lambda ix: fused_block_reduce(take_pages(data, ix), inverse, interpret=True)
    )(idx).reshape(idx.shape[:1] + data.shape[1:])
    return raw if latch is None else latch & raw


def threshold_block_reduce(
    cube: jax.Array, k: int, inverse: bool, *, interpret: bool | None = None
) -> jax.Array:
    """k-of-N threshold sensing on a gathered ``(blocks, wls, words)`` cube.

    Stage one is identical to a plain MWS: each block's NAND strings AND
    its selected wordlines (identity-padded rows are AND-neutral).  The
    cross-block combine then sets bit j iff at least ``k`` blocks conduct
    at j (dynamic sensing threshold); ``k == 1`` reproduces the wired-OR
    exactly.  Blocks padded with the all-zeros row never conduct, so
    family/vmap shape padding can never count toward the threshold.
    ``inverse`` complements AFTER the comparison.

    Like :func:`fused_block_reduce`, emulation folds with plain XLA ops —
    the same bit-sliced ripple-carry counter the Pallas kernel runs, so
    both paths are bit-identical by construction — while a TPU dispatches
    the fused kernels.  Explicit folds throughout (no
    ``jnp.bitwise_*.reduce``).
    """
    if resolve_interpret(interpret):
        raw = bitslice_threshold(_and_blocks(cube), k, cube.shape[0])[0]
    else:
        anded = mws_block_reduce_pallas(cube, or_blocks=False)
        raw = threshold_reduce(anded, k, interpret=False)
    return ~raw if inverse else raw


@dataclass
class FlashArray:
    """A (single-plane) Flash-Cosmos array: layout + packed page store."""

    layout: Layout = field(default_factory=Layout)
    store: PackedStore = field(default_factory=PackedStore)
    program_configs: dict[str, ProgramConfig] = field(default_factory=dict)
    pec: dict[int, int] = field(default_factory=dict)  # block -> P/E cycles
    # kernel path: None resolves, at construction, from the platform the
    # store lives on (repro.kernels.platform): compiled Pallas on a TPU,
    # emulation elsewhere
    interpret: bool | None = None
    # names of non-ESP pages, maintained incrementally so hot paths never
    # scan program_configs (one entry per (column, value) bitmap adds up)
    _non_esp: set = field(default_factory=set, repr=False)
    # host-initiated ESP page programs (fc_write(esp=True) + fc_append):
    # incremental ingest is gated on this — appending B rows must program
    # O(B) pages, not O(num_rows) (delta-page programming)
    esp_programs: int = 0
    # whole-block erases issued by erase_rebuild (NAND programs only 1->0,
    # so reclaiming tombstoned rows means erasing every block a stripe
    # occupies and reprogramming the live data — compaction charges these
    # in the SSD projection at t_bers_ms)
    block_erases: int = 0

    def __post_init__(self):
        self.interpret = resolve_interpret(self.interpret, self.store.device)

    # -- host API (fc_write / fc_read, §6.3) -------------------------------
    def fc_write(
        self,
        name: str,
        words: jax.Array,
        *,
        inverted: bool | None = None,
        block: int | None = None,
        wordline: int | None = None,
        esp: bool = True,
        charge: bool = True,
    ) -> None:
        """Program a page. ESP mode (default) guarantees error-free reads.

        Under multi-level packing (``layout.levels > 1``) the ESP margin
        stretches to ``tESP = (1 + levels) x tPROG`` — the per-level
        margin shrinks by 1/levels, so holding the paper's zero-error
        result needs the proportionally longer program (still zero-error
        per the reliability model at every supported level count).

        ``charge=False`` records the page content without bumping the
        wear/ESP counters: the MLC program path groups the co-resident
        logical pages of one physical page into ONE counted program (the
        group lead charges; the other levels ride the same ISPP pass).
        """
        if name in self.layout:
            p = self.layout[name]
            inverted = p.inverted if inverted is None else inverted
        else:
            inverted = bool(inverted)
            if block is None:
                (p,) = self.layout.place_colocated([name], inverted)
            else:
                p = self.layout.place(name, block, wordline or 0, inverted)
        levels = self.layout.levels
        cfg = (
            ProgramConfig(
                CellMode.SLC,
                randomized=False,
                tesp_ratio=1.0 + float(levels),
                levels=levels,
            )
            if esp
            else ProgramConfig(
                CellMode.SLC,
                randomized=False,
                tesp_ratio=1.0,
                levels=levels,
            )
        )
        self.program_configs[name] = cfg
        if esp:
            self._non_esp.discard(name)
        else:
            self._non_esp.add(name)
        physical = ~words if inverted else words
        self.store[name] = physical
        if charge:
            self.pec[p.block] = self.pec.get(p.block, 0) + 1
            if esp:
                self.esp_programs += 1

    def fc_append(
        self, name: str, words, *, start: int, charge: bool = True
    ) -> None:
        """Delta-page ESP program: extend an already-placed page's tail.

        Only ``words`` (logical, at word offset ``start``) are programmed —
        ONE page program's worth of traffic however many earlier words the
        page holds, which is what makes appending B rows to an N-row index
        cost O(B) instead of O(N).  The page keeps its placement, inversion,
        and program config; the store treats the write as a tail extension
        (compiled plans stay valid, see ``PackedStore.append_words``).
        ``charge=False`` as in :meth:`fc_write` (MLC physical-page groups).
        """
        p = self.layout[name]
        w = np.asarray(words, dtype=np.uint32)
        physical = ~w if p.inverted else w
        self.store.append_words(name, physical, start)
        if charge:
            self.pec[p.block] = self.pec.get(p.block, 0) + 1
            self.esp_programs += 1

    def fc_read(self, e: Expr) -> jax.Array:
        """Plan + execute a bulk bitwise expression; returns logical words."""
        plan = Planner(self.layout).compile(e)
        return self.execute(plan)

    def erase_rebuild(self) -> int:
        """Erase every programmed block and reset for a full reprogram.

        NAND programs cells 1->0 only; clearing a tombstone-riddled stripe
        back to fresh capacity requires erasing whole blocks (the erase
        unit) and reprogramming the surviving data — this is the device
        half of compaction.  Every block the layout occupies takes one P/E
        cycle (``pec``) and counts toward ``block_erases``; the page store
        and layout come back empty, but the store's content and region
        epochs are seeded ABOVE their old values, so every plan-cache /
        snapshot-cache key minted against the old data is permanently
        stale (a rebuild must never collide with a cached artifact of the
        pre-compaction page contents).  Returns the blocks erased.
        """
        blocks = {p.block for p in self.layout.placements.values()}
        for b in blocks:
            self.pec[b] = self.pec.get(b, 0) + 1
        self.block_erases += len(blocks)
        old = self.store
        self.store = PackedStore(planes=old.planes, device=old.device)
        self.store.epoch = old.epoch + 1
        self.store.region_epochs = {
            r: e + 1 for r, e in old.region_epochs.items()
        }
        self.layout = Layout(
            wls_per_block=self.layout.wls_per_block,
            levels=self.layout.levels,
        )
        self.program_configs.clear()
        self._non_esp.clear()
        return len(blocks)

    # -- sensing ------------------------------------------------------------
    def _gather_cube(
        self,
        cmd: MWSCommand,
        seed: int,
        scratch: dict[str, jax.Array] | None = None,
    ) -> jax.Array:
        """Gather the command's wordline rows into a padded (k, n, W) cube.

        Non-ESP pages get modelled bit errors injected on their gathered
        rows; ESP pages (the common case) come straight from the packed
        snapshot, so the gather is one fancy-index over the device array.
        ``scratch`` holds the device-resident values of pages spilled
        earlier in the executing plan — they live only in latch scratch
        (never the packed store), so their rows are substituted after the
        gather.  Spilled values are ESP-quality by construction (the spill
        IS an ESP program), so they never take injected errors.
        """
        snap = self.store.snapshot()
        n_max = max(len(t.wordlines) for t in cmd.targets)
        idx = []
        noisy: list[tuple[int, int, str]] = []
        subs: list[tuple[int, int, str]] = []
        for bi, t in enumerate(cmd.targets):
            row = []
            for wl in t.wordlines:
                name = self.layout.page_at(t.block, wl)
                if scratch is not None and name in scratch:
                    subs.append((bi, len(row), name))
                    row.append(IDENTITY_SLOT)  # placeholder, overwritten
                    continue
                row.append(self.store.slot(name))
                if name in self._non_esp:
                    noisy.append((bi, len(row) - 1, name))
            row.extend([IDENTITY_SLOT] * (n_max - len(row)))
            idx.append(row)
        cube = take_pages(snap, jnp.asarray(idx))
        for bi, wi, name in subs:
            cube = cube.at[bi, wi].set(scratch[name])
        for bi, wi, name in noisy:
            p = self.layout[name]
            r = rber(
                self.program_configs[name], pec=self.pec.get(p.block, 0)
            )
            cube = cube.at[bi, wi].set(
                inject_bit_errors(
                    cube[bi, wi], r, seed=seed ^ _stable_seed(name)
                )
            )
        return cube

    def _sense(
        self,
        cmd: MWSCommand,
        seed: int,
        scratch: dict[str, jax.Array] | None = None,
    ) -> jax.Array:
        cube = self._gather_cube(cmd, seed, scratch)
        if isinstance(cmd, ThresholdCommand):
            return threshold_block_reduce(
                cube, cmd.k, cmd.iscm.inverse_read, interpret=self.interpret
            )
        return fused_block_reduce(
            cube, cmd.iscm.inverse_read, interpret=self.interpret
        )

    # -- plan execution -------------------------------------------------------
    def execute(self, plan: CommandPlan, seed: int = 0) -> jax.Array:
        # Spilled sub-results stay device-resident for the plan's lifetime:
        # the SpillCommand's ESP program targets latch scratch, not the
        # packed store, so repeated executions of a cached spilling plan
        # never invalidate the store snapshot (the pre-pipeline engine
        # rewrote a store page per spill and re-uploaded the whole packed
        # buffer on the next sense).
        scratch: dict[str, jax.Array] = {}
        s = c = None
        out = None
        w = self.store.num_words
        for i, cmd in enumerate(plan.commands):
            if isinstance(cmd, MWSCommand):
                raw = self._sense(cmd, seed + i, scratch)
                s = raw if cmd.iscm.init_s_latch or s is None else s & raw
                if cmd.iscm.init_c_latch:
                    c = None  # M4 pulse wipes the cache latch (Fig. 6a)
                if cmd.iscm.move_s_to_c:
                    c = s if c is None else c | s
            elif isinstance(cmd, XORCommand):
                c = s ^ c
            elif isinstance(cmd, SpillCommand):
                # Keep the latch value as-is; when the sub-plan's logical
                # result is the complement of the latch, the planner
                # recorded that in the scratch page's layout.inverted flag
                # (spilled data is physical, like every stored page).
                scratch[cmd.page_name] = s if cmd.source == "S" else c
                self.pec[cmd.block] = self.pec.get(cmd.block, 0) + 1
            elif isinstance(cmd, TransferCommand):
                value = s if cmd.source == "S" else c
                out = ~value if cmd.invert else value
            elif isinstance(cmd, ESPCommand):
                pass  # data writes flow through fc_write in this model
        assert out is not None, "plan missing TransferCommand"
        return out[:w]


def eval_expr(e: Expr, logical: dict[str, jax.Array]) -> jax.Array:
    """Direct (oracle) evaluation of an expression on logical page data."""
    if isinstance(e, Page):
        return logical[e.name]
    if isinstance(e, Threshold):
        vals = jnp.stack([eval_expr(c, logical) for c in e.children])
        return bitslice_threshold(vals, e.k, vals.shape[0])[0]
    assert isinstance(e, Node)
    vals = jnp.stack([eval_expr(c, logical) for c in e.children])
    from repro.core.bitops import reduce_words

    return reduce_words(vals, e.op)
