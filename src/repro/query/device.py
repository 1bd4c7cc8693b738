"""FlashDevice: the vectorized multi-plane batch-execution engine.

A :class:`repro.core.engine.FlashArray` executes one plan at a time with a
Python loop over commands.  ``FlashDevice`` extends it for query serving:

* the page store is packed ``(planes, pages, words_per_plane)`` (see
  :class:`repro.core.store.PackedStore`) — a logical bit vector is striped
  across ``num_planes`` planes exactly like the paper's SSD stripes a
  800M-user bitmap, and because planes are word-axis shards, ONE fused
  block-reduce dispatch (:func:`repro.core.engine.fused_block_reduce`)
  senses a command on every plane at once;
* a :class:`CommandPlan` compiles to an :class:`ExecPlan`: per MWS command
  a static ``(blocks, wordlines)`` slot-index array (ragged wordline sets
  padded with the store's all-ones identity slot) plus the static ISCM
  flags.  Executing is then pure array code — gather, fused reduce, latch
  algebra — with **no Python-level per-page work**;
* plans with identical *signatures* (same command structure and shapes,
  different slot indices) execute as one batch under ``jax.vmap``: the
  whole batch becomes a handful of kernel dispatches regardless of batch
  size.  Runners are jitted and cached per signature;
* **plan-aware batching**: plans of one *family* (same command sequence
  and ISCM flags, narrower gather shapes) pad into the family's widest
  signature — extra wordlines gather the all-ones identity slot, extra
  blocks the all-zeros slot — so shape variance (and, in a sharded fleet,
  device fan-out) does not multiply the vmap group count.

Plans that spill lower too: the spilled latch values stay device-resident
inside the traced program (``"spill"`` steps + static cube substitutions),
so deep-range chains batch and vmap like any other plan instead of running
eagerly one by one.  The eager :meth:`FlashArray.execute` path remains for
plans that sense non-ESP pages (their reads inject modelled bit errors,
which the batch path never does).

:func:`make_flush_runner` goes one step further and fuses a WHOLE flush —
every signature group plus every aggregate reduce — into one jitted
program returning a single host payload (see
:func:`repro.query.compile.compile_flush`).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

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
from repro.core.engine import (
    FlashArray,
    fused_block_reduce,
    threshold_block_reduce,
)
from repro.core.store import IDENTITY_SLOT, ZERO_SLOT, PackedStore


@dataclass(frozen=True)
class _Step:
    """Static (trace-time) part of one executable command."""

    kind: str  # "mws" | "xor" | "xfer" | "spill"
    # "mws": threshold k for a ThresholdCommand sensing, 0 for the plain
    # wired-OR MWS.  Part of the signature AND the family (family erasure
    # rewrites only ``shape``), so a threshold plan never pads into a
    # plain group — the combine semantics differ.
    k: int = 0
    inverse: bool = False
    init_s: bool = True
    init_c: bool = True
    move: bool = False
    source: str = "C"
    invert: bool = False
    shape: tuple[int, int] = (0, 0)  # (blocks, padded wordlines) for "mws"
    # "mws": (block_pos, wordline_pos, spill ordinal) substitutions — the
    # gathered cube rows replaced by device-resident spilled values; the
    # positions are static, so spilling plans stay pure array programs
    subs: tuple[tuple[int, int, int], ...] = ()
    # "mws": (block_pos, wordline_pos, shared ordinal) substitutions into
    # the flush-level shared-value stack (cross-query CSE): the row is a
    # latch result another plan in the same flush already sensed
    shared: tuple[tuple[int, int, int], ...] = ()
    ordinal: int = 0  # "spill": index into the plan's scratch values


@dataclass(frozen=True)
class ExecPlan:
    """A CommandPlan lowered to gather indices + static step descriptors.

    Spilling plans lower too: each :class:`SpillCommand` becomes a
    ``"spill"`` step that parks the latch value in device-resident scratch
    (a plan-local value list inside the traced program — never a store
    write), and later MWS steps that sense the scratch page substitute it
    into the gathered cube at static positions.  Deep-range queries
    therefore batch, vmap, and join the fused flush reduce like any
    spill-free plan.
    """

    steps: tuple[_Step, ...]
    idxs: tuple[np.ndarray, ...]  # one (blocks, wordlines) array per MWS
    spills: int = 0  # scratch values the plan carries device-side
    # scratch blocks the plan's SpillCommands target: a spill is
    # physically an ESP program, so batched executions charge the same
    # P/E wear the eager path does (see age_spill_blocks)
    spill_blocks: tuple[int, ...] = ()

    @property
    def signature(self) -> tuple[_Step, ...]:
        """Batch key: two plans with equal signatures vmap together."""
        return self.steps

    @property
    def family(self) -> tuple[_Step, ...]:
        """Signature with MWS gather shapes erased (plan-aware batching).

        Two plans of one family run the same command sequence with the same
        ISCM flags and differ only in how many (blocks, wordlines) each MWS
        gathers; the narrower plan pads to the wider shape with identity
        slots (see :func:`pad_idx`) and then shares its vmap group.
        Scratch substitution positions are NOT erased — they are part of
        the command sequence, and padding never moves them.
        """
        return tuple(
            replace(st, shape=(0, 0)) if st.kind == "mws" else st
            for st in self.steps
        )


def age_spill_blocks(pec: dict, execs) -> None:
    """Charge P/E wear for the scratch programs of batch-executed plans.

    A SpillCommand is physically an ESP program to a scratch wordline; the
    batched paths run it as device-resident latch scratch, but the wear on
    the scratch block is real — this keeps ``pec`` consistent with the
    eager :meth:`FlashArray.execute`, which bumps per SpillCommand.
    """
    for e in execs:
        if e is not None:
            for b in e.spill_blocks:
                pec[b] = pec.get(b, 0) + 1


def pad_idx(idx: np.ndarray, shape: tuple[int, int]) -> np.ndarray:
    """Pad an MWS gather-index array to ``shape`` without changing results.

    Extra *wordlines* of real blocks gather the all-ones identity slot
    (AND-neutral within a block); extra *blocks* gather the all-zeros slot
    in their first wordline, so they AND to zero and are OR-neutral across
    blocks — and stay so under inverse read, which complements only after
    the cross-block OR.
    """
    b, w = idx.shape
    B, W = shape
    if (b, w) == (B, W):
        return idx
    out = np.full((B, W), IDENTITY_SLOT, dtype=np.int32)
    out[:b, :w] = idx
    if B > b:
        out[b:, 0] = ZERO_SLOT
    return out


def group_execs(
    execs: list["ExecPlan | None"], pad: bool = True
) -> list[tuple[tuple[_Step, ...], list[int], list[np.ndarray]]]:
    """Group batchable plans for vmap execution.

    Returns ``(signature, member_indices, stacked_idxs)`` triples, where
    ``stacked_idxs`` holds one ``(B, blocks, wordlines)`` array per MWS
    step.  With ``pad`` set, plans are grouped by :attr:`ExecPlan.family`
    and padded to the family's widest shapes — fewer, larger vmap groups;
    otherwise grouping is by exact signature.
    """
    groups: dict[tuple, list[int]] = {}
    for i, e in enumerate(execs):
        if e is not None:
            groups.setdefault(e.family if pad else e.signature, []).append(i)
    out = []
    for key, members in groups.items():
        first = execs[members[0]]
        n_mws = len(first.idxs)
        shapes = [
            (
                max(execs[i].idxs[s].shape[0] for i in members),
                max(execs[i].idxs[s].shape[1] for i in members),
            )
            for s in range(n_mws)
        ]
        it = iter(shapes)
        signature = tuple(
            replace(st, shape=next(it)) if st.kind == "mws" else st
            for st in first.steps
        )
        stacked = [
            np.stack([pad_idx(execs[i].idxs[s], shapes[s]) for i in members])
            for s in range(n_mws)
        ]
        out.append((signature, members, stacked))
    return out


def reorder_rows(pieces: list[jax.Array], order: list[int]) -> jax.Array:
    """Concatenate per-group output blocks and restore input order with a
    single inverse-permutation gather (never per-row slicing)."""
    allout = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    inv = np.empty(len(order), dtype=np.int32)
    inv[np.asarray(order)] = np.arange(len(order), dtype=np.int32)
    return allout[jnp.asarray(inv)]


def plan_step_fn(signature: tuple[_Step, ...], interpret: bool | None):
    """Pure single-plan executor for one signature:
    ``run_one(data, shared, *idxs)``.

    The traced body shared by :func:`make_plan_runner` (standalone jitted
    vmap) and :func:`make_flush_runner` (inlined into the fused flush
    program).  ``"spill"`` steps park the latch value in a plan-local
    scratch list; MWS steps with substitutions splice those values into the
    gathered cube at static positions (device-resident scratch — spilling
    plans never touch the store).  ``shared`` is the flush-level CSE value
    stack (``(K, words)`` or None): MWS steps carrying ``shared``
    substitutions splice those rows in the same way, fanning one sensing's
    latch result out to every plan that references it.
    """

    def run_one(data: jax.Array, shared, *idxs: jax.Array) -> jax.Array:
        s = c = out = None
        scratch: list[jax.Array] = []
        it = iter(idxs)
        for st in signature:
            if st.kind == "mws":
                # (blocks, wordlines, words); take, not data[idx]: it
                # traces several times cheaper inside vmapped programs
                cube = jnp.take(data, next(it), axis=0, mode="clip")
                for bi, wi, o in st.subs:
                    cube = cube.at[bi, wi].set(scratch[o])
                for bi, wi, k in st.shared:
                    cube = cube.at[bi, wi].set(shared[k])
                raw = (
                    threshold_block_reduce(
                        cube, st.k, st.inverse, interpret=interpret
                    )
                    if st.k
                    else fused_block_reduce(
                        cube, st.inverse, interpret=interpret
                    )
                )
                s = raw if (st.init_s or s is None) else s & raw
                if st.init_c:
                    c = None
                if st.move:
                    c = s if c is None else c | s
            elif st.kind == "spill":
                assert st.ordinal == len(scratch)
                scratch.append(s if st.source == "S" else c)
            elif st.kind == "xor":
                c = s ^ c
            else:
                val = s if st.source == "S" else c
                out = ~val if st.invert else val
        assert out is not None, "plan missing TransferCommand"
        return out

    return run_one


def make_plan_runner(
    signature: tuple[_Step, ...],
    interpret: bool | None,
    *,
    shard_data: bool = False,
):
    """Build the jitted vmap executor for one plan signature.

    ``shard_data=False``: ``run(data, *idxs)`` with one ``(slots, words)``
    snapshot shared by every batch element (single device).

    ``shard_data=True``: ``run(data, shard_ix, *idxs)`` where ``data`` is a
    stacked ``(shards, slots, words)`` fleet snapshot and ``shard_ix`` maps
    each batch element to its shard — one jit-of-vmap dispatch covers a
    whole signature group across every device of a sharded deployment.
    """
    run_one = plan_step_fn(signature, interpret)
    n_mws = sum(1 for st in signature if st.kind == "mws")
    if shard_data:
        return jax.jit(
            jax.vmap(
                lambda data, si, *ix: run_one(data[si], None, *ix),
                in_axes=(None, 0) + (0,) * n_mws,
            )
        )
    return jax.jit(
        jax.vmap(
            lambda data, *ix: run_one(data, None, *ix),
            in_axes=(None,) + (0,) * n_mws,
        )
    )


def make_flush_runner(key: tuple, interpret: bool | None):
    """Build the single jitted program executing a whole flush signature.

    ``key`` is the flush signature: ``(sense, reduce, w, cse)`` where
    ``sense`` is a tuple of ``(plan signature, member count)`` per vmap
    group, ``reduce`` a tuple of ``(aggregator kind, reduce_sig, member
    count, extra-plane count)`` per reduce group, ``w`` the store's logical
    word count, and ``cse`` a tuple of shared-plan signatures — the
    cross-query common subexpressions this flush senses ONCE and splices
    into every member plan that references them.  The returned
    ``run(data, group_idxs, inv_perm, mask, sels, extras, cse_idxs)``
    fuses EVERYTHING a flush does device-side — the shared sensings, the
    per-group gather + latch algebra, the member-order-restoring gather
    (``inv_perm`` maps members onto deduplicated unique-plan rows, so two
    queries with one predicate read one sensing's row twice), validity
    masking, and every aggregate's (weighted-)popcount reduce — and
    returns ONE flat ``uint32`` payload (see
    :func:`repro.query.aggregate.unpack_group`): one kernel dispatch and
    one host transfer per flush, however many signature groups and
    aggregate kinds it mixes.
    """
    from repro.query.aggregate import kind_reduce

    sense, reduce_sigs, w, cse = key

    # the module is named after this function (``jit_run``): the
    # benchmark's device-time metrics find the flush program by that name
    def run(data, group_idxs, inv_perm, mask, sels, extras, cse_idxs):
        with jax.named_scope("sense"):
            shared = None
            if cse:
                # shared subexpressions first: K is small, so these run as
                # plain (unvmapped) plans; members gather their rows below
                vals = [
                    plan_step_fn(psig, interpret)(data, None, *idxs)
                    for psig, idxs in zip(cse, cse_idxs)
                ]
                shared = jnp.stack(vals)
            pieces = []
            for (psig, _n), idxs in zip(sense, group_idxs):
                one = plan_step_fn(psig, interpret)
                n_mws = len(idxs)
                out = jax.vmap(one, in_axes=(None, None) + (0,) * n_mws)(
                    data, shared, *idxs
                )
                pieces.append(out[:, :w])
            allout = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
            masked = allout[inv_perm] & mask  # member order, padding zeroed
        with jax.named_scope("reduce"):
            parts = []
            for (kind, sig, _n, _p), sel, ex in zip(reduce_sigs, sels, extras):
                sub = masked if sel is None else masked[sel]
                out = kind_reduce(kind, sub, ex, sig, interpret=interpret)
                parts.extend(
                    jnp.ravel(leaf).astype(jnp.uint32)
                    for leaf in jax.tree_util.tree_leaves(out)
                )
            return jnp.concatenate(parts)

    return jax.jit(run)


@dataclass
class FlashDevice(FlashArray):
    """Multi-plane Flash-Cosmos device with batched plan execution."""

    num_planes: int = 4
    # plan-aware batching: pad narrower plans into a family's widest
    # signature so one vmap group covers every shape variant of a family
    pad_signatures: bool = True
    last_signature_groups: int = 0  # groups dispatched by the last batch
    last_eager_plans: int = 0  # noisy-page eager fallbacks in the last batch
    _runners: dict = field(default_factory=dict, repr=False)
    # prepared-batch cache: grouping + device-resident idx uploads per
    # recurring batch composition (see execute_batch_stacked's batch_key)
    _batch_cache: dict = field(default_factory=dict, repr=False)
    # attached by the owning scheduler (repro.query.telemetry.Telemetry):
    # counts jitted-runner builds and prepared-batch cache traffic
    telemetry: object = field(default=None, repr=False)

    def __post_init__(self):
        super().__post_init__()
        if self.store.planes != self.num_planes:
            if len(self.store):
                raise ValueError(
                    "cannot re-stripe a non-empty store; construct the "
                    "device with store=PackedStore(planes=num_planes)"
                )
            self.store = PackedStore(
                planes=self.num_planes, device=self.store.device
            )

    def reset_after_rebuild(self) -> None:
        """Drop prepared-batch state after :meth:`erase_rebuild`.

        Batch-cache keys embed plan-cache keys whose epochs can never be
        minted again, so the entries are unreachable — clearing just frees
        them eagerly.  Jitted runners stay: they are keyed on structural
        signatures and serve the rebuilt store unchanged.
        """
        self._batch_cache.clear()
        self.last_signature_groups = 0
        self.last_eager_plans = 0

    # -- plan lowering -----------------------------------------------------
    def build_exec(
        self,
        plan: CommandPlan,
        shared: dict[str, int] | None = None,
        layout=None,
    ) -> ExecPlan | None:
        """Lower a plan (spilling or not) to a batchable ExecPlan.

        Spill commands lower to ``"spill"`` steps whose values stay
        device-resident; MWS commands that re-sense a spilled scratch page
        record a static substitution instead of a store slot, so the whole
        plan — deep-range chains included — is a pure function of the
        packed snapshot and joins the fused/vmap execution paths.

        ``shared`` maps virtual CSE page names to ordinals in the flush's
        shared-value stack: sensing one records a ``shared`` substitution
        instead of a store slot (the value is another plan's latch result,
        resident only inside the fused program).  ``layout`` overrides the
        device layout for name resolution — CSE member plans compile
        against a fork that additionally places the virtual pages.
        """
        lay = self.layout if layout is None else layout
        steps: list[_Step] = []
        idxs: list[np.ndarray] = []
        scratch_ord: dict[str, int] = {}
        spill_blocks: list[int] = []
        for cmd in plan.commands:
            if isinstance(cmd, MWSCommand):
                n_max = max(len(t.wordlines) for t in cmd.targets)
                idx = np.full(
                    (len(cmd.targets), n_max), IDENTITY_SLOT, dtype=np.int32
                )
                subs: list[tuple[int, int, int]] = []
                shared_subs: list[tuple[int, int, int]] = []
                for bi, t in enumerate(cmd.targets):
                    for wi, wl in enumerate(t.wordlines):
                        name = lay.page_at(t.block, wl)
                        if name in scratch_ord:
                            subs.append((bi, wi, scratch_ord[name]))
                            continue  # placeholder gathers the identity row
                        if shared and name in shared:
                            shared_subs.append((bi, wi, shared[name]))
                            continue
                        idx[bi, wi] = self.store.slot(name)
                steps.append(
                    _Step(
                        "mws",
                        k=cmd.k if isinstance(cmd, ThresholdCommand) else 0,
                        inverse=cmd.iscm.inverse_read,
                        init_s=cmd.iscm.init_s_latch,
                        init_c=cmd.iscm.init_c_latch,
                        move=cmd.iscm.move_s_to_c,
                        shape=(len(cmd.targets), n_max),
                        subs=tuple(subs),
                        shared=tuple(shared_subs),
                    )
                )
                idxs.append(idx)
            elif isinstance(cmd, SpillCommand):
                steps.append(
                    _Step(
                        "spill",
                        source=cmd.source,
                        ordinal=len(scratch_ord),
                    )
                )
                scratch_ord[cmd.page_name] = len(scratch_ord)
                spill_blocks.append(cmd.block)
            elif isinstance(cmd, XORCommand):
                steps.append(_Step("xor"))
            elif isinstance(cmd, TransferCommand):
                steps.append(
                    _Step("xfer", source=cmd.source, invert=cmd.invert)
                )
            elif isinstance(cmd, ESPCommand):
                raise AssertionError("data writes flow through fc_write")
        return ExecPlan(
            tuple(steps),
            tuple(idxs),
            spills=len(scratch_ord),
            spill_blocks=tuple(spill_blocks),
        )

    # -- batched execution -------------------------------------------------
    def _runner(self, signature: tuple[_Step, ...]):
        fn = self._runners.get(signature)
        if fn is None:
            fn = make_plan_runner(signature, self.interpret)
            self._runners[signature] = fn
            if self.telemetry is not None:
                self.telemetry.count("runner_builds")
        return fn

    def _prepare_batch(
        self, execs: list[ExecPlan | None], batch_key=None
    ) -> tuple[list[tuple], tuple[int, ...]]:
        """Group + pad execs and upload their gather indices to the device.

        Returns ``(groups, eager_ix)``: the vmap groups plus the indices of
        plans demoted to the eager path — spilling plans that sense a
        non-ESP page keep their pre-pipeline error-injecting execution
        (the batch path never injects read errors); a spill-free plan over
        a non-ESP page still raises, as it always did.

        With ``batch_key`` (any hashable derived from the plan-cache keys,
        whose epoch components make staleness impossible), the prepared
        groups are memoized: a recurring batch composition — the steady
        state of query serving — skips grouping, padding, stacking, AND
        the host->device index transfer on every flush.
        """
        if batch_key is not None:
            prepared = self._batch_cache.get(batch_key)
            if prepared is not None:
                if self.telemetry is not None:
                    self.telemetry.count("batch_cache_hits")
                return prepared
            if self.telemetry is not None:
                self.telemetry.count("batch_cache_misses")
        noisy_slots = {
            self.store.slot(n) for n in self._non_esp if n in self.store
        }
        eager_ix: list[int] = []
        use = list(execs)
        if noisy_slots:
            for i, e in enumerate(execs):
                if e is not None and any(
                    bool(np.isin(idx, list(noisy_slots)).any())
                    for idx in e.idxs
                ):
                    if e.spills:
                        use[i] = None  # eager fallback injects the errors
                        eager_ix.append(i)
                    else:
                        raise ValueError(
                            "batched execution senses a non-ESP page; "
                            "reprogram it with esp=True or execute eagerly"
                        )
        prepared = (
            [
                (signature, members, tuple(jnp.asarray(s) for s in stacked))
                for signature, members, stacked in group_execs(
                    use, pad=self.pad_signatures
                )
            ],
            tuple(eager_ix),
        )
        if batch_key is not None:
            if len(self._batch_cache) >= 64:  # bound recurring compositions
                self._batch_cache.clear()
            self._batch_cache[batch_key] = prepared
        return prepared

    def execute_batch_stacked(
        self,
        plans: list[CommandPlan],
        seed: int = 0,
        execs: list[ExecPlan | None] | None = None,
        batch_key=None,
    ) -> jax.Array:
        """Execute independent plans; returns ``(B, num_words)`` results in
        input order as ONE stacked array.

        The whole batch costs O(signature groups) device dispatches — group
        outputs are concatenated and re-ordered with a single gather, never
        sliced per plan — which is what keeps serving overhead flat as
        batches grow.  The batch path never injects read errors, so every
        page a batched plan senses must be ESP-programmed (`fc_write`
        default) — unrelated non-ESP pages are fine, and spilling plans
        over noisy pages demote to the eager error-injecting path.  Pass
        ``execs`` (from :meth:`build_exec`) to skip re-lowering, and
        ``batch_key`` to memoize the batch grouping (see
        :meth:`_prepare_batch`).
        """
        if execs is None:
            execs = [self.build_exec(p) for p in plans]
        groups, eager_ix = self._prepare_batch(execs, batch_key)
        self.last_signature_groups = len(groups)
        self.last_eager_plans = len(eager_ix) + sum(
            1 for e in execs if e is None
        )

        w = self.store.num_words
        pieces: list[jax.Array] = []  # (B_g, w) per group / eager plan
        order: list[int] = []
        if groups:
            data = self.store.snapshot()
            for signature, members, idxs in groups:
                out = self._runner(signature)(data, *idxs)  # (B_g, Wp)
                pieces.append(out[:, :w])
                order.extend(members)
        for i, e in enumerate(execs):
            if e is None or i in eager_ix:  # noisy-page eager fallback
                # execute() charges its own spill wear
                pieces.append(self.execute(plans[i], seed=seed + i)[None])
                order.append(i)
            elif e.spill_blocks:
                age_spill_blocks(self.pec, (e,))
        if not pieces:
            return jnp.zeros((0, w or 0), jnp.uint32)
        return reorder_rows(pieces, order)

    def execute_batch(
        self,
        plans: list[CommandPlan],
        seed: int = 0,
        execs: list[ExecPlan | None] | None = None,
    ) -> list[jax.Array]:
        """List-of-arrays variant of :meth:`execute_batch_stacked`."""
        stacked = self.execute_batch_stacked(plans, seed=seed, execs=execs)
        return [stacked[i] for i in range(len(plans))]
