"""Plan execution: the python interpreter (the reference) and the compiled
shard_map MPMD executor.

The compiled executor is the TPU realization of ACETONE's generated
parallel C (paper §5.3): one mesh axis ``workers`` carries the m per-core
programs (MPMD-on-SPMD: every worker dispatches on ``axis_index``); comm
rounds become ``lax.ppermute`` collectives — the Writing/Reading flag
protocol realized as dataflow edges, whose ordering guarantees are
enforced by construction.

Register discipline: a **liveness pass** over the plan gives every layer
output a birth superstep (first computed anywhere) and a death superstep
(last read as a compute input or transfer payload), and
``pack_registers`` lays the registers out in one packed buffer whose
slots are reused once their occupant is dead.  This keeps ACETONE's
fully-static allocation story (every buffer's lifetime is known at
generation time — the analogue of the paper's static per-layer output
variables) while shrinking the per-worker footprint to the schedule's
actual working set.

The executor consumes the plan-side canonicalization (``pack_registers``
+ ``build_segments`` in ``plan.py``) and lowers each
:class:`~repro.codegen.plan.PlanSegment` to ``lax.scan`` loops whose carry
is the packed register buffer and whose body is a single ``lax.switch`` over
the segment's kernel table (structurally identical tile tasks share one
traced branch — see :mod:`repro.codegen.segment`) followed by the
segment's fixed ring-shift ``ppermute`` rounds, which gather padded index
rows instead of tracing per-transfer slicing.  Program size is bounded by
the number of *distinct* task structures, not the task count; results
match ``interpret_plan`` (up to float reassociation in the convolutions).

Its runtime fast paths:

* **value-returning dispatch** — switch branches return ``(y_pad,
  start)`` instead of threading the whole carry, and one outer
  ``dynamic_update_slice`` lands the result: the scan body never copies
  the register buffer through a conditional (on XLA:CPU a carry-threading
  ``lax.switch`` copies the full buffer per tick).  Branches pad their
  output to their scan's landing width with a *self-restoring tail* (a
  dynamic_slice of the columns the write is about to overwrite), so the
  uniform-width write is exact;
* **span-coalesced assembly** — fires per signature slot when
  ``segment.coalesce_spans`` finds that the slot's gather rows are
  piecewise contiguous across every occurrence (conv/pool row tiles, halo
  pads resolved into contiguous sentinel *regions*, whole-register
  reads): each piece of at least ``segment.MIN_SPAN`` elements becomes
  one memcpy-width ``dynamic_slice`` from a per-occurrence starts table,
  the scattered remainder shares one element gather;
* **window-gathered assembly** — a slot that stays scattered past the span
  thresholds (> ``segment.MAX_SPANS`` pieces or < ``segment.MIN_COVERAGE``
  coverage) but whose rows split into equal contiguous windows of at
  least ``segment.MIN_WINDOW`` elements (``segment.coalesce_windows``:
  an HWC channel slice, a seen-through concat's per-pixel channel runs)
  is read from a per-occurrence table of window starts, one index per
  window instead of per element: each window is the pair of ``LANES``-wide
  rows that holds it, gathered from an aligned slab of the carry and
  shifted into place (``_gather_windows``); only the remaining slots keep
  the whole-slot element gather;
* **staged comm with a pattern switch** — ``build_segments`` groups each
  delta's shipping ticks into payload-scale cohorts, pads each
  :class:`~repro.codegen.plan.CommRound` only to its cohort max (not the
  segment max) and elides fully-padded rounds at build time; the runtime
  dispatches each tick through one switch over the segment's distinct
  *active-round patterns*, whose branches execute exactly their fires
  (no per-round idle conds) and land the concatenated payloads with one
  ``dynamic_update_slice`` into the tick's contiguous block of staging
  strips.  Consumers read delivered values straight out of the strips:
  their gather tables are statically redirected at build time through a
  per-worker ``home`` map, so no receive-side scatter or runtime
  indexing exists at all;
* **single-structure runs** — when a landing run has exactly one
  signature and no idle (tick, worker) cells, every tick runs the same
  branch, so the ``lax.switch`` and its operand plumbing are skipped and
  the branch is called directly.

The executor's ops carry named scopes — ``seg<k>`` per segment and,
inside it, ``assemble`` / ``params`` / ``kernel`` / ``land`` / ``retire``
/ ``comm`` / ``checkpoint``, then ``output`` — so a profiler trace of the
served program attributes device time per segment and per phase
(``jax.named_scope`` only sets op metadata; the compiled program is the
same).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.codegen.plan import (
    ExecutionPlan,
    RegisterLayout,
    Transfer,
    build_segments,
    coalesce_transfer_steps,
    pack_registers,
)
from repro.models.cnn import CNNModel, apply_layer

__all__ = [
    "interpret_plan",
    "build_mpmd_executor",
    "plan_liveness",
    "executed_comm_bytes",
    "PlanTables",
    "SegmentAccess",
    "AccessTables",
    "plan_tables",
    "plan_access_walk",
    "segment_access_tables",
]


def _box_index(t: Transfer) -> Tuple[slice, ...]:
    """Batched register index of a windowed transfer's payload.

    One slice per per-sample axis, so 2-D grid-tile hulls (a row window ×
    a channel window) ship exactly like single-axis windows."""
    return (slice(None), *(slice(lo, hi) for (lo, hi) in t.box))


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: worker
    branches switch on ``axis_index``, which that check cannot type."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )


# --------------------------------------------------------------------------- #
# register liveness
# --------------------------------------------------------------------------- #
def plan_liveness(
    plan: ExecutionPlan, model: CNNModel
) -> Tuple[Dict[str, int], Dict[str, int], List[Set[str]]]:
    """Static birth/death supersteps of every register in ``plan``.

    ``birth[b]`` is the first superstep where ``b`` is computed on any
    worker; ``death[b]`` the last superstep where ``b`` is read — as a
    compute input, as a transfer payload, or (for the sink) at plan exit
    (``death[sink] == len(plan.steps)``, i.e. past every step).  Returns
    ``(birth, death, live_sets)`` where ``live_sets[i]`` is the set of
    buffers the executor must hold during superstep ``i``.
    """
    n = len(plan.steps)
    birth: Dict[str, int] = {}
    death: Dict[str, int] = {}
    for i, step in enumerate(plan.steps):
        for seg in step.compute:
            for name in seg:
                birth.setdefault(name, i)
                death[name] = max(death.get(name, i), i)
                spec = model.spec(name)
                if spec.op != "input":
                    for p in spec.inputs:
                        death[p] = max(death.get(p, i), i)
        for t in step.transfers:
            # a transfer both reads the register and materializes it on the
            # destination: a node whose first appearance is as a transfer
            # payload (e.g. a transfer-only first round in a hand-built
            # plan) must be born at its producing superstep, not default to
            # an unborn buffer with death at step 0
            birth.setdefault(t.node, i)
            death[t.node] = max(death.get(t.node, birth[t.node]), i)
    death[plan.sink] = n  # the output buffer survives the whole plan
    live_sets = [
        {b for b, bi in birth.items() if bi <= i <= death[b]} for i in range(n)
    ]
    return birth, death, live_sets


# --------------------------------------------------------------------------- #
# python interpreter — the oracle for plan logic (no devices needed)
# --------------------------------------------------------------------------- #
def interpret_plan(
    plan: ExecutionPlan,
    model: CNNModel,
    params,
    x: jax.Array,
) -> jax.Array:
    """Execute the plan with per-worker register dicts in python.

    Used by tests to check plan logic (availability, supplier choice,
    transfer completeness) independent of shard_map machinery.
    """
    regs: List[Dict[str, jax.Array]] = [dict() for _ in range(plan.n_workers)]
    for step in plan.steps:
        for w, seg in enumerate(step.compute):
            for name in seg:
                spec = model.spec(name)
                ins = [x] if spec.op == "input" else [regs[w][p] for p in spec.inputs]
                regs[w][name] = apply_layer(spec, params, ins)
        for t in step.transfers:
            src = regs[t.src][t.node]
            if t.box is None:
                regs[t.dst][t.node] = src
            else:
                # windowed transfer: copy only the consumed hull, leaving
                # the rest of the destination register unmaterialized
                # (zeros) — consumers read strictly inside the hull, and
                # this oracle catches any box-inference bug numerically
                idx = _box_index(t)
                cur = regs[t.dst].get(t.node, jnp.zeros_like(src))
                regs[t.dst][t.node] = cur.at[idx].set(src[idx])
    return regs[plan.sink_worker][plan.sink]


def _check_batch(x, batch: int) -> None:
    """Eager batch-dimension check shared by the executor wrappers."""
    lead = x.shape[0] if getattr(x, "ndim", 0) else None
    if lead != batch:
        raise ValueError(
            f"this executor was built for batch={batch} (baked into its "
            f"register layout) but the input has leading dimension "
            f"{lead}; rebuild with build_mpmd_executor(..., "
            f"batch={lead})"
        )


def _with_batch_check(
    jitted, batch: int, extra_args: Tuple = ()
) -> Callable[[jax.Array], jax.Array]:
    """Wrap a jitted executor with an eager batch-dimension check.

    The batch size is baked into every register shape at build time; calling
    with a different leading dimension would otherwise surface as an opaque
    shard_map/switch shape mismatch from deep inside tracing.  The wrapper
    exposes ``.lower`` (for inspecting the lowered program) with the same
    check.
    """

    def run(x: jax.Array) -> jax.Array:
        _check_batch(x, batch)
        return jitted(x, *extra_args)

    def lower(x: jax.Array):
        _check_batch(x, batch)
        return jitted.lower(x, *extra_args)

    run.lower = lower
    return run


def _with_carry_feedback(
    jitted, batch: int, carry_shape: Tuple[int, int, int], seg_tables,
    checkpoint: bool,
) -> Callable[[jax.Array], jax.Array]:
    """Streaming-executor wrapper: donate the packed carry across calls.

    The jitted executor takes the previous call's final carry as a donated
    argument (``donate_argnums``) and re-initializes the register region
    in-trace, so XLA updates the packed registers and rotating staging
    frames in place instead of materializing a fresh buffer every call.
    The wrapper owns the fed-back carry and hides the plumbing: the public
    signature stays ``f(x) -> y`` (or ``(y, snaps)`` under checkpoint),
    exactly like the write-once executor.  Backends without donation
    support just fall back to copying — the ignored-donation warning is
    suppressed because outputs never depend on the incoming carry's bytes.
    """
    import warnings

    state = {"carry": None}

    def fresh():
        return jnp.zeros(carry_shape, jnp.float32)

    def run(x: jax.Array):
        _check_batch(x, batch)
        c = state["carry"]
        if c is None:
            c = fresh()
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onat.*", category=UserWarning
            )
            out = jitted(x, c, seg_tables)
        if checkpoint:
            y, carry, snaps = out
            state["carry"] = carry
            return y, snaps
        y, carry = out
        state["carry"] = carry
        return y

    def lower(x: jax.Array):
        _check_batch(x, batch)
        return jitted.lower(x, fresh(), seg_tables)

    run.lower = lower
    return run


def executed_comm_bytes(
    plan: ExecutionPlan,
    model: CNNModel,
    batch: int = 1,
    dtype_bytes: int = 4,
    buffer_depth: int = 1,
) -> float:
    """Exact payload bytes the executor's collectives ship.

    Mirrors the comm lowering analytically: the cohort-sized ring rounds
    ship only the *real* (non-padding) entries of each active ``(tick,
    dst)`` index row — pad entries gather from and scatter into the dump
    column, shipping no register data — so the total is exactly
    ``plan.comm_bytes`` scaled by ``batch * dtype_bytes`` / producer-bytes,
    whatever the cohort shapes.  ``buffer_depth`` only relocates where a
    payload *lands* (write-once strip vs rotating frame): every delivery
    is counted exactly once here whatever the depth — the streaming
    executor's extra retire copies are local buffer moves, not shipped
    bytes — so the byte parity with ``plan.comm_bytes`` holds at every
    depth.
    """
    plan = coalesce_transfer_steps(plan)
    sizes = {l.name: max(int(np.prod(l.out_shape)), 1) for l in model.layers}
    reg_shapes = {l.name: tuple(l.out_shape) for l in model.layers}
    birth, death, _sets = plan_liveness(plan, model)
    offsets, total = pack_registers(plan, sizes, liveness=(birth, death))
    pad = total  # stand-in dump column; positions are in [0, total)
    segments = build_segments(
        plan, reg_shapes, offsets, pad_index=pad, buffer_depth=buffer_depth,
    )
    real = 0
    for seg in segments:
        for r in seg.rounds:
            per_row = (np.asarray(r.rows) != pad).sum(axis=1)
            real += int(per_row[np.asarray(r.slot)].sum())
    return float(real) * batch * dtype_bytes


# --------------------------------------------------------------------------- #
# shard_map MPMD executor
# --------------------------------------------------------------------------- #
def _gather_cols(
    buf: jax.Array, idx: jax.Array, sorted_: bool = False
) -> jax.Array:
    """``buf[:, idx]`` as one raw ``lax.gather`` (no jnp indexing machinery —
    these gathers run once per switch branch and comm round, so their
    tracing/lowering cost is the executor's hot path).  ``idx``
    must be in bounds (sentinel indices resolve to real buffer columns);
    comm rows are pre-sorted by the plan canonicalization."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(0,), collapsed_slice_dims=(1,), start_index_map=(1,)
    )
    return jax.lax.gather(
        buf, jax.lax.reshape(idx, (idx.shape[0], 1)), dnums,
        slice_sizes=(buf.shape[0], 1), indices_are_sorted=sorted_,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _gather_windows(
    buf: jax.Array, base: jax.Array, rel: jax.Array, length: int,
    slab: int, sorted_: bool,
) -> jax.Array:
    """``concatenate([buf[:, base + r:base + r + length] for r in rel], 1)``
    for a window table (``segment.coalesce_windows``, ``length <=
    LANES``).

    A ``lax.gather`` of ``(batch, length)`` slices would say this in one op,
    but XLA:TPU expands any such gather wider than a few elements into a
    loop of dynamic slices, ~1.6 us a window on a v5e.  A gather of whole
    rows of a ``(rows, LANES)`` array is native there, so the branch views
    the ``slab`` columns from the ``LANES``-aligned ``base`` as rows (the
    compiler copies them into that tiling: the cost grows with ``slab``,
    not with the carry), reads each window as the two rows that hold it,
    and shifts it left by its offset in the first row, one bit of the
    offset at a time (a barrel shifter of ``log2(LANES)`` selects, each
    trimming what no later step can reach).  The slab and its spare last
    row are in bounds by construction (``build_mpmd_executor``)."""
    from repro.codegen.segment import LANES

    batch = buf.shape[0]
    n = rel.shape[0]
    view = jax.lax.reshape(
        jax.lax.dynamic_slice_p.bind(
            buf, np.int32(0), base, slice_sizes=(batch, slab)
        ),
        (batch, slab // LANES, LANES),
    )
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(0, 2), collapsed_slice_dims=(1,), start_index_map=(1,)
    )
    row = jax.lax.div(rel, np.int32(LANES))

    def rows_at(r):
        return jax.lax.gather(
            view, jax.lax.reshape(r, (n, 1)), dnums,
            slice_sizes=(batch, 1, LANES), indices_are_sorted=sorted_,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )

    x = jax.lax.concatenate([rows_at(row), rows_at(row + 1)], 2)
    off = jax.lax.rem(rel, np.int32(LANES))
    for k in reversed(range(LANES.bit_length() - 1)):
        d = 1 << k
        keep = length + d - 1  # what shifts by less than d can still reach
        bit = jax.lax.ne(
            jax.lax.bitwise_and(off, np.int32(d)), np.int32(0)
        )
        x = jax.lax.select(
            jax.lax.broadcast_in_dim(bit, (batch, n, keep), (1,)),
            jax.lax.slice_in_dim(x, d, d + keep, axis=2),
            jax.lax.slice_in_dim(x, 0, keep, axis=2),
        )
    return jax.lax.reshape(x, (batch, n * length))


def _window_slabs(
    starts: np.ndarray, length: int, lane_width: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Slabs for a window table ``starts`` ``(n_occ, n_windows)`` read by
    :func:`_gather_windows` from a carry ``lane_width`` columns wide (a
    multiple of ``LANES``, past every window by at least one row).

    Returns each occurrence's ``LANES``-aligned slab ``base``, the window
    starts ``rel`` relative to it, and the ``slab`` width shared by every
    occurrence: the smallest whole number of rows that holds each
    occurrence's windows, plus a spare row for a window that straddles the
    last, with a base pulled back where the slab would run past the
    carry."""
    from repro.codegen.segment import LANES

    st = starts.astype(np.int64)
    lo = st.min(axis=1) // LANES * LANES
    hull = st.max(axis=1) + length - lo
    slab = -(-int(hull.max()) // LANES) * LANES + LANES
    base = np.minimum(lo, lane_width - slab)
    return (base.astype(np.int32), (st - base[:, None]).astype(np.int32),
            slab)


def _scatter_cols(buf: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    """``buf.at[:, idx].set(vals)`` as one raw ``lax.scatter``.  Rows are
    sorted (plan-side) so XLA can lower runs to memcpys; padding entries
    all point at the dump column — their writes collide in undefined
    order, which is fine because the dump column is never read."""
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(0,), inserted_window_dims=(1,),
        scatter_dims_to_operand_dims=(1,),
    )
    return jax.lax.scatter(
        buf, jax.lax.reshape(idx, (idx.shape[0], 1)), vals, dnums,
        indices_are_sorted=True, unique_indices=False,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _take_row(a: jax.Array, i: jax.Array) -> jax.Array:
    """``a[i]`` for a traced scalar ``i`` as one raw ``lax.gather``.

    ``lax.dynamic_slice``-family ops canonicalize traced start indices
    through jnp ufuncs (a wrap-negative ``where(i < 0, i + n, i)`` per
    call); across hundreds of branch/table lookups that machinery, not the
    math, dominated the executor's trace time.  Indices here are known
    non-negative, so a single PROMISE_IN_BOUNDS gather replaces it."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(a.ndim - 1)),
        collapsed_slice_dims=(0,),
        start_index_map=(0,),
    )
    return jax.lax.gather(
        a, jax.lax.reshape(i, (1,)), dnums,
        slice_sizes=(1, *a.shape[1:]),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _waterfill(loads: np.ndarray, lo: int, hi: int, n: int) -> np.ndarray:
    """Split ``n`` units across slots ``loads[lo:hi+1]`` minimizing the
    resulting per-slot maximum (the counts are returned, ``loads`` is not
    mutated).  Used to flatten retire bursts over their safe scheduling
    windows: the scan body pads every tick to the widest per-tick retire
    table, so the cost of retirement is the *max* load, not the sum."""
    win = np.asarray(loads[lo:hi + 1], np.int64)
    level_lo, level_hi = int(win.min()), int(win.max()) + n
    while level_lo < level_hi:
        mid = (level_lo + level_hi) // 2
        if int(np.maximum(0, mid - win).sum()) >= n:
            level_hi = mid
        else:
            level_lo = mid + 1
    add = np.maximum(0, level_lo - win)
    excess = int(add.sum()) - n
    for i in range(len(add)):
        if excess <= 0:
            break
        take = min(excess, int(add[i]))
        add[i] -= take
        excess -= take
    return add


@dataclasses.dataclass
class PlanTables:
    """Plan-side canonicalization shared by the executor build
    and the static analyzer (:mod:`repro.codegen.analyze`): packed register
    layout, sentinel regions, segment schema and per-node raw gather rows —
    all derived with numpy only.  One derivation serves both, so the
    executor and the happens-before analysis can never disagree about
    where a value lives."""
    offsets: Dict[str, int]
    total: int
    zero_base: int
    neginf_base: int
    dump_col: int
    reg_shapes: Dict[str, Tuple[int, ...]]
    reg_sizes: Dict[str, int]
    birth: Dict[str, int]
    death: Dict[str, int]
    segments: List
    raw_rows: Dict[str, List[np.ndarray]]

    @property
    def zrun(self) -> int:
        return self.neginf_base - self.total

    @property
    def nrun(self) -> int:
        return self.dump_col - self.neginf_base


@dataclasses.dataclass
class SegmentAccess:
    """Build-time access metadata for one segment: every gather the
    kernels will issue (statically redirected through the schedule walk's
    per-worker ``home`` map), the water-filled retire copy tables, and the
    checkpoint materialization pairs.  This is the executor's exact
    memory-access schedule, exposed so the analyzer can verify the tables
    the runtime actually compiles rather than a parallel reconstruction."""
    gin_red: Dict[Tuple[int, int], List[np.ndarray]]  # (tick, worker)
    ret_src: Optional[np.ndarray]   # (n_ticks, m, k) int32, dump-padded
    ret_dst: Optional[np.ndarray]
    retire_elems: int
    mat: Optional[Tuple[np.ndarray, np.ndarray]]  # (m, k) src/dst pairs


@dataclasses.dataclass
class AccessTables:
    """A plan's full access schedule at one ``buffer_depth``."""
    tables: PlanTables
    access: List[SegmentAccess]
    buffer_depth: int
    checkpoint: bool


def plan_tables(
    plan: ExecutionPlan,
    model: CNNModel,
    buffer_depth: int = 1,
    offsets: Optional[Dict[str, int]] = None,
) -> PlanTables:
    """Derive the packed layout, sentinel regions, raw gather rows and
    segment schema for a plan (numpy only — no tracing).  ``offsets``
    overrides the packed layout (the analyzer's mutation oracle uses this
    to alias registers without re-deriving everything else)."""
    from repro.codegen.segment import max_sentinel_runs, node_gather_rows

    reg_shapes = {l.name: tuple(l.out_shape) for l in model.layers}
    reg_sizes = {
        n: (int(np.prod(s)) if s else 1) for n, s in reg_shapes.items()
    }
    birth, death, _sets = plan_liveness(plan, model)
    if offsets is None:
        offsets, total = pack_registers(
            plan, reg_sizes, liveness=(birth, death)
        )
    else:
        total = max(offsets[n] + reg_sizes[n] for n in offsets)

    # raw gather rows once per node; the longest sentinel *runs* size the
    # sentinel regions so every halo-pad run can resolve to a contiguous
    # ascending range and join a span (see segment.resolve_rows)
    raw_rows: Dict[str, List[np.ndarray]] = {}
    zrun = nrun = 1
    for step in plan.steps:
        for seg_nodes in step.compute:
            for node in seg_nodes:
                if node in raw_rows:
                    continue
                rws = node_gather_rows(model, node, offsets)
                raw_rows[node] = rws
                for r in rws:
                    z, nf = max_sentinel_runs(r)
                    zrun, nrun = max(zrun, z), max(nrun, nf)
    # pristine sentinel regions follow the registers: ``[total, total+zrun)``
    # holds 0.0 (virtualized conv/avgpool halo pads), the next ``nrun``
    # columns hold -inf (maxpool halo pads), and the final column is the
    # dump column comm padding gathers from and scatters into — so every
    # index is in bounds and padding can never touch a real register
    zero_base = total
    neginf_base = total + zrun
    dump_col = total + zrun + nrun
    segments = build_segments(
        plan, reg_shapes, offsets, pad_index=dump_col,
        buffer_depth=buffer_depth,
    )
    return PlanTables(
        offsets=offsets, total=total, zero_base=zero_base,
        neginf_base=neginf_base, dump_col=dump_col,
        reg_shapes=reg_shapes, reg_sizes=reg_sizes,
        birth=birth, death=death, segments=segments, raw_rows=raw_rows,
    )


def plan_access_walk(
    plan: ExecutionPlan,
    pt: PlanTables,
    buffer_depth: int = 1,
    checkpoint: bool = False,
) -> List[SegmentAccess]:
    """Replay the tick schedule and emit each segment's access metadata.

    The walk mirrors the runtime tick order exactly — compute first, then
    the retire copies of a reused frame's surviving occupants, then the
    comm rounds' landings — while maintaining the per-worker ``home`` map:
    where each packed register column's current value actually lives (its
    own column, or a staging strip column when the value arrived via a
    comm round and has not been recomputed since).  Every gather table is
    redirected through the home state its tick will observe.

    Rotating frames (``buffer_depth >= 2``) additionally track per-frame
    occupancy: when a shipping tick reuses a frame, every delivery record
    still current in ``home`` is retired — copied back to its packed
    register columns just before the landing DUS clobbers the frame.
    Retiring is always semantics-preserving (the packed column is reserved
    until the value's death, and the runner materializes deliveries there
    anyway), so no liveness analysis is needed: over-retiring a dead value
    writes a column nothing will read again.  Retire bursts are
    water-filled backward across their safe windows (delivery + 1 ..
    eviction) so the uniform scan table pays the mean, not the burst max.
    """
    m = plan.n_workers
    total, dump_col = pt.total, pt.dump_col
    ident = np.arange(total, dtype=np.int32)
    home = np.tile(ident, (m, 1))
    owner = np.full((m, total), -1, np.int64)    # node id of last delivery
    pos2node = np.full(total, -1, np.int64)      # current producer per col
    node_ids: Dict[str, int] = {}

    def nid_of(node: str) -> int:
        i = node_ids.get(node)
        if i is None:
            i = node_ids[node] = len(node_ids)
        return i

    def redirect(w: int, rws: List[np.ndarray]) -> List[np.ndarray]:
        out = []
        for rr in rws:
            a = np.asarray(rr, np.int32).copy()
            msk = a >= 0
            a[msk] = home[w, a[msk]]
            out.append(a)
        return out

    # rotating-frame occupancy: per frame, the (worker, packed cols, strip
    # cols, delivery segment, delivery tick) records currently living there
    frame_occ: List[List[Tuple[int, np.ndarray, np.ndarray, int, int]]] = [
        [] for _ in range(buffer_depth)
    ]
    out: List[SegmentAccess] = []
    for seg_i, seg in enumerate(pt.segments):
        n_ticks = len(seg.ticks)
        act_np = seg.stage.act
        soff = seg.stage.soff
        round_rows = [np.asarray(r.rows) for r in seg.rounds]
        round_slots = [np.asarray(r.slot) for r in seg.rounds]
        # (worker, strip cols, packed cols, window lo, window hi): retire
        # chunks with the tick range each copy may legally run in
        ret_chunks: List[
            Tuple[int, np.ndarray, np.ndarray, int, int]
        ] = []
        gin_red: Dict[Tuple[int, int], List[np.ndarray]] = {}
        for t, row in enumerate(seg.ticks):
            for w, node in enumerate(row):
                if node is None:
                    continue
                gin_red[(t, w)] = redirect(w, pt.raw_rows[node])
                off_n, sz_n = pt.offsets[node], pt.reg_sizes[node]
                home[w, off_n:off_n + sz_n] = ident[off_n:off_n + sz_n]
                pos2node[off_n:off_n + sz_n] = nid_of(node)
            if buffer_depth > 1 and seg.stage.payloads[t]:
                # this shipping tick reuses rotating frame ``fr``: retire
                # its still-current occupants to their packed columns
                # (compute at this tick already resolved its gathers
                # against the strips — the runtime retire copy runs
                # after the kernel write, before the landing DUS)
                fr = int(seg.stage.frame_of[t])
                for (w, pcs, scs, d_seg, d_t) in frame_occ[fr]:
                    valid = home[w, pcs] == scs
                    if valid.any():
                        # a pair still current now was current ever since
                        # its delivery (``home`` entries are only touched
                        # by delivery, compute reuse, and retirement), so
                        # the copy may run at any tick after the strip
                        # landed and no later than this one
                        lo = d_t + 1 if d_seg == seg_i else 0
                        ret_chunks.append(
                            (w, scs[valid], pcs[valid], min(lo, t), t)
                        )
                        home[w, pcs[valid]] = pcs[valid]
                frame_occ[fr] = []
            for r_i, r in enumerate(seg.rounds):
                if not act_np[t, r_i]:
                    continue
                strip = soff[t, r_i]
                for w in range(m):
                    rw = round_rows[r_i][round_slots[r_i][t, w]]
                    real = np.nonzero(rw != dump_col)[0]
                    if not real.size:
                        continue
                    cols = rw[real]
                    s = (w - r.delta) % m
                    if not (home[s, cols] == cols).all():
                        raise NotImplementedError(
                            "staged comm: sender would forward a value it "
                            "received rather than produced"
                        )
                    strips = strip + real.astype(np.int32)
                    home[w, cols] = strips
                    owner[w, cols] = pos2node[cols]
                    if buffer_depth > 1:
                        frame_occ[int(seg.stage.frame_of[t])].append(
                            (w, np.asarray(cols, np.int32), strips, seg_i, t)
                        )
        # per-tick retire tables (rotating frames only): dst-sorted
        # (strip, packed) column pairs per worker, dump-padded to the
        # segment max — one gather + one sorted scatter per tick moves a
        # reused frame's surviving occupants home.  The scan body pads
        # every tick to the segment's widest retire, so eviction bursts
        # are first water-filled backward across their safe windows
        # (delivery + 1 .. eviction), flattening the per-tick maximum
        # toward the mean instead of the burst size.
        ret_by_tw: Dict[Tuple[int, int], List[Tuple[np.ndarray, np.ndarray]]]
        ret_by_tw = {}
        if ret_chunks:
            loads = np.zeros((n_ticks, m), np.int64)
            for (w, scs, pcs, lo, hi) in ret_chunks:
                counts = _waterfill(loads[:, w], lo, hi, len(scs))
                off = 0
                for t_r, c in zip(range(lo, hi + 1), counts):
                    c = int(c)
                    if not c:
                        continue
                    ret_by_tw.setdefault((t_r, w), []).append(
                        (scs[off:off + c], pcs[off:off + c])
                    )
                    loads[t_r, w] += c
                    off += c
        retire_elems = 0
        ret_k = max(
            [0] + [
                sum(len(s) for (s, _d) in chunks)
                for chunks in ret_by_tw.values()
            ]
        )
        ret_src = ret_dst = None
        if ret_k:
            ret_src = np.full((n_ticks, m, ret_k), dump_col, np.int32)
            ret_dst = np.full((n_ticks, m, ret_k), dump_col, np.int32)
            for (t, w), chunks in ret_by_tw.items():
                scs = np.concatenate([s for (s, _d) in chunks])
                pcs = np.concatenate([d for (_s, d) in chunks])
                order = np.argsort(pcs, kind="stable")
                ret_src[t, w, : len(scs)] = scs[order]
                ret_dst[t, w, : len(pcs)] = pcs[order]
                retire_elems += len(pcs)
        # barrier materialization (checkpoint runs only): copy every
        # staged delivery back to its packed column, so snapshots stay
        # bit-equivalent to the reference runner's barrier state (which
        # writes deliveries straight into the register file, live or not)
        # and fault-time replan/resume (migrate_registers) sees a
        # canonical register file
        mat = None
        if checkpoint:
            pairs = []
            for w in range(m):
                moved = np.nonzero(home[w] != ident)[0]
                keep = sorted(p for p in moved if owner[w, p] >= 0)
                pairs.append([(home[w, p], p) for p in keep])
            k_max = max(len(p) for p in pairs)
            if k_max:
                src = np.full((m, k_max), dump_col, np.int32)
                dst = np.full((m, k_max), dump_col, np.int32)
                for w, pr in enumerate(pairs):
                    for j, (s_c, d_c) in enumerate(pr):
                        src[w, j] = s_c
                        dst[w, j] = d_c
                mat = (src, dst)
        out.append(SegmentAccess(
            gin_red=gin_red, ret_src=ret_src, ret_dst=ret_dst,
            retire_elems=retire_elems, mat=mat,
        ))
    return out


def segment_access_tables(
    plan: ExecutionPlan,
    model: CNNModel,
    *,
    buffer_depth: int = 1,
    checkpoint: bool = True,
    offsets: Optional[Dict[str, int]] = None,
) -> AccessTables:
    """The executor's access metadata for one plan at one ``buffer_depth``
    — the single entry point the happens-before analyzer consumes."""
    pt = plan_tables(
        plan, model, buffer_depth=buffer_depth, offsets=offsets,
    )
    access = plan_access_walk(
        plan, pt, buffer_depth=buffer_depth, checkpoint=checkpoint,
    )
    return AccessTables(
        tables=pt, access=access, buffer_depth=buffer_depth,
        checkpoint=checkpoint,
    )


def _make_branch(
    sig, tab, x, batch: int, gin_kinds, pidx_identity: bool,
    wland: int = 1,
):
    """One switch branch: assemble the signature's input blocks from the
    packed buffer through the occurrence's index tables, run the shared
    kernel with its operand params, and return the output as a value.

    Branches read the carry but do **not** return it: a ``lax.switch``
    whose branches thread the full carry lowers to nested conditionals
    that each copy the buffer (ruinously expensive on a wide carry), so
    every branch instead returns a small ``(y_pad, start)`` pair and the
    caller performs one in-place ``dynamic_update_slice`` outside the
    switch.  ``y_pad`` is the kernel output padded to the landing width
    ``wland`` of the branch's scan (the widest register it lands, see
    ``segment.land_runs``) with a *self-restoring tail* — the current
    buffer contents at ``[start + w, start + wland)`` — so the
    uniform-width write never corrupts neighbouring columns.

    Per-slot assembly is span-coalesced (``gin_kinds[j] == ("spans", lens,
    kinds)``): each contiguous piece of the slot's gather rows is one
    memcpy-width ``dynamic_slice`` from a per-occurrence starts table, the
    genuinely scattered remainder (if any) is served by a single element
    gather cut up with static slices, and the pieces concatenate in row
    order.  Slots whose rows stay scattered past the coalescing thresholds
    but split into equal contiguous windows (``gin_kinds[j] == ("windows",
    length, slab, sorted_)``) gather their windows from a per-occurrence
    slab base and window-starts table (``_gather_windows``); the rest
    (``gin_kinds[j] == "rows"``) fall back to one whole-slot element
    gather.
    ``pidx_identity`` elides the parameter-dedup indirection when every
    occurrence carries distinct parameters anyway.

    The branch's phases are named scopes (``assemble``, ``params``,
    ``kernel``, ``land``), so a device trace attributes its ops by phase."""
    from repro.codegen.segment import make_kernel

    kern = make_kernel(sig)
    slot_shapes = sig[1]

    def branch(buf: jax.Array, oc):
        with jax.named_scope("assemble"):
            ins = []
            for j, shp in enumerate(slot_shapes):
                kind = gin_kinds[j]
                if kind == "rows":
                    flat = _gather_cols(buf, _take_row(tab["gin"][j], oc))
                elif kind[0] == "windows":
                    _tag, ln, slab, srt = kind
                    g = tab["gin"][j]
                    flat = _gather_windows(
                        buf, _take_row(g["base"], oc),
                        _take_row(g["rel"], oc), ln, slab, srt,
                    )
                else:
                    _tag, lens, kinds = kind
                    g = tab["gin"][j]
                    starts = (
                        _take_row(g["starts"], oc) if "starts" in g else None
                    )
                    rem = (
                        _gather_cols(buf, _take_row(g["rem"], oc))
                        if "rem" in g else None
                    )
                    pieces = []
                    si = ri = 0
                    for ln, k in zip(lens, kinds):
                        if k == "span":
                            st = jax.lax.index_in_dim(starts, si, 0, False)
                            si += 1
                            # primitive bind skips traced-start
                            # canonicalization ufuncs; starts are
                            # non-negative by construction
                            pieces.append(jax.lax.dynamic_slice_p.bind(
                                buf, np.int32(0), st, slice_sizes=(batch, ln)
                            ))
                        else:
                            pieces.append(
                                jax.lax.slice(rem, (0, ri), (batch, ri + ln))
                            )
                            ri += ln
                    flat = (
                        pieces[0] if len(pieces) == 1
                        else jax.lax.concatenate(pieces, 1)
                    )
                ins.append(jax.lax.reshape(flat, (batch, *shp)))
        with jax.named_scope("params"):
            pops = ()
            if "p" in tab:
                pi = oc if pidx_identity else _take_row(tab["pidx"], oc)
                pops = [_take_row(p, pi) for p in tab["p"]]
        with jax.named_scope("kernel"):
            y = kern(x, ins, pops).astype(jnp.float32)
            w = int(np.prod(y.shape)) // batch
            y2 = jax.lax.reshape(y, (batch, w))
        with jax.named_scope("land"):
            st = _take_row(tab["out"], oc)
            if w < wland:
                # self-restoring tail: read back what the uniform-width
                # write is about to overwrite, so the pad columns keep
                # their values
                tail = jax.lax.dynamic_slice_p.bind(
                    buf, np.int32(0), jax.lax.add(st, np.int32(w)),
                    slice_sizes=(batch, wland - w),
                )
                y2 = jax.lax.concatenate([y2, tail], 1)
        return y2, st

    return branch


def build_mpmd_executor(
    plan: ExecutionPlan,
    model: CNNModel,
    params,
    mesh: jax.sharding.Mesh,
    axis: str = "workers",
    batch: int = 1,
    checkpoint: bool = False,
    buffer_depth: int = 1,
) -> Callable[[jax.Array], jax.Array]:
    """Compile the plan into a jitted shard_map function ``f(x) -> y``.

    ``mesh`` must have ``axis`` of size ``plan.n_workers``.  Input ``x`` and
    output are replicated over the axis (P() specs); the result equals the
    sequential reference on every worker (final broadcast via psum).  The
    input's leading dimension must equal ``batch`` — it is baked into the
    register layout, so the returned function validates it eagerly instead
    of failing deep inside shard_map.

    The plan's consecutive transfer-only supersteps are merged first
    (``coalesce_transfer_steps``, idempotent).  Plan-side canonicalization
    (``pack_registers``/``build_segments``) supplies the packed register
    layout and the per-segment tick/round schema; this builder adds the
    model-side compute tables — per-segment kernel lists keyed by
    structural signature, with per-occurrence operand tables (register
    offsets, span starts, deduplicated parameter slices) — and emits one
    scan per landing run of each segment (``segment.land_runs``).  All
    tables are passed as jit arguments rather than baked as constants, so
    tracing cost stays bounded by the number of distinct signatures.

    Two options:

    * ``checkpoint=True`` makes the executor additionally return its packed
      register carries at every segment boundary: ``f(x) -> (y, snaps)``
      with ``snaps`` of shape ``(n_segments, n_workers, batch, width)`` —
      the fault-tolerant runtime's superstep checkpoints, taken at the
      barriers the scan already synchronizes on.
    * ``buffer_depth`` (default 1 = write-once staging) selects the
      **streaming** mode at 2/4: comm payloads land in that many rotating
      staging frames (``SegmentStaging``) instead of write-once strips,
      per-tick retire tables copy a frame's still-live occupants back to
      their packed columns before reuse, and the packed carry is
      **donated** across calls (``donate_argnums`` + in-trace re-init)
      instead of re-materialized.  Outputs, and checkpoint snapshots'
      register region, are bit-identical across depths; the carry width
      is bounded by ``buffer_depth`` × the largest per-tick payload.

    The returned callable exposes ``.layout`` (the
    :class:`~repro.codegen.plan.RegisterLayout` of the carry, sentinel
    columns excluded), ``.width``, ``.segment_spans`` and
    ``.checkpoint_steps`` so recovery code can interpret the snapshots
    without re-deriving the packing, and ``.segment_stats`` (static
    span/window/round tables per segment).  Its device phases are named
    scopes (``_run_all``), so a profiler trace of the served program
    splits its time by segment and phase.
    """
    m = plan.n_workers
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in mesh_axes:
        raise KeyError(
            f"mesh has no axis named {axis!r} (available axes: "
            f"{tuple(mesh.axis_names)}); build the mesh with "
            f"jax.make_mesh(({m},), ({axis!r},)) or pass the executor "
            f"axis=<your axis name>"
        )
    if mesh_axes[axis] != m:
        raise ValueError(
            f"mesh axis {axis!r} has size {mesh_axes[axis]} but the plan "
            f"schedules {m} workers; build the mesh with "
            f"jax.make_mesh(({m},), ({axis!r},))"
        )
    if not (isinstance(buffer_depth, int) and buffer_depth >= 1):
        raise ValueError(
            f"buffer_depth must be a positive int (1 = write-once staging, "
            f"2/4 = double/quad-buffered streaming), got {buffer_depth!r}"
        )
    plan = coalesce_transfer_steps(plan)

    from repro.codegen.segment import (
        LANES,
        LAND_SCAN_COST,
        coalesce_spans,
        coalesce_windows,
        land_runs,
        node_signature,
        param_slices,
        resolve_rows,
    )

    # plan-side canonicalization + the build-time schedule walk (shared
    # with codegen/analyze.py, which verifies these exact tables)
    pt = plan_tables(plan, model, buffer_depth=buffer_depth)
    offsets, total = pt.offsets, pt.total
    reg_shapes, reg_sizes = pt.reg_shapes, pt.reg_sizes
    zero_base, neginf_base = pt.zero_base, pt.neginf_base
    dump_col, nrun = pt.dump_col, pt.nrun
    segments = pt.segments
    access = plan_access_walk(
        plan, pt, buffer_depth=buffer_depth, checkpoint=checkpoint,
    )

    # staging layout (plan-side, ``SegmentStaging``): every comm round
    # lands its payload in a staging strip via an in-place
    # dynamic_update_slice instead of an element scatter (scatter costs
    # scale per element on CPU; an in-place DUS is a memcpy).  Strips are
    # allocated tick-major, so one tick's fires form a single contiguous
    # block: the runtime ships a whole tick's rounds through one
    # **pattern switch** (one branch per distinct active-round set,
    # executing exactly its fires, no per-round idle conds) and lands the
    # concatenated payload with one DUS at the tick's block base.
    # ``buffer_depth == 1`` gives every fire a private write-once strip;
    # ``buffer_depth >= 2`` rotates the landing blocks over that many
    # frames, and the schedule walk below emits per-tick **retire
    # tables** copying a frame's still-live occupants back to their
    # packed register columns just before the frame is reused.
    # Consumers of delivered values read the strips directly: the
    # per-occurrence gather tables are statically redirected through a
    # per-worker "home" map maintained by the build-time schedule walk
    # below, so no runtime receive-side indexing exists at all.
    seg_patterns = []
    seg_patids = []
    for seg in segments:
        n_ticks = len(seg.ticks)
        act_np = seg.stage.act
        patterns: List[Tuple[int, ...]] = []
        pat_index: Dict[Tuple[int, ...], int] = {}
        pat_ids = np.zeros(n_ticks, np.int32)
        for t in range(n_ticks):
            key = tuple(np.nonzero(act_np[t])[0].tolist())
            pid = pat_index.setdefault(key, len(pat_index))
            if pid == len(patterns):
                patterns.append(key)
            pat_ids[t] = pid
        seg_patterns.append(tuple(patterns))
        seg_patids.append(pat_ids)
    # the uniform-width output write needs `start + wland <= width` for
    # every output offset (starts never exceed `total`); the staging
    # extent already covers every tick block plus its read-back tail
    wmax = max(
        [1] + [
            reg_sizes[n]
            for seg in segments for row in seg.ticks for n in row if n
        ]
    )
    stage_end = segments[0].stage.stage_end if segments else dump_col + 1
    width = max(stage_end, total + wmax)
    # window gathers read LANES-aligned slabs with a spare last row: where
    # any slot takes them, the carry grows to a whole number of rows plus
    # one, so every slab fits inside it
    lane_width = -(-width // LANES) * LANES + LANES
    windowed = False

    sig_cache: Dict[str, Tuple] = {}

    def sig_of(node: str):
        if node not in sig_cache:
            sig_cache[node] = node_signature(model, node)
        return sig_cache[node]

    seg_meta = []     # (sig_list, sig_infos, deltas, lengths, patterns,
                      #  lmax, has_ret, runs); runs: (start, stop, sids,
                      #  single, wland, idle_st) per landing run
    seg_tables = []   # per segment: pytree of jnp operand tables (jit args)
    seg_stats = []    # per segment: static span/round statistics
    for seg_i, seg in enumerate(segments):
        n_ticks = len(seg.ticks)
        act_np = seg.stage.act
        patterns = seg_patterns[seg_i]
        acc = access[seg_i]
        sig_list: List = []
        sig_index: Dict = {}
        occs: List[Dict] = []
        sig_tab = np.zeros((n_ticks, m), np.int32)
        occ_tab = np.zeros((n_ticks, m), np.int32)
        for t, row in enumerate(seg.ticks):
            for w, node in enumerate(row):
                if node is None:
                    continue
                sig, pkey = sig_of(node)
                sid = sig_index.get(sig)
                if sid is None:
                    sid = sig_index[sig] = len(sig_list)
                    sig_list.append(sig)
                    occs.append({"gin": [], "out": [], "pidx": [],
                                 "uniq": {}, "parrs": []})
                o = occs[sid]
                o["gin"].append(acc.gin_red[(t, w)])
                o["out"].append(offsets[node])
                if pkey is not None:
                    pi = o["uniq"].get(pkey)
                    if pi is None:
                        pi = o["uniq"][pkey] = len(o["parrs"])
                        o["parrs"].append(param_slices(model, params, pkey))
                    o["pidx"].append(pi)
                sig_tab[t, w] = sid + 1  # 0 is the idle branch
                occ_tab[t, w] = len(o["out"]) - 1
        sig_tabs = []
        sig_infos = []
        span_elems = gather_elems = window_elems = window_indices = 0
        for sig, o in zip(sig_list, occs):
            n_slots = len(sig[1])
            gin = []
            gin_kinds = []
            for j in range(n_slots):
                rows = resolve_rows(
                    np.stack([r[j] for r in o["gin"]]),
                    zero_base, neginf_base,
                )
                span = coalesce_spans(rows) if rows.shape[1] else None
                win = None
                if span is None:
                    # scattered past the span thresholds: equal windows?
                    win = coalesce_windows(rows)
                gather_elems += rows.size
                if span is not None:
                    span_elems += int(round(span.coverage * rows.size))
                    g = {"starts": jnp.asarray(span.starts)}
                    if span.rem.size:
                        g["rem"] = jnp.asarray(span.rem)
                    gin.append(g)
                    gin_kinds.append(("spans", span.lens, span.kinds))
                elif win is not None:
                    window_elems += rows.size
                    window_indices += win.starts.size
                    base, rel, slab = _window_slabs(
                        win.starts, win.length, lane_width)
                    gin.append({"base": jnp.asarray(base),
                                "rel": jnp.asarray(rel)})
                    gin_kinds.append(
                        ("windows", win.length, slab, win.sorted_))
                    windowed = True
                else:
                    gin.append(jnp.asarray(rows))
                    gin_kinds.append("rows")
            tab = {
                "gin": tuple(gin),
                "out": jnp.asarray(np.asarray(o["out"], np.int32)),
            }
            pidx_identity = True
            if o["parrs"]:
                pidx = np.asarray(o["pidx"], np.int32)
                pidx_identity = bool((pidx == np.arange(len(pidx))).all())
                if not pidx_identity:
                    tab["pidx"] = jnp.asarray(pidx)
                tab["p"] = tuple(
                    jnp.asarray(np.stack([pa[j] for pa in o["parrs"]]))
                    for j in range(len(o["parrs"][0]))
                )
            sig_tabs.append(tab)
            sig_infos.append((tuple(gin_kinds), pidx_identity))
        lmax = max(
            [0] + [
                sum(seg.rounds[r].length for r in pat) for pat in patterns
            ]
        )
        xs = {"occ": jnp.asarray(occ_tab)}
        if seg.rounds:
            xs["slot"] = jnp.asarray(
                np.stack([r.slot for r in seg.rounds], axis=1)
            )  # (n_ticks, n_rounds, m)
            # per-tick staging block base + active-round pattern id: the
            # comm pattern switch dispatches on the id (tick data,
            # identical on every worker — all workers take the same
            # branch, so each branch's collectives stay matched)
            xs["base"] = jnp.asarray(seg.stage.base)
            if len(patterns) > 1:
                xs["pat"] = jnp.asarray(seg_patids[seg_i])
        # per-tick retire tables + barrier materialization pairs come from
        # the shared schedule walk (plan_access_walk) — the same tables
        # codegen/analyze.py verifies hazard-free
        ret_k = acc.ret_src is not None
        if ret_k:
            xs["rsrc"] = jnp.asarray(acc.ret_src)
            xs["rdst"] = jnp.asarray(acc.ret_dst)
        retire_elems = acc.retire_elems
        mat = None
        if acc.mat is not None:
            mat = (jnp.asarray(acc.mat[0]), jnp.asarray(acc.mat[1]))
        # landing runs: consecutive scans over the segment's tick rows,
        # each landing at its own widest tick (the widest register any
        # worker lands there), so every worker cuts at the same ticks and
        # the comm rounds' collectives stay matched
        tick_w = [max([0] + [reg_sizes[n] for n in row if n])
                  for row in seg.ticks]
        runs = []
        land_elems = 0
        sig_run = np.zeros_like(sig_tab)  # each cell's branch in its run
        for a, b in land_runs(tick_w, LAND_SCAN_COST):
            # the run's switch holds only the signatures it runs, in the
            # segment's order (so one run over the segment is the
            # segment's switch)
            sids = sorted({int(s) - 1 for s in sig_tab[a:b].ravel() if s})
            remap = np.zeros(len(sig_list) + 1, np.int32)
            remap[[s + 1 for s in sids]] = np.arange(1, len(sids) + 1)
            sig_run[a:b] = remap[sig_tab[a:b]]
            # single-structure specialization: one signature and no idle
            # cells means every tick runs the same branch — skip the
            # lax.switch and its operand plumbing entirely
            single = len(sids) == 1 and bool((sig_tab[a:b] != 0).all())
            wland = max([1] + tick_w[a:b])
            land_elems += (b - a) * wland
            runs.append((a, b, tuple(sids), single, wland, width - wland))
        if not all(r[3] for r in runs):
            xs["sig"] = jnp.asarray(sig_run)
        seg_meta.append((
            sig_list, sig_infos, tuple(r.delta for r in seg.rounds),
            tuple(r.length for r in seg.rounds), patterns,
            lmax, bool(ret_k), tuple(runs),
        ))
        seg_tables.append({
            "xs": xs,
            "sigs": sig_tabs,
            "rows": tuple(jnp.asarray(r.rows) for r in seg.rounds),
            **({"mat": mat} if mat is not None else {}),
        })
        real_elems = shipped_elems = 0
        for r_i, r in enumerate(seg.rounds):
            per_row = (np.asarray(r.rows) != dump_col).sum(axis=1)
            real_elems += int(per_row[np.asarray(r.slot)].sum())
            shipped_elems += int(act_np[:, r_i].sum()) * r.length * m
        seg_stats.append({
            "steps": (seg.start, seg.stop),
            "ticks": n_ticks,
            "sigs": len(sig_list),
            "single_structure": single,
            "rounds": len(seg.rounds),
            "round_lengths": [r.length for r in seg.rounds],
            "round_fires": int(act_np.sum()),
            "comm_patterns": len(patterns),
            "comm_real_elems": real_elems,
            "comm_shipped_elems": shipped_elems,
            "stage_elems": int(sum(
                int(act_np[:, r_i].sum()) * r.length
                for r_i, r in enumerate(seg.rounds)
            )),
            # resident staging footprint (global, counted once — NOT per
            # fire): write-once strips for depth 1, depth * frame for the
            # rotating layout; plus the retire traffic rotation adds
            "buffer_depth": buffer_depth,
            "peak_staging_elems": int(stage_end - (dump_col + 1)),
            "retire_elems": retire_elems,
            "span_elems": span_elems,
            "gather_elems": gather_elems,
            "span_coverage": (
                span_elems / gather_elems if gather_elems else 1.0
            ),
            "window_elems": window_elems,
            "window_indices": window_indices,
            "window_coverage": (
                window_elems / gather_elems if gather_elems else 0.0
            ),
            # landing writes: scans, columns landed per request (each
            # tick at its run's width), and the ticks' own widest outputs
            # over that
            "land_runs": len(runs),
            "land_elems": land_elems,
            "land_efficiency": sum(tick_w) / land_elems,
        })

    if windowed:
        width = lane_width
    sink_off = offsets[plan.sink]
    sink_sz = reg_sizes[plan.sink]
    sink_shape = reg_shapes[plan.sink]

    def run_segment(buf, x, meta, tabs):
        """Scan one segment's ticks over the packed carry, one scan per
        landing run (``segment.land_runs``), in tick order.

        Every per-tick write is an in-place ``dynamic_update_slice``: the
        switch returns ``(y_pad, start)`` values (see ``_make_branch``)
        and the comm **pattern switch** returns the tick's concatenated
        round payloads, landed as one block at the tick's staging base.
        The carry is never threaded through a conditional, so the scan
        body is free of buffer copies, element scatters, and per-round
        idle conds.

        Phases are named scopes: the branches' own (``_make_branch``),
        ``land`` for the idle branch and the tick's write, ``retire`` and
        ``comm``."""
        for run in meta[-1]:
            buf = scan_run(buf, x, meta, tabs, run)
        return buf

    def scan_run(buf, x, meta, tabs, run):
        """One landing run's ticks as one ``lax.scan``: its switch holds
        the run's signatures and every branch lands ``wland`` columns.

        The run's rows of the segment's tick tables are sliced here, in
        the program, and not passed apart: every table is an argument that
        each call places on each worker's device, so the argument count
        stays the segment's whatever the cut."""
        wid = jax.lax.axis_index(axis)
        (sig_list, sig_infos, deltas, lengths, patterns,
         lmax, has_ret, _runs) = meta
        a, b, sids, single, wland, idle_st = run
        xs = {
            k: v if (a, b) == (0, v.shape[0])
            else jax.lax.slice_in_dim(v, a, b)
            for k, v in tabs["xs"].items() if k != "sig" or not single
        }
        rows = tabs["rows"]

        def idle(b, oc):
            # self-restoring no-op: read wland columns, write them back
            with jax.named_scope("land"):
                return (
                    jax.lax.slice(b, (0, idle_st), (batch, idle_st + wland)),
                    jnp.asarray(idle_st, jnp.int32),
                )

        branches = [idle]
        for s in sids:
            branches.append(_make_branch(
                sig_list[s], tabs["sigs"][s], x, batch, *sig_infos[s],
                wland=wland,
            ))

        def body(b, tk):
            oc = _take_row(tk["occ"], wid)
            if single:
                y, st = branches[1](b, oc)
            else:
                y, st = jax.lax.switch(
                    _take_row(tk["sig"], wid), branches, b, oc
                )
            with jax.named_scope("land"):
                b = jax.lax.dynamic_update_slice_p.bind(
                    b, y, np.int32(0), st
                )
            if not deltas:
                return b, None
            if has_ret:
                # rotating frames: move the reused frame's surviving
                # occupants back to their packed columns before this
                # tick's landing DUS clobbers them (pad lanes shuttle
                # the dump column's don't-care bytes)
                with jax.named_scope("retire"):
                    b = _scatter_cols(
                        b, _take_row(tk["rdst"], wid),
                        _gather_cols(b, _take_row(tk["rsrc"], wid)),
                    )

            # comm pattern switch: each branch executes exactly the ring
            # rounds active on its ticks — worker w ships to w + delta,
            # the source gathers the row of its *destination* (the row
            # describes what the destination receives, and a register's
            # offset is the same on every worker) — and concatenates the
            # payloads in round order, padding to the segment's widest
            # tick block with a self-restoring tail.  One DUS lands the
            # whole block at the tick's staging base; ticks with no
            # active round reduce to a read-back of their base columns.
            def mk_pat(pat, b=b, tk=tk):
                def branch():
                    mvs = []
                    for r in pat:
                        delta = deltas[r]
                        slot_row = jax.lax.index_in_dim(
                            tk["slot"], r, 0, False
                        )
                        dst = jax.lax.rem(
                            jax.lax.add(wid, np.int32(delta)), np.int32(m)
                        )
                        send = _take_row(rows[r], _take_row(slot_row, dst))
                        mvs.append(jax.lax.ppermute(
                            _gather_cols(b, send, sorted_=True), axis,
                            [(i, (i + delta) % m) for i in range(m)],
                        ))
                    lp = sum(lengths[r] for r in pat)
                    if lp < lmax:
                        mvs.append(jax.lax.dynamic_slice_p.bind(
                            b, np.int32(0),
                            jax.lax.add(tk["base"], np.int32(lp)),
                            slice_sizes=(batch, lmax - lp),
                        ))
                    if len(mvs) == 1:
                        return mvs[0]
                    return jax.lax.concatenate(mvs, 1)
                return branch

            with jax.named_scope("comm"):
                if len(patterns) == 1:
                    mv = mk_pat(patterns[0])()
                else:
                    mv = jax.lax.switch(
                        tk["pat"], [mk_pat(p) for p in patterns]
                    )
                b = jax.lax.dynamic_update_slice_p.bind(
                    b, mv, np.int32(0), tk["base"]
                )
            return b, None

        buf, _ = jax.lax.scan(body, buf, xs)
        return buf

    def init_buf() -> jax.Array:
        buf = jnp.zeros((batch, width), jnp.float32)
        return jax.lax.dynamic_update_slice(
            buf, jnp.full((batch, nrun), -jnp.inf), (0, neginf_base)
        )

    def _run_all(x: jax.Array, buf: jax.Array, tables, wid):
        # device phases are named scopes: ``seg<k>`` per segment, with the
        # phases of ``run_segment`` and ``checkpoint`` inside, then
        # ``output``
        snaps: List[jax.Array] = []
        for k, (meta, tabs) in enumerate(zip(seg_meta, tables)):
            with jax.named_scope(f"seg{k}"):
                buf = run_segment(buf, x, meta, tabs)
                if checkpoint:
                    with jax.named_scope("checkpoint"):
                        if "mat" in tabs:
                            src, dst = tabs["mat"]
                            buf = _scatter_cols(
                                buf, _take_row(dst, wid),
                                _gather_cols(buf, _take_row(src, wid)),
                            )
                    snaps.append(buf)
        with jax.named_scope("output"):
            out = jax.lax.reshape(
                jax.lax.slice(
                    buf, (0, sink_off), (batch, sink_off + sink_sz)
                ),
                (batch, *sink_shape),
            )
            out = jnp.where(wid == plan.sink_worker, out, 0.0)
            out = jax.lax.psum(out, axis)
        return out, buf, snaps

    def worker_fn(x: jax.Array, tables):
        wid = jax.lax.axis_index(axis)
        out, _buf, snaps = _run_all(x, init_buf(), tables, wid)
        if checkpoint:
            # (n_segments, 1, batch, width) per worker; the worker axis is
            # concatenated by shard_map into (n_segments, m, batch, width).
            # Left unscoped: a scope here renames instructions of the
            # compiled multi-worker program
            return out, jnp.stack(snaps)[:, None]
        return out

    def worker_fn_stream(x: jax.Array, carry, tables):
        # streaming (buffer_depth >= 2): the previous call's final carry
        # arrives as a donated argument and is re-initialized in place —
        # zero the register + zero-sentinel prefix, rewrite the -inf
        # block.  Staging columns keep the previous call's bytes: every
        # strip is written before it is read within a call, and idle-tick
        # tails are value-preserving read-backs, so XLA aliases the
        # donated buffer instead of materializing a fresh one.
        wid = jax.lax.axis_index(axis)
        b = jax.lax.squeeze(carry, (0,))
        b = jax.lax.dynamic_update_slice_p.bind(
            b, jnp.zeros((batch, neginf_base), jnp.float32),
            np.int32(0), np.int32(0),
        )
        b = jax.lax.dynamic_update_slice_p.bind(
            b, jnp.full((batch, nrun), -jnp.inf),
            np.int32(0), np.int32(neginf_base),
        )
        out, b, snaps = _run_all(x, b, tables, wid)
        b = jax.lax.expand_dims(b, (0,))
        if checkpoint:
            return out, b, jnp.stack(snaps)[:, None]
        return out, b

    p_rep = jax.sharding.PartitionSpec()
    if buffer_depth == 1:
        out_specs = (
            (p_rep, jax.sharding.PartitionSpec(None, axis))
            if checkpoint else p_rep
        )
        fn = _shard_map(
            worker_fn, mesh=mesh, in_specs=(p_rep, p_rep),
            out_specs=out_specs,
        )
        wrapped = _with_batch_check(
            jax.jit(fn), batch, extra_args=(seg_tables,)
        )
    else:
        p_carry = jax.sharding.PartitionSpec(axis)
        out_specs = (
            (p_rep, p_carry, jax.sharding.PartitionSpec(None, axis))
            if checkpoint else (p_rep, p_carry)
        )
        fn = _shard_map(
            worker_fn_stream, mesh=mesh,
            in_specs=(p_rep, p_carry, p_rep), out_specs=out_specs,
        )
        wrapped = _with_carry_feedback(
            jax.jit(fn, donate_argnums=(1,)), batch,
            (m, batch, width), seg_tables, checkpoint,
        )
    wrapped.layout = RegisterLayout(
        offsets=offsets, total=total,
        shapes={n: reg_shapes[n] for n in offsets},
    )
    wrapped.width = width
    wrapped.segment_spans = tuple((s.start, s.stop) for s in segments)
    # superstep each checkpoint snapshot is the entering barrier of:
    # snaps[k] == the runner's barrier entering superstep checkpoint_steps[k]
    # (migrate_registers takes exactly this (snapshot, step) pair)
    wrapped.checkpoint_steps = tuple(s.stop for s in segments)
    wrapped.segment_stats = seg_stats
    return wrapped
