"""Plan specialization: lower an ExecutionPlan into a rollout *program*.

The paper's design flow does not stop at knowing the matrix structure — it
compiles the structure *into the computation*: constant propagation deletes
work (zero digits cost nothing), CSD logic minimization strength-reduces
what remains, and the matrix stays spatially resident so it is never
re-fetched.  This module is the software synthesis step that buys the
:class:`~repro.plan.plan.ExecutionPlan`'s static knowledge back as speed.
``specialize_rollout`` turns one plan into a :class:`RolloutProgram`:

* **regime selection** — when every kept weight tile fits the VMEM budget
  the program is ``resident``: tiles are hoisted on-chip once and the
  ``(T, B_tiles)`` grid iterates with *zero* per-step weight traffic.
  Otherwise the program is ``pipelined``: output columns are packed into
  bands of at most half the budget, so the Pallas pipeline can prefetch
  band ``k+1`` while band ``k`` reduces (double buffering).
* **constant-propagated CSD folding** (int8 modes) — the per-plane
  ``2^w`` scales and digit signs are trace-time constants, so all planes
  of a block that stay on the matmul path fold into ONE int8 tile
  (``sum_w 2^w d_w`` — exactly the quantized block, by construction):
  one int32 MXU product replaces ``width`` shifted plane products, with
  bit-identical results because int32 accumulation is exact.
* **shift-add strength reduction** — a digit plane of a block whose
  ``ones`` count falls below the plan-computed crossover skips the matmul
  entirely: its few set digits are emitted as static shift-add terms
  (``acc[:, j] += ±(x[:, i] << w)``), the software mirror of the paper's
  synthesized adder trees.
* **scattered table** — the same digits, summed per matrix entry, as
  static ELL tables (:class:`ScatteredTable`): per output column the
  source rows and values, padded to the largest in-degree.  A consumer
  that can gather (the XLA backend) reads the remainder from the table in
  one gather-multiply-accumulate, so its program does not grow with the
  matrix's nonzeros or digits; the Pallas kernel, which cannot gather,
  unrolls the shift-add terms.
* **batch tiling** — the batch axis splits into tiles of at most
  ``batch_tile_max`` rows, so a batch-64 rollout runs as grid-parallel
  batch tiles instead of one monolithic VMEM block.  A tile is a multiple
  of the TPU's 8-row sublane tiling, or the whole batch.

Every schedule is arithmetic-order-safe: int8 terms accumulate in exact
int32 (any order gives the same bits) and fp32 terms keep the banded
kernel's ascending-row order — so the specialized program is bit-identical
to the generic banded kernel in every regime (property-tested).
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.plan.plan import DEFAULT_VMEM_BUDGET, ExecutionPlan

__all__ = [
    "MM",
    "SA",
    "DEFAULT_BATCH_TILE",
    "RolloutProgram",
    "ScatteredTable",
    "scattered_table",
    "specialize_rollout",
    "specialize_summary",
    "int8_recur_reference",
    "table_product",
]

# Term tags in a band schedule (static tuples unrolled at trace time):
#   (MM, slot, shift, row_block)          one tile matmul, then << shift
#   (SA, row_block, ((i, j, sign, w)...)) unrolled shift-add digits
MM = 0
SA = 1

# Default cap on batch-tile rows: one tile's state slab stays well under a
# VMEM bank even at dim 4096 (16 * 4096 * 4 B = 256 KiB), and batch 64
# runs as four grid-parallel tiles instead of one monolithic block.
DEFAULT_BATCH_TILE = 16

# Rows of one (8, 128) vreg tile: a batch tile that is not the whole batch
# must be a multiple of it, or Mosaic refuses the (1, b_tile, I) block.
SUBLANES = 8


def default_crossover(block: int) -> int:
    """Set-digit count below which shift-adds beat a folded tile matmul.

    A folded (block x block) int8 tile costs one MXU pass regardless of
    content; a shift-add plane costs ``ones`` vector adds.  The VPU issues
    ~block lanes per add, so once a plane carries fewer than ~block/2 set
    digits the adds win even against the systolic array — the same
    crossover the paper's synthesizer faces between a carry-save tree and
    bare adders.
    """
    return max(8, block // 2)


@dataclasses.dataclass(frozen=True)
class ScatteredTable:
    """The digits a program leaves off its folded tiles, as ELL tables.

    Column ``j`` of the remainder ``sum(±2^w d_w)`` over the planes below
    the crossover holds its nonzero entries in ``idx[:, j]`` (source rows)
    and ``val[:, j]`` (values), padded with row 0 and value 0 to
    ``degree``, the largest count in any column.  Where a block has no
    plane at or above the crossover, its values are exactly its quantized
    entries.  Static numpy: the structure is a compile-time constant of
    whatever consumes it.
    """

    idx: np.ndarray            # (degree, cols_pad) int32 source rows
    val: np.ndarray            # (degree, cols_pad) int32 values
    entries: int               # nonzero entries (the rest is padding)

    @property
    def degree(self) -> int:
        return int(self.idx.shape[0])

    @property
    def slots(self) -> int:
        """Entries a product reads, padding included: degree x columns."""
        return int(self.idx.size)

    @property
    def nbytes(self) -> int:
        return int(self.idx.nbytes + self.val.nbytes)


@dataclasses.dataclass(frozen=True)
class RolloutProgram:
    """A matrix-specialized rollout: banded folded tiles + static schedule.

    ``schedules`` is the nested static tuple the kernels unroll — one entry
    per band, each listing ``(ci, terms)`` per output column block with
    :data:`MM`/:data:`SA` tagged terms.  ``data`` holds the folded weight
    tiles the MM terms index.
    """

    mode: str                  # "fp32" | "int8"
    block: int
    regime: str                # "resident" | "pipelined"
    data: jnp.ndarray          # (n_bands, max_terms, block, block)
    schedules: tuple
    max_terms: int
    vmem_budget: int | None
    crossover: int
    batch_tile_max: int
    n_matmul_terms: int        # folded-tile matmul terms kept
    n_shiftadd_terms: int      # (block, plane-group) shift-add terms
    shiftadd_digits: int       # unrolled digit adds across all SA terms
    shiftadd_lanes: int        # output lanes the SA digits land in
    resident_bytes: int        # weight bytes on-chip while executing
    table: ScatteredTable | None = None   # the SA digits, summed per entry

    @property
    def n_bands(self) -> int:
        return len(self.schedules)

    @property
    def kind(self) -> str:
        """``tiles`` (folded tiles only), ``scattered`` (no tile: every
        block lies below the crossover) or ``mixed``."""
        return _kind(self.n_matmul_terms, self.shiftadd_digits)

    @property
    def table_slots(self) -> int:
        return 0 if self.table is None else self.table.slots

    @property
    def table_bytes(self) -> int:
        return 0 if self.table is None else self.table.nbytes

    def batch_tiling(self, batch: int) -> tuple[int, int, int]:
        """(b_tile, n_tiles, b_padded) for a batch of ``batch`` rows.

        A batch that fits one tile is one tile of exactly ``batch`` rows.
        Otherwise the tile is the multiple of :data:`SUBLANES` (at most
        ``max(batch_tile_max, SUBLANES)``) that pads the batch least,
        the larger tile on a tie; padding stays below one tile.
        """
        cap = max(self.batch_tile_max, SUBLANES)
        if batch <= cap:
            return batch, 1, batch
        b_tile = min(range(SUBLANES, cap + 1, SUBLANES),
                     key=lambda bt: (-(-batch // bt) * bt, -bt))
        n_tiles = -(-batch // b_tile)
        return b_tile, n_tiles, b_tile * n_tiles

    def describe(self) -> str:
        dbl = " x2 (double-buffered)" if self.regime == "pipelined" else ""
        table = ("" if self.table is None else
                 f"; table {self.table.entries} entries, degree "
                 f"{self.table.degree}, {self.table.nbytes} B")
        return (f"{self.mode} {self.regime}: {self.n_bands} band(s), "
                f"{self.resident_bytes} B weights on-chip{dbl}, "
                f"{self.n_matmul_terms} matmul terms + "
                f"{self.n_shiftadd_terms} shift-add terms "
                f"({self.shiftadd_digits} digit adds, "
                f"crossover {self.crossover}){table}")


def _kind(n_matmul_terms: int, shiftadd_digits: int) -> str:
    if not shiftadd_digits:
        return "tiles"
    return "mixed" if n_matmul_terms else "scattered"


def _int8_block_lowering(plan: ExecutionPlan, di: int, crossover: int):
    """Constant-propagate one block's digit planes.

    Returns ``(mm_tiles, sa_digits)``: ``mm_tiles`` is a list of
    ``(tile_int8, shift)`` — one folded tile (shift 0) when the partial
    fold stays in int8 range, else the unfolded per-plane tiles — and
    ``sa_digits`` the strength-reduced ``(i, j, sign, w)`` terms of the
    planes below the crossover.
    """
    tiles = plan.int8_tiles                      # (width, n_nnz, bk, bk)
    keep = plan.plane_block_mask
    sa_digits: list[tuple] = []
    mm_planes: list[int] = []
    for w in range(plan.width):
        if not keep[w, di]:
            continue                              # culled at compile time
        plane = tiles[w, di]
        ones = int(np.count_nonzero(plane))
        if ones < crossover:
            ii, jj = np.nonzero(plane)
            sa_digits.extend(
                (int(i), int(j), int(plane[i, j]), w)
                for i, j in zip(ii, jj))
        else:
            mm_planes.append(w)
    if not mm_planes:
        return [], tuple(sa_digits)
    folded = sum(tiles[w, di].astype(np.int64) << w for w in mm_planes)
    if np.abs(folded).max() <= 127:
        # the full fold is always the quantized block (|q| <= 127); only a
        # *partial* fold — CSD's 2^width carry digit staying behind — can
        # overflow int8, in which case the planes stay separate.
        return [(folded.astype(np.int8), 0)], tuple(sa_digits)
    return ([(tiles[w, di], w) for w in mm_planes], tuple(sa_digits))


def _column_lowerings(plan: ExecutionPlan, mode: str, crossover: int):
    """Per output column block: ``[(ri, mm_tiles, sa_digits), ...]`` in the
    banded kernel's ascending-tile order."""
    rows, cols = plan.block_rows, plan.block_cols
    out: list[list] = []
    for ci in range(plan.nbc):
        entries = []
        for di in np.flatnonzero(cols == ci):
            ri = int(rows[di])
            if mode == "fp32":
                entries.append((ri, [(plan.fp32_tiles[int(di)], 0)], ()))
            else:
                mm, sa = _int8_block_lowering(plan, int(di), crossover)
                entries.append((ri, mm, sa))
        out.append(entries)
    return out


def _table(plan: ExecutionPlan, cols: list) -> ScatteredTable | None:
    """The shift-add digits of ``cols``, summed per matrix entry, as ELL
    tables (None where no digit lies below the crossover)."""
    bk = plan.block
    digits = [(ri * bk + i, ci * bk + j, s << w)
              for ci, entries in enumerate(cols)
              for ri, _mm, sa in entries for i, j, s, w in sa]
    if not digits:
        return None
    rows, col_ids, vals = (np.asarray(a, np.int64) for a in zip(*digits))
    # one entry per (column, row): a matrix entry's digits in several
    # planes below the crossover add up to its remainder value
    keys, inverse = np.unique(col_ids * plan.rows_pad + rows,
                              return_inverse=True)
    sums = np.zeros(len(keys), np.int64)
    np.add.at(sums, inverse, vals)
    keep = sums != 0
    keys, sums = keys[keep], sums[keep]
    col_of, row_of = keys // plan.rows_pad, keys % plan.rows_pad
    # keys are sorted by column, then row: each entry's slot is its rank
    # within its column
    starts = np.searchsorted(col_of, col_of, side="left")
    slot = np.arange(len(keys)) - starts
    degree = int(slot.max()) + 1
    idx = np.zeros((degree, plan.cols_pad), np.int32)
    val = np.zeros((degree, plan.cols_pad), np.int32)
    idx[slot, col_of] = row_of
    val[slot, col_of] = sums
    return ScatteredTable(idx=idx, val=val, entries=int(len(keys)))


def scattered_table(plan: ExecutionPlan,
                    crossover: int | None = None) -> ScatteredTable | None:
    """The int8 program's shift-add remainder at ``crossover`` as ELL
    tables, cached on the plan per crossover; builds no tile data."""
    crossover = default_crossover(plan.block) if crossover is None else crossover
    cache = getattr(plan, "_tables", None)
    if cache is None:
        cache = plan._tables = {}
    if crossover not in cache:
        cache[crossover] = _table(plan, _lowerings(plan, "int8", crossover))
    return cache[crossover]


def table_product(table: ScatteredTable, xq: jnp.ndarray) -> jnp.ndarray:
    """Exact ``xq @ remainder`` from the tables: (..., rows) integers ->
    (..., cols_pad) int32, in one gather-multiply-accumulate whose size is
    fixed by the table's shape, never by its entries."""
    g = jnp.take(xq.astype(jnp.int32), jnp.asarray(table.idx.reshape(-1)),
                 axis=-1)
    g = g.reshape(xq.shape[:-1] + table.idx.shape)
    return jnp.sum(g * jnp.asarray(table.val), axis=-2)


def _partition(plan: ExecutionPlan, col_mm_counts: np.ndarray,
               tile_bytes: int, vmem_budget: int | None):
    """Regime selection + greedy band packing over folded-term counts.

    Resident when every kept tile fits the budget at once; otherwise bands
    are capped at *half* the budget so two bands fit in flight (the
    prefetch of band ``k+1`` overlaps the reduction of band ``k``).
    """
    total = int(col_mm_counts.sum()) * tile_bytes
    if vmem_budget is None or total <= vmem_budget:
        return "resident", ((0, plan.nbc),)
    cap = vmem_budget // 2
    spans: list[list[int]] = [[0, 0, 0]]          # [lo, hi, n_terms]
    for ci in range(plan.nbc):
        n = int(col_mm_counts[ci])
        if n * tile_bytes > cap:
            raise ValueError(
                f"column block {ci} alone needs {n * tile_bytes} B of folded "
                f"tiles > half the vmem_budget ({cap} B needed for double "
                f"buffering); raise the budget or compile with a smaller "
                f"block than {plan.block}")
        last = spans[-1]
        if last[1] > last[0] and (last[2] + n) * tile_bytes > cap:
            spans.append([ci, ci, 0])
            last = spans[-1]
        last[1] = ci + 1
        last[2] += n
    return "pipelined", tuple((lo, hi) for lo, hi, _n in spans)


def _lowerings(plan: ExecutionPlan, mode: str, crossover: int):
    """Column lowerings cached per ``(mode, crossover)`` on the plan — the
    expensive half of the analysis (digit-plane folding) is independent of
    the band budget and batch tile, so every budget and tile of one
    crossover shares one fold."""
    cache = getattr(plan, "_lowerings", None)
    if cache is None:
        cache = plan._lowerings = {}
    key = (mode, crossover)
    if key not in cache:
        cache[key] = _column_lowerings(plan, mode, crossover)
    return cache[key]


def _analyze(plan: ExecutionPlan, mode: str, crossover: int,
             vmem_budget: int | None) -> dict:
    """The shared schedule analysis both the summary and the full program
    build from: column lowerings, band partition, regime, and every
    derived count — ONE set of formulas, so BENCH_specialize.json can
    never drift from what the kernel actually runs.  Materializes no
    tile data."""
    cols = _lowerings(plan, mode, crossover)
    itemsize = 4 if mode == "fp32" else 1
    tile_bytes = plan.block * plan.block * itemsize
    counts = np.array([sum(len(mm) for _ri, mm, _sa in entries)
                       for entries in cols])
    regime, spans = _partition(plan, counts, tile_bytes, vmem_budget)
    max_terms = max(1, max(int(counts[lo:hi].sum()) for lo, hi in spans))
    table = scattered_table(plan, crossover) if mode == "int8" else None
    shiftadd_digits = sum(len(sa) for entries in cols
                          for _ri, _mm, sa in entries)
    return {
        "cols": cols,
        "spans": spans,
        "tile_bytes": tile_bytes,
        "max_terms": max_terms,
        "mode": mode,
        "regime": regime,
        "n_bands": len(spans),
        "n_matmul_terms": int(counts.sum()),
        "n_shiftadd_terms": sum(1 for entries in cols
                                for _ri, _mm, sa in entries if sa),
        "shiftadd_digits": shiftadd_digits,
        # output lanes the unrolled digits land in, one (b_tile, 1) column
        # each in the Pallas kernel
        "shiftadd_lanes": sum(len({j for _i, j, _s, _w in sa})
                              for entries in cols for _ri, _mm, sa in entries),
        "kind": _kind(int(counts.sum()), shiftadd_digits),
        "table": table,
        "table_slots": 0 if table is None else table.slots,
        "table_bytes": 0 if table is None else table.nbytes,
        "resident_bytes": max_terms * tile_bytes * (
            1 if regime == "resident" else 2),
        "crossover": crossover,
        "vmem_budget": vmem_budget,
    }


_SUMMARY_KEYS = ("mode", "regime", "n_bands", "n_matmul_terms",
                 "n_shiftadd_terms", "shiftadd_digits", "shiftadd_lanes",
                 "kind", "table_slots", "table_bytes", "resident_bytes",
                 "crossover", "vmem_budget", "batch_tile_max")


def _summary_dict(src) -> dict:
    """Public summary fields from an analysis dict or RolloutProgram."""
    get = src.get if isinstance(src, dict) else lambda k: getattr(src, k)
    return {k: get(k) for k in _SUMMARY_KEYS}


def specialize_summary(plan: ExecutionPlan, mode: str = "fp32",
                       vmem_budget: int | None = DEFAULT_VMEM_BUDGET,
                       crossover: int | None = None,
                       batch_tile_max: int = DEFAULT_BATCH_TILE) -> dict:
    """Counts-level view of the specialization — what ``describe`` reports
    and what :func:`~repro.plan.schedule.choose_schedule` reads the
    resident bytes from.

    Keyed on the FULL schedule tuple ``(mode, vmem_budget, crossover,
    batch_tile_max)`` — the same key :func:`specialize_rollout` caches
    programs under, so schedules that differ only in batch tiling never
    collide.  Reads the fields off an already-cached
    :class:`RolloutProgram` when one exists for exactly these parameters;
    otherwise runs the shared analysis once — never materializing the
    banded data array — and caches the result on the plan.  Always
    returns a fresh dict (callers may annotate it).
    """
    assert mode in ("fp32", "int8"), mode
    crossover = default_crossover(plan.block) if crossover is None else crossover
    key = (mode, vmem_budget, crossover, batch_tile_max)
    prog = getattr(plan, "_programs", {}).get(key)
    if prog is not None:
        return _summary_dict(prog)
    cache = getattr(plan, "_summaries", None)
    if cache is None:
        cache = plan._summaries = {}
    if key not in cache:
        d = _summary_dict(dict(
            _analyze(plan, mode, crossover, vmem_budget),
            batch_tile_max=batch_tile_max))
        cache[key] = d
    return dict(cache[key])


def specialize_rollout(plan: ExecutionPlan, mode: str = "fp32",
                       vmem_budget: int | None = DEFAULT_VMEM_BUDGET,
                       crossover: int | None = None,
                       batch_tile_max: int = DEFAULT_BATCH_TILE,
                       ) -> RolloutProgram:
    """Lower one plan into a matrix-specialized :class:`RolloutProgram`.

    Cached per ``(mode, vmem_budget, crossover, batch_tile_max)`` on the
    plan — like the plan itself, the specialization is paid once per
    frozen matrix.
    """
    assert mode in ("fp32", "int8"), mode
    crossover = default_crossover(plan.block) if crossover is None else crossover
    key = (mode, vmem_budget, crossover, batch_tile_max)
    cache = getattr(plan, "_programs", None)
    if cache is None:
        cache = plan._programs = {}
    if key in cache:
        return cache[key]

    from repro import obs
    t_spec = time.perf_counter()
    bk = plan.block
    dtype = np.float32 if mode == "fp32" else np.int8
    a = _analyze(plan, mode, crossover, vmem_budget)

    schedules: list[tuple] = []
    band_data: list[list[np.ndarray]] = []
    for lo, hi in a["spans"]:
        tiles: list[np.ndarray] = []
        band_cols = []
        for ci in range(lo, hi):
            terms: list[tuple] = []
            for ri, mm, sa in a["cols"][ci]:
                for tile, shift in mm:
                    terms.append((MM, len(tiles), shift, ri))
                    tiles.append(np.asarray(tile, dtype))
                if sa:
                    terms.append((SA, ri, sa))
            band_cols.append((ci, tuple(terms)))
        schedules.append(tuple(band_cols))
        band_data.append(tiles)

    data = np.zeros((a["n_bands"], a["max_terms"], bk, bk), dtype)
    for bi, tiles in enumerate(band_data):
        if tiles:
            data[bi, : len(tiles)] = np.stack(tiles)
    program = RolloutProgram(
        mode=mode, block=bk, regime=a["regime"], data=jnp.asarray(data),
        schedules=tuple(schedules), max_terms=a["max_terms"],
        vmem_budget=vmem_budget, crossover=crossover,
        batch_tile_max=batch_tile_max,
        n_matmul_terms=a["n_matmul_terms"],
        n_shiftadd_terms=a["n_shiftadd_terms"],
        shiftadd_digits=a["shiftadd_digits"],
        shiftadd_lanes=a["shiftadd_lanes"],
        resident_bytes=a["resident_bytes"], table=a["table"])
    cache[key] = program
    obs.span("plan.specialize", t_spec, time.perf_counter(), clock="wall",
             mode=mode, regime=a["regime"], n_bands=a["n_bands"],
             kind=a["kind"], table_bytes=a["table_bytes"],
             n_matmul_terms=a["n_matmul_terms"],
             n_shiftadd_terms=a["n_shiftadd_terms"],
             shiftadd_digits=a["shiftadd_digits"])
    obs.event("specialize", mode=mode, regime=a["regime"])
    return program


def int8_recur_reference(program: RolloutProgram, xq: jnp.ndarray,
                         rows_pad: int, out_cols: int) -> jnp.ndarray:
    """Schedule-driven exact integer recurrent product (XLA consumer).

    ``xq``: (..., rows) quantized states within int8 range -> (...,
    out_cols) int32 — bit-identical to ``FixedMatrix.matvec_int_exact``
    because every term accumulates in exact int32.  The program's folded
    tiles, as the Pallas kernel multiplies them, plus its shift-add
    remainder read from the scattered table (:func:`table_product`)
    rather than unrolled digit by digit.
    """
    assert program.mode == "int8"
    bk = program.block
    xp = jnp.zeros(xq.shape[:-1] + (rows_pad,), jnp.int32
                   ).at[..., : xq.shape[-1]].set(xq.astype(jnp.int32))
    out = (None if program.table is None
           else table_product(program.table, xp))
    if program.n_matmul_terms or out is None:
        pieces = []
        for bi, band in enumerate(program.schedules):
            for ci, terms in band:
                acc = jnp.zeros(xq.shape[:-1] + (bk,), jnp.int32)
                for _tag, slot, shift, ri in (t for t in terms
                                              if t[0] == MM):
                    xs = xp[..., ri * bk:(ri + 1) * bk].astype(jnp.int8)
                    acc = acc + (jnp.matmul(
                        xs, program.data[bi, slot],
                        preferred_element_type=jnp.int32) << shift)
                pieces.append(acc)
        tiles = jnp.concatenate(pieces, axis=-1)
        out = tiles if out is None else out + tiles
    return out[..., :out_cols]
