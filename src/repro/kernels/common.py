"""Shared kernel utilities: interpret policy, memory-space grid layer, padding.

All kernels target TPU (``pl.pallas_call`` + explicit ``BlockSpec`` tiling).
On non-TPU backends they execute in ``interpret=True`` mode, which runs the
kernel body as traced JAX ops — the correctness oracle path used by the test
suite.  ``REPRO_FORCE_INTERPRET=1`` forces interpret mode on CPU runners
(CI sets it).  On a TPU backend interpret mode is refused outright — by the
environment variable and by an explicit ``interpret=True`` alike — so a chip
run can never fall back to the interpreter in silence.

Memory spaces (DESIGN.md §4 "Memory-space tiers")
-------------------------------------------------
The three indirection kernel families (``kernels/paged``,
``kernels/push_back``, ``kernels/flatten``) each exist in two tilings behind
one :class:`GridPlan`:

``"vmem"``
    Every operand is auto-pipelined into VMEM by its ``BlockSpec``; the
    indirection tables (page tables, size vectors, prefix sums) ride along as
    ordinary tiled operands and the *data* operands (slab pool, bucket
    levels, compacted plane) are resident per grid step.  Exactly what
    interpret mode wants, and **interpret-only**: Mosaic refuses these
    tilings (unaligned blocks, 1-D gathers, in-kernel ``cumsum``), so
    :meth:`GridPlan.pallas_call` raises instead of compiling them.

``"hbm"``
    The data stays HBM-resident.  The indirection tables become
    **scalar-prefetch operands** (``pltpu.PrefetchScalarGridSpec``) — they are
    tiny (Tarjan & Zwick: O(√n)–O(log n) entries), live in SMEM, and are
    available *before* the kernel body runs, so a ``BlockSpec.index_map`` can
    read them to DMA exactly one slab / level / block-row tile per grid step.
    Kernels that need data-dependent tile *counts* (flatten's ragged block
    spans, push_back's and slab-append's touched windows) instead take
    ``pltpu.ANY``-space refs and issue explicit ``make_async_copy`` DMAs.
    Every such DMA moves whole HBM tiles: a 2-D array is tiled
    ``(tile_rows(dtype), 128)``, so one logical row is read and written
    through the aligned :func:`row_window` that holds it.  Prefix sums are
    computed outside the kernels (they are mask arithmetic on the wave) and
    wave elements are placed by a one-hot matmul on byte planes
    (:func:`byte_planes`, :func:`wave_select`) — exact for every 32-bit
    bit pattern, with no in-kernel ``cumsum`` or gather.

Both spaces run the same index math and are bit-exact against the jnp
oracles; ``resolve_memory_space`` picks ``vmem`` under interpret mode and
``hbm`` on a real TPU unless overridden (arg > ``REPRO_MEMORY_SPACE`` env >
backend default).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "should_interpret",
    "pad_to",
    "MXU_LANE",
    "MEMORY_SPACES",
    "resolve_memory_space",
    "DISPATCH_METHODS",
    "MXU_DISPATCH_WAVE",
    "resolve_dispatch",
    "extent_row",
    "tile_rows",
    "row_window",
    "to_words",
    "from_words",
    "byte_planes",
    "wave_select",
    "fill_window",
    "GridPlan",
]

MXU_LANE = 128  # MXU systolic dimension / VREG lane count

MEMORY_SPACES = ("vmem", "hbm")

# Wave width at which the insert permutation moves from the exact int32
# one-hot reduction (VPU, O(m²) compares) to the MXU dispatch matmul.
# Measured, not a-priori: the threshold lives in kernels/tuning.py (single
# source of truth shared with the benchmark sweeps).
from repro.kernels.tuning import MXU_DISPATCH_WAVE  # noqa: E402

DISPATCH_METHODS = ("auto", "onehot", "mxu")


def should_interpret(interpret: bool | None) -> bool:
    """Resolve the interpret flag: env force > explicit > interpret off-TPU.

    On a TPU backend interpret mode is an error, however it was asked for:
    a kernel there is compiled by Mosaic or the call fails.
    """
    forced = os.environ.get("REPRO_FORCE_INTERPRET") == "1"
    if jax.default_backend() == "tpu":
        if forced or interpret:
            why = "REPRO_FORCE_INTERPRET=1" if forced else "interpret=True"
            raise RuntimeError(
                f"{why} on a TPU backend: Pallas kernels run compiled on the "
                "chip; interpret mode is for CPU runs only"
            )
        return False
    if forced:
        return True
    if interpret is not None:
        return interpret
    return True


def resolve_memory_space(
    memory_space: str | None, interpret: bool | None = None
) -> str:
    """Resolve the kernel memory space: arg > env > backend default.

    The default is ``"hbm"`` on a real TPU (pools/levels cannot be VMEM
    resident at serving scale) and ``"vmem"`` in interpret mode (everything
    is host memory anyway and the simpler tiling traces faster).  Setting
    ``REPRO_MEMORY_SPACE=vmem|hbm`` overrides the default everywhere — the
    hook CI uses to run the hbm tilings on CPU runners.
    """
    env = os.environ.get("REPRO_MEMORY_SPACE")
    space = memory_space if memory_space is not None else env
    if space is None:
        space = "vmem" if should_interpret(interpret) else "hbm"
    if space not in MEMORY_SPACES:
        raise ValueError(f"memory_space {space!r} not in {MEMORY_SPACES}")
    return space


def resolve_dispatch(dispatch: str, m: int, dtype: Any) -> str:
    """Resolve the insert-permutation backend for an ``m``-wide wave.

    ``"auto"`` routes waves of at least :data:`MXU_DISPATCH_WAVE` lanes
    through the MXU dispatch matmul — but only for payloads the f32 matmul
    reproduces bit-for-bit (f32/bf16/f16, int8/int16); wide ints and f64
    can exceed the f32 mantissa the MXU accumulates in and stay on the
    exact one-hot reduction.  Explicit ``"onehot"``/``"mxu"`` are honored
    as given.
    """
    if dispatch not in DISPATCH_METHODS:
        raise ValueError(f"dispatch {dispatch!r} not in {DISPATCH_METHODS}")
    if dispatch != "auto":
        return dispatch
    dt = jnp.dtype(dtype)
    exact = (jnp.issubdtype(dt, jnp.floating) and dt.itemsize <= 4) or (
        jnp.issubdtype(dt, jnp.integer) and dt.itemsize <= 2
    )
    return "mxu" if m >= MXU_DISPATCH_WAVE and exact else "onehot"


def extent_row(ext, off, e: int, size: int):
    """Two-level page-table resolution for a ``BlockSpec.index_map``.

    ``ext``/``off`` are this step's scalar-prefetched two-level table entries
    (``pool/extents.resolve_pages``); the index map of extent ``e``'s operand
    returns ``off`` when the step's slab lives in extent ``e`` and a parked
    in-bounds row otherwise — every extent DMAs a tile each step, but the
    body consumes only the one ``ext`` selects, so off-extent tiles are
    provably inert (the multi-extent analog of the page −1 clip).
    """
    return jnp.where(ext == e, jnp.clip(off, 0, size - 1), 0)


def tile_rows(dtype) -> int:
    """Second-minor HBM tile of a 2-D array: 8 rows of 32-bit, 16 of 16-bit."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def row_window(ref, row, col, width: int):
    """→ (DMA view, row in view) of logical ``row``, columns ``[col, +width)``.

    A 3-D ``(rows, n, d)`` ref is untiled in its leading dim, so the view is
    the row itself.  A 2-D ref is tiled ``(tile_rows, 128)`` in HBM and a
    DMA must move whole tiles, so the view is the aligned band of
    ``tile_rows`` rows holding ``row`` (all rows when there are fewer).
    ``col`` must be a multiple of 128 (or 0 with ``width`` the full row) for
    Mosaic to accept the slice.
    """
    if ref.ndim == 3:
        return ref.at[pl.ds(row, 1), pl.ds(col, width)], 0
    nrows = ref.shape[0]
    tr = tile_rows(ref.dtype)
    if nrows <= tr:
        return ref.at[pl.ds(0, nrows), pl.ds(col, width)], row
    if nrows % tr:  # unaligned tail band: interpret mode only
        r0 = jnp.minimum((row // tr) * tr, nrows - tr)
    else:
        r0 = pl.multiple_of((row // tr) * tr, tr)
    return ref.at[pl.ds(r0, tr), pl.ds(col, width)], row - r0


def to_words(x: jax.Array) -> jax.Array:
    """Payload → int32 words that :func:`from_words` inverts bit for bit.

    32-bit payloads are bitcast; narrower floats widen to f32 first (exact,
    signed zeros and infinities included) and narrower ints to int32.
    """
    dt = jnp.dtype(x.dtype)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return x.astype(jnp.int32)


def from_words(w: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`to_words`."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(w, dt)
    if jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(w, jnp.float32).astype(dt)
    return w.astype(dt)


def byte_planes(x: jax.Array, axis: int) -> jax.Array:
    """Payload → its 4 word bytes as bf16 planes stacked at ``axis``.

    A byte (0..255) is exact in bf16, so a one-hot bf16 matmul with f32
    accumulation moves each plane exactly — :func:`wave_select` reassembles
    the words.  Computed once per wave, outside the kernels.
    """
    w = to_words(x)
    planes = [((w >> (8 * p)) & 0xFF).astype(jnp.bfloat16) for p in range(4)]
    return jnp.stack(planes, axis=axis)


def wave_select(planes, off, first, width: int):
    """Words of ``width`` consecutive slots placed from one row's wave.

    Slot ``j`` takes the wave lane ``k`` with ``off[k] == first + j``
    (``off`` is the row's exclusive prefix sum of its mask, −1 on masked-off
    lanes, shape ``(1, m)``).  ``planes`` is :func:`byte_planes` of the
    row's lanes: ``(4, m)`` for scalar items → words ``(1, width)``;
    ``(4, m, d)`` for ``d``-wide items → words ``(width, d)``.  Slots no
    lane lands on come back 0 — callers keep their old value there.
    """
    m = off.shape[-1]
    slot = first + jax.lax.broadcasted_iota(jnp.int32, (width, m), 0)
    onehot = jnp.where((slot == off) & (off >= 0), 1.0, 0.0)
    onehot = onehot.astype(jnp.bfloat16)
    if planes.ndim == 2:  # (4, m) · (width, m)ᵀ → (4, width), lanes = slots
        got = jax.lax.dot_general(
            planes, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        shift = 8 * jax.lax.broadcasted_iota(jnp.int32, got.shape, 0)
        return jnp.sum(got << shift, axis=0, keepdims=True)
    words = jnp.zeros((width, planes.shape[-1]), jnp.int32)
    for p in range(4):
        got = jnp.dot(onehot, planes[p], preferred_element_type=jnp.float32)
        words = words + (got.astype(jnp.int32) << (8 * p))
    return words


def fill_window(view, row, buf, sem, planes, off, first, count) -> None:
    """Read-modify-write one HBM window with a row's wave (kernel body).

    DMAs ``view`` (from :func:`row_window`) into ``buf``, overwrites the
    slots of logical ``row`` whose wave offset ``first + j`` lies in
    ``[0, count)`` with the lanes :func:`wave_select` places there, and
    DMAs the window back.  Both copies are waited, so windows that share an
    HBM tile with other rows never race.
    """
    cp = pltpu.make_async_copy(view, buf, sem)
    cp.start()
    cp.wait()
    width = buf.shape[1]
    vals = wave_select(planes, off, first, width)
    words = to_words(buf[...])
    if buf.ndim == 2:  # a row band: only ``row`` changes
        o = first + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        band = jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)
        put = (band == row) & (o >= 0) & (o < count)
    else:
        o = first + jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
        put = ((o >= 0) & (o < count))[None]
        vals = vals[None]
    buf[...] = from_words(jnp.where(put, vals, words), buf.dtype)
    cp = pltpu.make_async_copy(buf, view, sem)
    cp.start()
    cp.wait()


def pad_to(x: jax.Array, multiple: int, axis: int, value=0) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple (VMEM tile alignment)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """One kernel grid, two memory spaces — the shared scalar-prefetch layer.

    A kernel family builds one ``GridPlan`` per memory space and calls
    :meth:`pallas_call`; the plan owns the mechanics that differ between the
    spaces so the kernel modules only describe *what* each operand is:

    * operand order is uniform — ``body(*tables, *tensors, *outs, *scratch)``
      in both spaces, with the ``num_tables`` leading operands being the
      int32 indirection tables;
    * on the ``hbm`` path the tables become ``PrefetchScalarGridSpec`` scalar
      operands (SMEM, readable from every ``index_map``), and
      ``table_specs`` is ignored;
    * on the ``vmem`` path the tables are ordinary operands tiled by
      ``table_specs``;
    * ``aliases`` maps *tensor*-operand positions to outputs; the plan
      offsets them by the table count for the flat numbering
      ``input_output_aliases`` wants (scalar-prefetch operands included).

    ``in_specs`` entries may be ``pl.BlockSpec(memory_space=pltpu.ANY)`` for
    operands the body DMAs manually (flatten's compact plane, push_back's
    bucket levels).

    ``instrument=True`` appends the device counter plane's block
    (``obs/device``: (8, 128) int32, every grid step mapped to the same
    block — the grid-accumulator idiom) as one extra output in **both**
    memory spaces: the body receives its ref after the declared outputs and
    before scratch, and writes it with ``device.ctr_accum``.  Off by
    default, and when off this dataclass field doesn't reach the
    ``pallas_call`` — the uninstrumented plan builds the exact same program
    as before the counter plane existed.
    """

    memory_space: str
    grid: tuple[int, ...]
    num_tables: int
    table_specs: Sequence[Any]
    in_specs: Sequence[Any]
    out_specs: Any
    scratch_shapes: Sequence[Any] = ()
    aliases: Mapping[int, int] = dataclasses.field(default_factory=dict)
    instrument: bool = False

    def __post_init__(self):
        if self.memory_space not in MEMORY_SPACES:
            raise ValueError(
                f"memory_space {self.memory_space!r} not in {MEMORY_SPACES}"
            )

    def _with_counters(self, out_specs, out_shape):
        """Append the counter block's spec + shape (instrumented plans)."""
        from repro.obs import device

        if not isinstance(out_specs, (list, tuple)):
            out_specs = [out_specs]
        if not isinstance(out_shape, (list, tuple)):
            out_shape = [out_shape]
        return (
            list(out_specs) + [device.ctr_block_spec()],
            list(out_shape) + [device.ctr_shape()],
        )

    def pallas_call(self, body, out_shape, *, interpret: bool = False):
        """→ the configured ``pl.pallas_call`` (call it with tables first)."""
        if self.memory_space == "vmem" and not interpret:
            raise NotImplementedError(
                f"{getattr(body, 'func', body).__name__}: the vmem tiling is "
                "an interpret-mode oracle that Mosaic does not compile; use "
                "memory_space='hbm' on a TPU"
            )
        aliases = {self.num_tables + i: o for i, o in self.aliases.items()}
        out_specs = self.out_specs
        if self.instrument:
            out_specs, out_shape = self._with_counters(out_specs, out_shape)
        if self.memory_space == "hbm":
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=self.num_tables,
                grid=self.grid,
                in_specs=list(self.in_specs),
                out_specs=out_specs,
                scratch_shapes=list(self.scratch_shapes),
            )
            return pl.pallas_call(
                body,
                grid_spec=grid_spec,
                out_shape=out_shape,
                input_output_aliases=aliases,
                interpret=interpret,
            )
        kwargs: dict[str, Any] = {}
        if self.scratch_shapes:
            kwargs["scratch_shapes"] = list(self.scratch_shapes)
        return pl.pallas_call(
            body,
            grid=self.grid,
            in_specs=list(self.table_specs) + list(self.in_specs),
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            input_output_aliases=aliases,
            **kwargs,
        )
