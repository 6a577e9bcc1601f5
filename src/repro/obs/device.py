"""Device counter plane — in-kernel counters drained without host syncs.

The paper's pitch is growth *without host synchronization*, which makes the
structure's health (wave occupancy, masked-lane waste, tiles DMA'd per page
walk) invisible exactly where it matters: inside the kernels.  This module
is the device-side half of the observability layer (DESIGN.md §9.x): each
instrumented Pallas family writes a small int32 counter block as one extra
kernel output, the ops wrappers pack those blocks into a fixed-layout
float32 vector (:data:`SLOTS`), and the vector rides the caller's pytree —
through scan carries, across jit boundaries — as ordinary device data.

Nothing here reads a device value.  Draining goes through
:class:`DeviceCounterPlane`: ``add()`` appends a device vector (a list
append), ``flush()`` slices the device total into per-slot
``Counter.add_lazy`` pends — still zero transfers — and the numbers only
materialize when the registry snapshots or a counter is read, the same
explicit drain points the PR-8 layer already has.  The decode hot path
therefore stays at **zero** device→host transfers with instrumentation on
(transfer-guard + device_get-spy tested).

Collection inside traced code uses a :func:`tape`: ``kvcache``/ops record
vectors while the step function traces, and the step body (``serving/
steps.py``) sums the tape into an extra output when ``cfg.instrument`` is
set.  With ``instrument=False`` no tape exists, no vector is built, and
every trace is byte-identical to the uninstrumented program (compile-spy
tested).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

__all__ = [
    "SLOTS",
    "NSLOTS",
    "SLOT_INDEX",
    "CTR_ROWS",
    "CTR_LANES",
    "ctr_shape",
    "ctr_block_spec",
    "ctr_accum",
    "zeros",
    "pack",
    "from_block",
    "as_dict",
    "Tape",
    "tape",
    "record",
    "recording",
    "DeviceCounterPlane",
]

# One lane per counter, fixed layout: lane i of the in-kernel block row 0 is
# SLOTS[i].  Grouped by kernel family; the names double as registry counter
# names under the "device." prefix.
SLOTS: tuple[str, ...] = (
    # push_back: fused bucket append (kernels/push_back)
    "push_back.waves",          # kernel launches (one wave each)
    "push_back.lanes",          # wave lanes processed (rows × padded width)
    "push_back.active_lanes",   # Σ mask — lanes that carried an element
    "push_back.padded_lanes",   # lanes added by tile/MXU padding (pure waste)
    "push_back.level_writes",   # bucket-level slots written across all levels
    # paged gather: page-table walk (kernels/paged)
    "paged_gather.launches",
    "paged_gather.tiles",       # page tiles with a live slab id (DMA'd work)
    "paged_gather.masked_tiles",  # −1 / padded page entries walked (waste)
    # paged attend: flash-decode page walk (kernels/paged); a tile is one
    # (row, block) grid step over every KV head
    "paged_attend.launches",
    "paged_attend.tiles",         # K/V blocks entering the online softmax
    "paged_attend.tiles_skipped",  # steps gated off (tail blocks, −1, idle rows)
    "paged_attend.lanes",         # score lanes in visited tiles
    "paged_attend.masked_lanes",  # score lanes past kv_len in visited tiles
    # flatten: segmented gather (kernels/flatten)
    "flatten.launches",
    "flatten.rows_touched",     # block rows visited by the gather
    "flatten.span_rows",        # Σ (ends − starts) — the information bound
    # slab append: arena wave insert (kernels/paged.slab_append)
    "slab_append.waves",
    "slab_append.lanes",
    "slab_append.active_lanes",
    # held experts: dropless MoE (models/moe._moe_held), per layer call
    "moe.held_assignments",  # routed (token, expert) pairs that land here
    "moe.max_held_load",     # Σ over calls of the busiest held expert's load
)
NSLOTS = len(SLOTS)
SLOT_INDEX: dict[str, int] = {name: i for i, name in enumerate(SLOTS)}

# In-kernel counter block: (8, 128) int32 — the minimum int32 VMEM tile, so
# the extra output never perturbs the data operands' tiling.  Row 0 carries
# the counters (lane i = SLOTS[i]); rows 1..7 stay zero.
CTR_ROWS = 8
CTR_LANES = 128
assert NSLOTS <= CTR_LANES


def ctr_shape():
    """Out-shape of the in-kernel counter block."""
    return jax.ShapeDtypeStruct((CTR_ROWS, CTR_LANES), jnp.int32)


def ctr_block_spec():
    """BlockSpec pinning every grid step to the same (only) counter block —
    the grid-accumulator idiom: step 0 initializes, later steps add."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((CTR_ROWS, CTR_LANES), lambda *_: (0, 0))


def _contrib(shape, pairs):
    """Σ one-hot(lane=slot)·value over ``pairs`` → (CTR_ROWS, CTR_LANES)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    blk = jnp.zeros(shape, jnp.int32)
    for slot, value in pairs:
        hit = (rows == 0) & (lanes == SLOT_INDEX[slot])
        blk = blk + jnp.where(hit, jnp.asarray(value, jnp.int32), 0)
    return blk


def ctr_accum(ctr_ref, first, pairs):
    """Accumulate ``pairs`` of (slot name, int32 scalar) into the counter
    block ref.  ``first`` is this launch's first-grid-step predicate: that
    step overwrites (the output block is revisited, not zero-initialized),
    every later step adds.  Values must already be gated (use
    ``jnp.where(cond, v, 0)``, not ``pl.when``, so the accumulate itself is
    unconditional and the block stays consistent)."""
    from jax.experimental import pallas as pl

    blk = _contrib(ctr_ref.shape, pairs)

    @pl.when(first)
    def _init():
        ctr_ref[...] = blk

    @pl.when(jnp.logical_not(first))
    def _add():
        ctr_ref[...] = ctr_ref[...] + blk


# --------------------------------------------------------------------------
# host-side vector layout — (NSLOTS,) float32, one value per slot.
# --------------------------------------------------------------------------

def zeros() -> jax.Array:
    return jnp.zeros((NSLOTS,), jnp.float32)


def pack(**slots) -> jax.Array:
    """Build a counter vector from named slot values (device scalars or
    ints); unnamed slots are zero.  Dots in slot names are passed as
    ``pack(**{"push_back.waves": 1})``."""
    vec = zeros()
    for name, value in slots.items():
        vec = vec.at[SLOT_INDEX[name]].add(jnp.asarray(value, jnp.float32))
    return vec


def from_block(block: jax.Array) -> jax.Array:
    """In-kernel counter block → (NSLOTS,) vector (row 0, leading lanes)."""
    return block[0, :NSLOTS].astype(jnp.float32)


def as_dict(vec) -> dict[str, float]:
    """Materialize a counter vector → {slot: value}.  This READS the device
    value — call it only at drain points (benches, bundles, tests)."""
    host = jax.device_get(vec)
    return {name: float(host[i]) for i, name in enumerate(SLOTS)}


# --------------------------------------------------------------------------
# tape — collect vectors recorded inside traced code.
# --------------------------------------------------------------------------

class Tape:
    """An ordered list of counter vectors recorded under one :func:`tape`."""

    __slots__ = ("vecs",)

    def __init__(self):
        self.vecs: list = []

    def add(self, vec) -> None:
        self.vecs.append(vec)

    def total(self):
        """Device sum of everything recorded (zeros when nothing was)."""
        if not self.vecs:
            return zeros()
        if len(self.vecs) == 1:
            return self.vecs[0]
        return jnp.sum(jnp.stack(self.vecs), axis=0)


_ACTIVE: list[Tape] = []


@contextlib.contextmanager
def tape():
    """Open a collection scope: :func:`record` calls inside land on the
    yielded tape.  Scopes nest (innermost wins) — the step functions open
    one per scan-body iteration so recorded tracers never escape their
    trace level."""
    t = Tape()
    _ACTIVE.append(t)
    try:
        yield t
    finally:
        _ACTIVE.pop()


def record(vec) -> None:
    """Record a counter vector on the innermost active tape (no-op without
    one — ops can record unconditionally)."""
    if _ACTIVE:
        _ACTIVE[-1].add(vec)


def recording() -> bool:
    return bool(_ACTIVE)


# --------------------------------------------------------------------------
# plane — engine-side accumulator, drained through Counter.add_lazy.
# --------------------------------------------------------------------------

class DeviceCounterPlane:
    """Holds per-step counter vectors as device values; never syncs itself.

    ``add()`` is the hot-path call (a list append).  ``flush()`` sums the
    pending vectors on device and hands one scalar slice per slot to
    ``Counter.add_lazy`` — still zero transfers; the registry's existing
    drain points (snapshot / metric reads) do the single ``device_get``
    per counter.
    """

    PREFIX = "device."

    def __init__(self, registry):
        self.registry = registry
        self._pending: list = []

    @property
    def pending(self) -> int:
        return len(self._pending)

    def add(self, vec) -> None:
        self._pending.append(vec)

    def flush(self) -> None:
        """Move pending vectors into the registry as lazy counter adds
        (no device→host transfer happens here)."""
        if not self._pending:
            return
        tot = (
            self._pending[0]
            if len(self._pending) == 1
            else jnp.sum(jnp.stack(self._pending), axis=0)
        )
        self._pending = []
        for i, name in enumerate(SLOTS):
            self.registry.counter(
                self.PREFIX + name, help="device counter plane slot"
            ).add_lazy(tot[i])

    def counters(self) -> dict[str, float]:
        """Flush + read every slot → {slot: value}.  This is a drain point
        (one ``device_get`` per counter with pending adds)."""
        self.flush()
        out = {}
        for name in SLOTS:
            c = self.registry.get(self.PREFIX + name)
            out[name] = float(c.total()) if c is not None else 0.0
        return out
