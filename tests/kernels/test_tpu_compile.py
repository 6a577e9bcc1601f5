"""Mosaic compiles of the main-path kernels for a described TPU v5e.

Each kernel's ``hbm`` tiling — the TPU default — is lowered with
``interpret=False`` and compiled for one chip of a ``v5e:2x2`` topology
that is described, not attached: the TPU compiler installed here refuses
what the chip would (unaligned blocks, unsupported primitives, SMEM or VMEM
overuse), and nothing runs.  Shapes are qwen2.5-3b's KV geometry (2 KV
heads of 128, bf16), the benchmark cells' decode geometries and the int32
payloads of the grow → freeze pipeline.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under several test workers only
the worker given this file may load it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flatten import ops as flatten_ops
from repro.kernels.paged import ops as paged_ops
from repro.kernels.push_back import ops as pb_ops

KH, D, B, G = 2, 128, 8, 8  # qwen2.5-3b: 16 query heads over 2 KV heads of 128
S, T, P = 128, 256, 16  # slabs of 256 tokens, 16 pages per sequence
B0 = 256


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip; ``shapes`` are (shape, dtype)
    pairs or tuples of them (segmented pools)."""

    def arg(x):
        if isinstance(x[0], tuple) and isinstance(x[1], tuple):
            return tuple(arg(e) for e in x)
        return jax.ShapeDtypeStruct(x[0], x[1], sharding=chip)

    compiled = jax.jit(fn).lower(*map(arg, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4}
_FREE = ("custom-call", "parameter", "bitcast", "tuple", "get-tuple-element")


def _instructions_at_least(text: str, limit: int) -> list[str]:
    """Instructions of an optimized HLO module, other than the kernel's
    custom call and free views, whose output holds ≥ ``limit`` bytes."""
    big = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if not m or m.group(1) not in _HLO_BYTES or m.group(3) in _FREE:
            continue
        n = _HLO_BYTES[m.group(1)]
        for dim in filter(None, m.group(2).split(",")):
            n *= int(dim)
        if n >= limit:
            big.append(line.strip()[:160])
    return big


KV = ((S, T, KH, D), jnp.bfloat16)
I32 = jnp.int32


@pytest.mark.parametrize("extents", [1, 2])
def test_paged_attend_compiles(chip, extents):
    flat = ((S, T, KH * D), jnp.bfloat16)  # the engine's pool layout
    pools = flat if extents == 1 else (flat,) * extents
    _compile(
        chip,
        lambda q, k, v, pg, ln: paged_ops.paged_attend(q, k, v, pg, ln, interpret=False),
        ((B, KH, G, D), jnp.float32), pools, pools, ((B, P), I32), ((B,), I32),
    )


# The benchmark cells' decode geometries: pool slabs, slab tokens, KV heads,
# head width, batch, pages per sequence, queries per KV head.
CELLS = {
    "chat": (64, 2048, 2, 128, 32, 5, 8),  # qwen2.5-3b
    "longdoc": (48, 2048, 16, 64, 16, 5, 1),  # qwen1.5-0.5b
}


@pytest.mark.parametrize("extents", [1, 2])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_attend_reads_pool_in_place(chip, cell, extents):
    """At the cells' real sizes the compiled attend is the kernel over the
    pool as it lies: no instruction outside the custom call (a transpose, a
    padded gather, a relayout or a prefetch copy) is as large as a pool
    extent, and XLA keeps no pool-sized temporary."""
    n_slabs, slab, kh, d, b, p, g = CELLS[cell]
    ext = ((n_slabs // extents, slab, kh * d), jnp.bfloat16)
    pools = ext if extents == 1 else (ext,) * extents
    compiled = _compile(
        chip,
        lambda q, k, v, pg, ln: paged_ops.paged_attend(q, k, v, pg, ln, interpret=False),
        ((b, kh, g, d), jnp.float32), pools, pools, ((b, p), I32), ((b,), I32),
    )
    ext_bytes = n_slabs // extents * slab * kh * d * 2
    assert _instructions_at_least(compiled.as_text(), ext_bytes) == []
    assert compiled.memory_analysis().temp_size_in_bytes < ext_bytes


@pytest.mark.parametrize("pool", [KV, ((1024, T), I32)], ids=["kv", "scalar"])
def test_paged_gather_compiles(chip, pool):
    _compile(
        chip,
        lambda p, pg: paged_ops.paged_gather(p, pg, interpret=False),
        pool, ((B, P), I32),
    )


@pytest.mark.parametrize(
    "pool,item", [(KV, (KH, D)), (((1024, T), I32), ())], ids=["kv", "scalar"]
)
def test_slab_append_compiles(chip, pool, item):
    n_slabs, m = pool[0][0], 256
    _compile(
        chip,
        lambda p, o, b, s, e, mk: paged_ops.slab_append(p, o, b, s, e, mk, interpret=False),
        pool, ((n_slabs,), I32), ((n_slabs,), I32), ((B,), I32),
        ((B, m, *item), pool[1]), ((B, m), jnp.bool_),
    )


@pytest.mark.parametrize(
    "dtype,item,nlev,m",
    [(I32, (), 9, 256), (jnp.float32, (), 4, 64), (jnp.bfloat16, (KH, D), 4, 32)],
    ids=["int32", "f32", "kv"],
)
def test_push_back_compiles(chip, dtype, item, nlev, m):
    nblocks = 64 if not item else B
    levels = tuple(((nblocks, B0 * 2**b, *item), dtype) for b in range(nlev))
    _compile(
        chip,
        lambda lv, s, e, mk: pb_ops.push_back_fused(lv, s, B0, e, mk, interpret=False),
        levels, ((nblocks,), I32), ((nblocks, m, *item), dtype),
        ((nblocks, m), jnp.bool_),
    )


@pytest.mark.parametrize("dtype", [I32, jnp.bfloat16], ids=["int32", "bf16"])
def test_segmented_flatten_compiles(chip, dtype):
    levels = tuple(((64, B0 * 2**b), dtype) for b in range(6))
    _compile(
        chip,
        lambda lv, s: flatten_ops.flatten(lv, s, B0, interpret=False),
        levels, ((64,), I32),
    )


def test_flash_attention_compiles(chip):
    q = ((B * KH * G, 1024, D), jnp.bfloat16)
    kv = ((B * KH, 1024, D), jnp.bfloat16)
    _compile(
        chip,
        lambda q, k, v: flash_ops.flash_attention(q, k, v, group=G, interpret=False),
        q, kv, kv,
    )
