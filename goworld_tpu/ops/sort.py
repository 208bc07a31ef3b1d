"""Two-pass counting sort of entities by grid-cell row.

The AOI sweep's front half orders entity slots by cell row id
(:func:`goworld_tpu.ops.aoi._sort_cells`). XLA lowers the generic
``argsort`` to a bitonic network — ~half log2(n)^2 compare-exchange
passes, each streaming keys + payload through HBM. At the 1M-entity
bench shape that is the single worst term of the tick's memory budget
(docs/ROOFLINE.md charged it 1.5-3.2 GB of the ~4.6-6.2 GB/tick total).

Cell-row keys are TINY relative to n (a few hundred thousand bins at
1M entities, tens of thousands at the 131K shard), so the classic
particle-code replacement applies: a **counting sort** —

1. histogram the keys with one scatter-add,
2. exclusive cumsum for the per-bin output offsets,
3. stable scatter: element ``i`` lands at
   ``row_start[key_i] + rank_i`` where ``rank_i`` is the number of
   EARLIER elements with the same key.

Passes 1-2 are single XLA ops. Pass 3's ``rank_i`` is the only part
with no direct XLA primitive (it is what atomicAdd returns on GPUs);
it decomposes exactly over id-ordered chunks:

    rank_i = fill[key_i]  (same-key count in earlier chunks)
           + |{j in chunk, j < i, key_j == key_i}|  (within-chunk)

so a ``lax.scan`` over chunks of ``chunk`` elements carries the
running per-bin ``fill`` histogram, and the within-chunk term is a
[chunk, chunk] masked equality reduce — pure VPU work, no sort network
anywhere. Total traffic is ~2 streaming passes over the keys plus the
[n_bins] fill array per chunk (~tens of MB at 1M vs the bitonic GB),
trading it for n*chunk vectorized compares.

The result is STABLE and therefore **bit-identical to
``jnp.argsort(srow)``** in every regime — including which entities a
``cell_cap`` overflow drops — so the sort impl is a pure lowering
choice (``GridSpec.sort_impl``), never a fidelity knob.

:func:`counting_sort_cells_pallas` is the same algorithm as a Pallas
kernel: the sequential TPU grid walks the chunks while the ``fill``
histogram persists in VMEM scratch across grid steps. Two kernel
bodies share that structure (``lowering=``):

* ``"vector"`` — the original interpret-mode form: the per-chunk fill
  lookups are vector gathers (``fill[keys]``), which jax's interpreter
  executes directly but Mosaic cannot lower (TPU has no vector
  gather/scatter over VMEM).
* ``"serial"`` — the TPU lowering: the per-bin ``starts``/``fill``
  tables, the chunk's keys and its destinations all live in SMEM as
  1-D i32 arrays, and the fill walk is a ``fori_loop`` of
  single-element reads/updates on the scalar core — the emulation of
  what atomicAdd returns on GPUs. (Mosaic refuses the same walk over
  VMEM tiles: "Cannot store scalars to VMEM"; the v5e compile of this
  body at the 131,072 shard is held by tests/test_tpu_compile.py.) The
  per-element walk subsumes the within-chunk rank (the running counter
  already counts earlier same-key elements of the chunk), so no
  [chunk, chunk] triangle compare exists in this body at all. The bin
  tables must fit SMEM — the compiler refuses a bin space that does
  not, loudly, at compile time. No DMA semaphores are needed because
  the sequential grid + automatic block pipelining already serialize
  the scratch reuse. The same body passes interpret-mode parity on CPU
  (tests/test_sort.py), so hardware runs exercise a CPU-validated
  algorithm.

Off-TPU, selecting the pallas impl runs in interpret mode with a
one-time warning (:mod:`goworld_tpu.ops.pallas_compat`) instead of
failing at trace time; on a TPU backend interpret mode is refused.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNK = 2048


def _chunk_keys(srow: jax.Array, n_rows: int, chunk: int):
    """Pad to a whole number of chunks with dump-bin keys. Padded
    elements carry indices >= n, sit AFTER every real element, and so
    scatter past the end of the output (dropped)."""
    n = srow.shape[0]
    c = max(1, min(chunk, n))
    nb = -(-n // c)
    pad = nb * c - n
    if pad:
        srow = jnp.concatenate(
            [srow, jnp.full((pad,), n_rows, jnp.int32)]
        )
    return srow.reshape(nb, c), c, nb


def row_starts(srow: jax.Array, n_rows: int) -> jax.Array:
    """Exclusive-cumsum bin offsets (passes 1-2): ``row_starts[r]`` is
    the first sorted position of cell row ``r``; the dump bin
    ``n_rows`` (dead entities) sorts last. int32[n_rows + 1]."""
    counts = jnp.zeros(n_rows + 1, jnp.int32).at[srow].add(1)
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts[:-1], dtype=jnp.int32)]
    )


def _finish(srow, dst, n):
    """Invert the destination map into (order, sorted_row). ``dst`` is
    a permutation of [0, n) over the real elements (padded elements
    land past n and drop)."""
    m = dst.shape[0]
    order = jnp.zeros(n, jnp.int32).at[dst].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop"
    )
    return order, srow[order]


@partial(jax.jit, static_argnums=(1, 2))
def counting_sort_cells(
    srow: jax.Array, n_rows: int, chunk: int = DEFAULT_CHUNK
) -> tuple[jax.Array, jax.Array]:
    """Stable counting sort of slot ids by cell row (pure XLA).

    Args:
      srow: int32[n] cell-row keys in ``[0, n_rows]`` (``n_rows`` is
        the dump bin for dead entities — sorts last, like argsort).
      n_rows: static bin count.
      chunk: scan chunk size; a pure execution knob (any value yields
        identical results). Larger chunks mean fewer sequential scan
        steps but n*chunk total within-chunk compares.

    Returns:
      (order, sorted_row) — exactly ``jnp.argsort(srow)`` (stable) and
      ``srow[order]``.
    """
    n = srow.shape[0]
    starts = row_starts(srow, n_rows)
    keys_c, c, _nb = _chunk_keys(srow, n_rows, chunk)
    tri = jnp.tril(jnp.ones((c, c), bool), -1)

    def body(fill, keys):
        # within-chunk stable rank: earlier same-key elements
        r = ((keys[:, None] == keys[None, :]) & tri).sum(
            axis=1, dtype=jnp.int32
        )
        dst = starts[keys] + fill[keys] + r
        return fill.at[keys].add(1), dst

    _, dst = lax.scan(body, jnp.zeros(n_rows + 1, jnp.int32), keys_c)
    return _finish(srow, dst.reshape(-1), n)


# ---------------------------------------------------------------- pallas ----

def counting_sort_cells_pallas(
    srow: jax.Array,
    n_rows: int,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
    lowering: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """:func:`counting_sort_cells` with pass 3 as a Pallas kernel.

    The grid is sequential on TPU, so the VMEM ``fill`` scratch carries
    the running per-bin histogram across grid steps — the same
    loop-carried state the XLA path threads through ``lax.scan``.

    ``interpret`` resolves via
    :func:`goworld_tpu.ops.pallas_compat.resolve_interpret`: hardware
    lowering on TPU (asking for interpret mode there raises), interpret
    mode (with a one-time warning) anywhere else. ``lowering`` picks
    the kernel body (module docstring): ``"auto"`` = the ``"serial"`` TPU lowering
    when compiling for hardware, the ``"vector"`` gather form under
    interpret (the interpreter executes vector gathers directly and far
    faster than a serial loop); both are explicitly selectable so tests
    can run the hardware body under interpret for parity. Identical
    results from every combination — and therefore to argsort.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from goworld_tpu.ops.pallas_compat import resolve_interpret

    interpret = resolve_interpret("counting_sort_fill", interpret)
    if lowering not in ("auto", "serial", "vector"):
        raise ValueError(
            f"lowering must be auto|serial|vector, got {lowering!r}"
        )
    if lowering == "auto":
        lowering = "vector" if interpret else "serial"
    n = srow.shape[0]
    starts = row_starts(srow, n_rows)
    keys_c, c, nb = _chunk_keys(srow, n_rows, chunk)

    if lowering == "serial":
        def kernel(starts_ref, keys_ref, dst_ref, fill_ref):
            @pl.when(pl.program_id(0) == 0)
            def _init():
                def zero(b, _):
                    fill_ref[b] = 0
                    return 0

                lax.fori_loop(0, n_rows + 1, zero, 0)

            # the element-wise fill walk IS the stable rank: the running
            # per-bin counter already counts earlier same-key elements
            # of this chunk (unlike the vector body, whose fill only
            # advances per chunk and needs the [c, c] triangle rank on
            # top) — exactly what atomicAdd returns on GPUs
            def body(i, _):
                key = keys_ref[i]
                f = fill_ref[key]
                dst_ref[i] = starts_ref[key] + f
                fill_ref[key] = f + 1
                return 0

            lax.fori_loop(0, c, body, 0)

        dst = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((c,), lambda i: (i,),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((c,), lambda i: (i,),
                                   memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((nb * c,), jnp.int32),
            scratch_shapes=[pltpu.SMEM((n_rows + 1,), jnp.int32)],
            interpret=interpret,
        )(starts, keys_c.reshape(-1))
        return _finish(srow, dst, n)

    def kernel(starts_ref, keys_ref, dst_ref, fill_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            fill_ref[...] = jnp.zeros((n_rows + 1,), jnp.int32)

        keys = keys_ref[...].reshape(c)
        fill = fill_ref[...]
        st = starts_ref[...]
        # strict lower triangle via 2D iota (TPU vector units need >= 2D)
        tri = lax.broadcasted_iota(jnp.int32, (c, c), 1) \
            < lax.broadcasted_iota(jnp.int32, (c, c), 0)
        r = ((keys[:, None] == keys[None, :]) & tri).sum(
            axis=1, dtype=jnp.int32
        )
        dst_ref[...] = (st[keys] + fill[keys] + r).reshape(1, c)
        fill_ref[...] = fill.at[keys].add(1)

    dst = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((n_rows + 1,), lambda i: (0,)),
            pl.BlockSpec((1, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, c), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_rows + 1,), jnp.int32)],
        interpret=interpret,
    )(starts, keys_c)
    return _finish(srow, dst.reshape(-1), n)
