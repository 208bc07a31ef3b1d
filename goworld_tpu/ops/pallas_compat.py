"""Shared execution-mode policy for the Pallas kernels.

Every Pallas kernel in the tree (the counting-sort fill pass in
:mod:`~goworld_tpu.ops.sort`, the fused AOI back half in
:mod:`~goworld_tpu.ops.aoi`, the async halo in
:mod:`~goworld_tpu.parallel.halo`) has one hardware lowering and one
interpret-mode form, and the backend decides which runs:

* off-TPU, interpret mode — tier-1 runs on CPU, and an operator
  typo'ing ``sort_impl = pallas`` into a CPU deployment's ini should
  get a slow but correct game, not a crash loop. Loud exactly once per
  kernel per process: interpret mode emulates the kernel op-by-op
  (orders of magnitude slower than the native XLA impls), so a silent
  switch would look like a perf regression with no cause in the logs;
* on a TPU backend, the hardware lowering and nothing else. Asking for
  interpret mode there raises: an emulated kernel on the chip would be
  a run that looks like the kernel ran. A kernel the chip's compiler
  refuses is refused by its option's validation with the compiler's
  own message (``FUSED_SWEEP_REFUSAL``) — never a quiet switch to
  another implementation.
"""

from __future__ import annotations

from goworld_tpu.utils import log

logger = log.get("ops.pallas")

# kernels that already warned this process (one line per kernel, not
# one per trace — jit re-traces must not spam)
_WARNED: set[str] = set()

# What Mosaic (jax 0.9.0 / libtpu 0.0.34, v5e) says to ops/aoi.py
# _sweep_fused at n = 131,072, k/cell_cap = 32/12 and 64/32, once its
# block specs are made legal: the per-query window read
# ``s_ref[:, pl.ds(lo, 3*cell_cap)]`` is a lane-dimension slice whose
# start is a run-time scalar, and the TPU vector load needs that start
# provably 128-aligned. Repairing it is a different gather (aligned
# loads + lane rotates, or per-window DMA), not layout work — so the
# option is refused on a TPU backend until the kernel zoo is decided
# (ROADMAP S3/D1).
FUSED_SWEEP_REFUSAL = (
    "sweep_impl='fused' is refused on a TPU backend: Mosaic failed to "
    "compile TPU kernel: cannot statically prove that index in "
    "dimension 1 is a multiple of 128 (the vector.load of the "
    "[3, 3*cell_cap] candidate window at a run-time lane offset of the "
    "sorted view, ops/aoi.py _sweep_fused). Use sweep_impl='ranges' "
    "(the default) — same candidates, same results."
)


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU."""
    import jax

    return jax.default_backend() == "tpu"


def resolve_interpret(kernel: str, interpret: bool | None = None) -> bool:
    """The ``interpret=`` flag a Pallas kernel runs with.

    On a TPU backend: False (hardware lowering); an explicit
    ``interpret=True`` raises. Elsewhere: True (interpret mode) unless
    the caller passed a value, logging a one-time warning naming the
    kernel so the CPU-emulation cost is attributable from the logs
    alone.
    """
    if on_tpu():
        if interpret:
            raise RuntimeError(
                f"Pallas kernel {kernel!r}: interpret mode requested on "
                "a TPU backend — the chip runs the hardware lowering or "
                "nothing"
            )
        return False
    if interpret is not None:
        return interpret
    if kernel not in _WARNED:
        _WARNED.add(kernel)
        logger.warning(
            "Pallas kernel %r: no TPU backend — running in interpret "
            "mode (correct but slow CPU emulation; pick a non-pallas "
            "impl off-TPU for production)", kernel,
        )
    return True
