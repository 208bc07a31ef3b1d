"""Game host (utils/compile_cache.py): seconds JAX spent tracing,
lowering and compiling since the game process started, as
``jax_compile_seconds_sum`` reads when the window opens — the share of
set-up that is the compiler's."""


def read(scrapes, trace, cell):
    return scrapes["open"]["game"].get("jax_compile_seconds_sum")
