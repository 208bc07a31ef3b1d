"""goworld_tpu — a TPU-native distributed game-server framework.

A from-scratch rebuild of the capabilities of GoWorld (the reference at
/root/reference: spaces & entities, AOI interest management, reactive
attribute sync, location-transparent entity RPC, entity migration, sharded
services, persistence, hot reload, gate/dispatcher/game deployment), with an
execution model designed for TPUs:

* Each Space's entity population lives as a structure-of-arrays (SoA) pytree
  of JAX arrays on device (``goworld_tpu.core.state``).
* The per-tick hot loop of the reference — AOI sweep + position/attr sync
  (``engine/entity/Entity.go:1208-1267`` ``CollectEntitySyncInfos``) — is a
  single jitted step function over those arrays (``goworld_tpu.core.step``).
* Spaces are pinned to TPU cores; cross-space RPC, AOI halos and entity
  migration ride XLA collectives over ICI (``goworld_tpu.parallel``) instead
  of the reference's dispatcher TCP hop.
* The host side keeps GoWorld's programming model — entity classes with
  lifecycle hooks, reactive attrs, timers, services
  (``goworld_tpu.entity``) — staging events into fixed-capacity per-tick
  batches.

The public facade mirrors the reference's root package ``goworld.go:34-256``.
"""

__version__ = "0.1.0"


def __getattr__(name: str):
    """Lazy facade: ``goworld_tpu.api`` pulls in jax (via the entity
    runtime); dispatcher/gate processes import this package for config and
    wire code only and must NOT initialize a TPU client (a chip
    belongs to one process at a time: the game that serves on it).

    Submodules resolve first (so ``from goworld_tpu import config`` does
    not recurse through the api import); everything else proxies to the
    facade in :mod:`goworld_tpu.api`."""
    import importlib

    try:
        return importlib.import_module(f"goworld_tpu.{name}")
    except ModuleNotFoundError as e:
        # only swallow "no such submodule"; a submodule's own failing
        # import (e.g. a missing third-party dep) must surface as-is
        if e.name != f"goworld_tpu.{name}":
            raise
    from goworld_tpu import api

    try:
        return getattr(api, name)
    except AttributeError:
        raise AttributeError(
            f"module 'goworld_tpu' has no attribute {name!r}"
        ) from None
