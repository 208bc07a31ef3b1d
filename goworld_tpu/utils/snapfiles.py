"""Snapshot file names and the freshest-first candidate walk — jax-free.

The ops CLI (start / watchdog / supervise) and tools/chaos_soak.py are
PARENTS of the game processes that own the accelerator; they decide
restore-vs-cold from these paths and must never import jax to do it
(:mod:`goworld_tpu.freeze`, which reads and writes the files, does).
"""

from __future__ import annotations

import os


def freeze_filename(game_id: int) -> str:
    """Reference ``game%d_freezed.dat`` (``GameService.go:252``)."""
    return f"game{game_id}_freezed.dat"


def checkpoint_filename(game_id: int) -> str:
    return f"game{game_id}_checkpoint.dat"


def chain_key_filename(game_id: int) -> str:
    return f"game{game_id}_ckpt_key.dat"


def chain_delta_filename(game_id: int) -> str:
    return f"game{game_id}_ckpt_delta.dat"


def snapshot_candidates(game_id: int, directory: str = ".") -> list[str]:
    """Existing snapshot files for a game, freshest (by mtime) first:
    the freeze file (intentional reload), the periodic crash-recovery
    checkpoint, and the quantized/delta snapshot chain (delta first —
    it is the newest state; a corrupt or base-mismatched delta raises
    CorruptSnapshotError and the walk falls back to its keyframe).
    Mtime orders because any can be stale — a freeze file left over
    from an old reload must not shadow hours of newer checkpoints
    after a crash, and vice versa."""
    cands = []
    for p in (os.path.join(directory, freeze_filename(game_id)),
              os.path.join(directory, checkpoint_filename(game_id)),
              os.path.join(directory, chain_delta_filename(game_id)),
              os.path.join(directory, chain_key_filename(game_id))):
        try:
            cands.append((os.path.getmtime(p), p))
        except OSError:
            continue
    return [p for _, p in sorted(cands, reverse=True)]


def latest_snapshot_path(game_id: int, directory: str = ".") -> str | None:
    cands = snapshot_candidates(game_id, directory)
    return cands[0] if cands else None
