"""Checkpoint / resume via orbax.

The reference delegates checkpointing entirely to workloads and cloud storage
(models read from GCS/S3/PVC — SURVEY.md §5.4); job restart just reruns the
container. Here restart-from-checkpoint is a framework capability: the train
loop saves sharded TrainState periodically (ASYNC — the device keeps
training while orbax commits in the background, so checkpoint cadence
doesn't trade against MFU) and a final synchronous save on preemption, and
resumes from the latest step found. Multi-host safe — every process
participates in the save (orbax handles the per-shard writes + atomic
commit).

No file of a checkpoint is large: orbax's default packs arrays into data
files of 2 GiB and more, which a per-process file-size limit
(``RLIMIT_FSIZE``) refuses with ``EFBIG`` — the llama-1b state lost its
save that way on a TPU host. ``DATA_FILE_BYTES`` caps them instead; an
array larger than that is stored in chunks."""

from __future__ import annotations

import os
from typing import Any

import orbax.checkpoint as ocp

# A data file is closed once it reaches this size, and no chunk of an
# array is larger, so a file stays under twice this (32 MiB).
DATA_FILE_BYTES = 16 << 20


def _manager(ckpt_dir: str, max_to_keep: int = 3, *,
             async_saves: bool = False) -> ocp.CheckpointManager:
    return ocp.CheckpointManager(
        os.path.abspath(ckpt_dir),
        options=ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep, create=True,
            enable_async_checkpointing=async_saves,
        ),
    )


def _save_args(state: Any) -> ocp.args.PyTreeSave:
    return ocp.args.PyTreeSave(
        state, ocdbt_target_data_file_size=DATA_FILE_BYTES)


def _restore_args(abstract_state: Any) -> ocp.args.PyTreeRestore:
    return ocp.args.PyTreeRestore(
        abstract_state,
        restore_args=ocp.checkpoint_utils.construct_restore_args(
            abstract_state))


class Checkpointer:
    """One persistent manager for a training run.

    ``save`` returns as soon as the on-device state is snapshotted;
    serialization and the atomic commit run on orbax's background thread
    (enable_async_checkpointing). ``wait`` blocks until every pending
    save is durable — call it before exiting (and on the preemption
    path, where the final save must land inside the grace window).
    """

    def __init__(self, ckpt_dir: str, *, max_to_keep: int = 3,
                 async_saves: bool = True):
        self.ckpt_dir = os.path.abspath(ckpt_dir)
        self._mgr = _manager(ckpt_dir, max_to_keep,
                             async_saves=async_saves)

    def save(self, step: int, state: Any, *, force: bool = False) -> None:
        self._mgr.save(step, args=_save_args(state), force=force)

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore_latest(self, abstract_state: Any
                       ) -> tuple[Any, int] | None:
        step = self._mgr.latest_step()
        if step is None:
            return None
        state = self._mgr.restore(step, args=_restore_args(abstract_state))
        return state, step

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()


def save(ckpt_dir: str, step: int, state: Any, *, force: bool = False) -> None:
    mgr = _manager(ckpt_dir)
    mgr.save(step, args=_save_args(state), force=force)
    mgr.wait_until_finished()
    mgr.close()


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    mgr = _manager(ckpt_dir)
    step = mgr.latest_step()
    mgr.close()
    return step


def restore(ckpt_dir: str, step: int, abstract_state: Any) -> Any:
    """Restore into the structure/shardings of ``abstract_state`` (build it
    with jax.eval_shape + shardings so restoring places shards directly on
    device)."""
    mgr = _manager(ckpt_dir)
    state = mgr.restore(step, args=_restore_args(abstract_state))
    mgr.close()
    return state


def restore_latest(ckpt_dir: str, abstract_state: Any) -> tuple[Any, int] | None:
    step = latest_step(ckpt_dir)
    if step is None:
        return None
    return restore(ckpt_dir, step, abstract_state), step
