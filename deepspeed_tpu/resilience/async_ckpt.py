"""Async sharded checkpointing: snapshot on the step thread, commit off it.

The save is split exactly along the device/host boundary
(checkpoint/checkpointing.py):

- **snapshot** (``snapshot_checkpoint``) runs on the caller's thread — it is
  the device→host copy plus any multi-host collective gathers, and it is the
  ONLY part that must see a consistent device state. The step programs donate
  their buffers, but ``jax.device_get`` materializes host copies before the
  next step's donation can retire them, so the snapshot needs no fence: the
  exposed cost is the D2H transfer, not a step-long stall.
- **commit** (``write_snapshot``) is pure host file I/O and runs on a
  background writer thread. The commit protocol (tmp dir → fsync → atomic
  rename → ``latest`` via ``os.replace``) means a crash at any point — the
  trainer's or the writer thread's — leaves either the previous committed
  checkpoint or an ignorable ``.tmp`` dir, never a loadable torn state.

One save may be in flight at a time: a new ``save()`` first joins the
previous writer (re-raising its failure rather than dropping it), so the
steady state is "training overlaps one background commit". Multi-host runs
degrade the COMMIT to the caller thread — ``write_snapshot``'s cross-process
barrier must not rendezvous from per-host daemon threads — while keeping the
same two-phase structure and crash-safety via the manifest-last ordering.
"""

import threading
import time

import jax

from ..checkpoint.checkpointing import snapshot_checkpoint, write_snapshot
from ..utils import logger


class AsyncCheckpointer:
    """Owns the background writer for one engine. ``last_stall_ms`` is the
    caller-visible cost of the most recent ``save()`` (snapshot + join of the
    previous writer) — what a step pays for a checkpoint."""

    def __init__(self, engine, save_dir: str, save_latest: bool = True,
                 fence_delay_s: float = 0.0):
        self.engine = engine
        self.save_dir = save_dir
        self.save_latest = save_latest
        self._thread = None
        self._error = None
        self.last_stall_ms = 0.0
        self.saves_started = 0
        self.saves_committed = 0
        # fault-injection hook (ds-tpu crash-sim goodput attribution): a known
        # extra stall inside the snapshot fence, so the run ledger's
        # checkpoint_stall attribution can be checked against ground truth
        self.fence_delay_s = float(fence_delay_s)

    def _commit(self, snapshot):
        try:
            write_snapshot(snapshot, self.save_dir,
                           save_latest=self.save_latest)
            self.saves_committed += 1
            logger.info(f"[deepspeed_tpu] async checkpoint {snapshot['tag']} "
                        f"committed to {self.save_dir}")
        except BaseException as e:   # surfaced by the next save()/wait()
            self._error = e

    def save(self, tag=None, client_state={}):
        """Snapshot now, commit in the background. Blocks only for the
        device→host copy (and any previous still-running commit)."""
        t0 = time.perf_counter()
        self.wait()
        if self.fence_delay_s > 0.0:
            time.sleep(self.fence_delay_s)
        snapshot = snapshot_checkpoint(self.engine, tag=tag,
                                       client_state=client_state)
        self.saves_started += 1
        if snapshot["single_process"]:
            self._thread = threading.Thread(
                target=self._commit, args=(snapshot,),
                name="ds-tpu-ckpt-writer", daemon=True)
            self._thread.start()
        else:
            # multi-host: the commit's cross-process barrier must run on the
            # thread every process drives in lockstep
            self._commit(snapshot)
        self.last_stall_ms = (time.perf_counter() - t0) * 1000.0
        return snapshot["tag"]

    def wait(self):
        """Join the in-flight commit (if any); re-raise its failure."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err
