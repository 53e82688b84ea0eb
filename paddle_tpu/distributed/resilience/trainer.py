"""Rank-death recovery: one per-step poll over every failure signal.

The signals already existed separately — ``PreemptionHandler`` (SIGTERM
a few tens of seconds before a TPU-VM spot/maintenance kill),
``ElasticManager.should_checkpoint()`` (a peer's broadcast notice),
``ElasticManager.pod_status()`` (TTL-lease membership: a SIGKILLed rank
stops heartbeating), and the comm watchdog (a wedged cross-host
collective). :class:`ResilientTrainer` composes them into one
``poll()`` the step loop calls once per step:

* preemption notice (own SIGTERM or a peer's)  →  snapshot NOW
  (blocking — the VM is about to die) and return ``CHECKPOINT_EXIT``;
  the process exits cleanly and the launcher relaunches the survivors.
* lost heartbeat / collective timeout  →  ``RESTART``: the process
  exits non-zero, the elastic launcher re-ranks the survivors
  (world-size change included), and the relaunched generation restores
  from the latest COMMITTED checkpoint via reshard-on-load.
* otherwise  →  an async snapshot every ``snapshot_every`` steps whose
  I/O overlaps the next captured steps, then ``CONTINUE``.

Every transition lands in the flight recorder and the
``resilience.{preemptions,rank_deaths,restores,resume_step}`` metrics,
so a post-mortem can reconstruct exactly why a generation ended.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Optional, Tuple

import numpy as np

from ...observability import flight_recorder as _flight
from ...observability import incident as _incident
from ...observability import metrics as _metrics
from ...observability import perf as _perf_mod
from ..checkpoint.save_load import latest_checkpoint
from .anomaly import AnomalyAction, AnomalyDetector
from .checkpointer import AsyncCheckpointer, restore_state

__all__ = ["ResilientTrainer", "TrainerAction"]

_M_PREEMPTIONS = _metrics.registry().counter(
    "resilience.preemptions",
    help="preemption notices this trainer checkpointed-and-exited on")
_M_RANK_DEATHS = _metrics.registry().counter(
    "resilience.rank_deaths",
    help="lost-member / collective-timeout events that forced a restart")
_M_RESTORES = _metrics.registry().counter(
    "resilience.restores",
    help="restores from a committed checkpoint generation")
_M_RESUME_STEP = _metrics.registry().gauge(
    "resilience.resume_step",
    help="step this process resumed from after its last restore")
_M_REWINDS = _metrics.registry().counter(
    "anomaly.rewinds",
    help="anomaly-triggered restores from a committed generation")
_M_REWIND_SECONDS = _metrics.registry().histogram(
    "anomaly.rewind_seconds",
    help="wall time of each anomaly rewind (restore + stream reposition)")


_record = _flight.record_event


class TrainerAction:
    CONTINUE = "continue"
    CHECKPOINT_EXIT = "checkpoint_exit"   # preempted: snapshot taken, exit 0
    RESTART = "restart"                   # lost rank: exit for re-rank+restore
    REWIND = "rewind"                     # numerical fault: restore in process
    COMPLETED = "completed"


class ResilientTrainer:
    """Wires checkpointer + elastic membership + watchdog into a loop.

    ``state_fn()`` returns the live state tree to snapshot (model
    ``state_dict`` + optimizer ``state_dict`` + anything else);
    ``apply_fn(rebuilt, step)`` pushes restored values back into owners
    that return copies (e.g. ``optimizer.set_state_dict``) — Tensor
    leaves are already restored in place before it runs.
    """

    def __init__(self, checkpointer: AsyncCheckpointer,
                 state_fn: Callable[[], Any],
                 apply_fn: Optional[Callable[[Any, int], None]] = None,
                 elastic=None, watchdog=None,
                 snapshot_every: int = 50,
                 install_signal: bool = True,
                 signum: Optional[int] = None,
                 anomaly: Optional[AnomalyDetector] = None,
                 optimizer=None, data_loader=None):
        self.checkpointer = checkpointer
        self.anomaly = anomaly
        self.optimizer = optimizer   # sentinel source (consume_anomaly)
        self.data_loader = data_loader
        if data_loader is not None:
            # journal the stream position next to the model/opt state:
            # the loader's (epoch, cursor, seed) are host scalars, so
            # they land in the generation's host_state.json and both
            # preemption-resume and rewind replay the exact batch order
            self._user_state_fn = state_fn
            state_fn = lambda: {"train": self._user_state_fn(),  # noqa: E731
                                "data_stream": data_loader.state_dict()}
        self.state_fn = state_fn
        self.apply_fn = apply_fn
        self.elastic = elastic
        self._skip_window: Optional[Tuple[int, int]] = None
        self.snapshot_every = max(0, int(snapshot_every))
        self.handler = None
        if elastic is not None and install_signal:
            from ..fleet.elastic import PreemptionHandler
            self.handler = PreemptionHandler(elastic).install(signum)
        # incident bundles land next to the checkpoint generations, the
        # artifact an operator already inspects after a bad run
        self._incident_root = os.path.join(checkpointer.root, "incidents")
        _incident.attach_root(self._incident_root)
        self._comm_timeout = threading.Event()
        self._watchdog = watchdog
        if watchdog is not None:
            watchdog.add_handler(self._on_comm_timeout)
        self._preempted = False
        self._rank_death = False
        self._next_member_check = 0.0
        self.resume_step = 0

    # -- watchdog fan-in -----------------------------------------------------
    def _on_comm_timeout(self, task) -> None:
        # runs on the watchdog scan thread: flag only, poll() acts on it
        if not self._comm_timeout.is_set():
            self._comm_timeout.set()
            _record("resilience.comm_timeout",
                    (task.name, f"{task.elapsed():.1f}s"))
            # forensics before the RESTART exit: the classified stacks
            # name the thread wedged in the collective (the post-restart
            # log only knows the timeout fired)
            _incident.record_incident(
                "trainer.comm_timeout",
                root=self._incident_root,
                attrs={"task": task.name,
                       "elapsed_s": round(task.elapsed(), 1)})

    # -- restore -------------------------------------------------------------
    def restore(self) -> int:
        """Restore from the newest committed generation (if any) and
        return the step to resume FROM (committed step + 1, or 0)."""
        path = latest_checkpoint(self.checkpointer.root)
        if path is None:
            return 0
        rebuilt, step = restore_state(self.state_fn(), path)
        resume = (step + 1) if step is not None else 0
        if self.data_loader is not None:
            stream = rebuilt.get("data_stream")
            if stream is not None:
                self.data_loader.load_state_dict(stream)
            rebuilt = rebuilt.get("train")
        if self.apply_fn is not None:
            self.apply_fn(rebuilt, resume)
        _M_RESTORES.inc()
        _M_RESUME_STEP.set(float(resume))
        _record("resilience.restore", (path, resume))
        self.resume_step = resume
        return resume

    # -- anomaly policy ------------------------------------------------------
    def observe(self, step: int, loss=None) -> str:
        """Feed the per-step anomaly signals (loss + the optimizer's
        device sentinel) to the detector. Returns ``CONTINUE`` or
        ``REWIND`` — the in-device sentinel already neutralized a SKIP,
        so nothing more is needed for it here."""
        if self.anomaly is None:
            return TrainerAction.CONTINUE
        skipped, gnorm = False, None
        if self.optimizer is not None \
                and hasattr(self.optimizer, "consume_anomaly"):
            sent = self.optimizer.consume_anomaly()
            if sent is not None:
                skipped, gnorm = sent
        lv = None
        if loss is not None:
            arr = getattr(loss, "_data", loss)
            try:
                lv = float(np.asarray(arr))
            except (TypeError, ValueError):
                lv = None   # step_fn returned something that isn't a loss
        act = self.anomaly.observe(step, lv, skipped=skipped,
                                   grad_norm=gnorm)
        if act == AnomalyAction.REWIND:
            return TrainerAction.REWIND
        return TrainerAction.CONTINUE

    def rewind(self, step: int) -> Optional[int]:
        """Anomaly escalation: restore the newest COMMITTED generation
        (params, optimizer state, data-stream position) and mark the
        poison data window ``[first_bad_step, step]`` for deterministic
        skipping on the replay. Returns the step to resume from, or
        None when no committed generation exists (the sentinel's
        in-device skips keep the run safe; training just continues)."""
        self.checkpointer.wait()   # an in-flight async write may be the
        #                            generation this rewind needs
        path = latest_checkpoint(self.checkpointer.root)
        if path is None:
            _record("anomaly.rewind_unavailable", (step,))
            if self.anomaly is not None:
                self.anomaly.reset()
            return None
        t0 = time.monotonic()
        first_bad = step
        if self.anomaly is not None \
                and self.anomaly.first_bad_step is not None:
            first_bad = self.anomaly.first_bad_step
        resume = self.restore()
        self._skip_window = (first_bad, step)
        _M_REWINDS.inc()
        _M_REWIND_SECONDS.observe(time.monotonic() - t0)
        _record("anomaly.rewind", (step, resume, first_bad))
        # the rewind destroys the in-process evidence (params, optimizer
        # state, anomaly history are all restored over): bundle the
        # metrics/flight/trace view of the poisoned window first
        _incident.record_incident(
            "trainer.rewind", root=self._incident_root, step=step,
            attrs={"resume_step": resume, "first_bad_step": first_bad,
                   "restored_from": path})
        if self.anomaly is not None:
            self.anomaly.reset()
        return resume

    def should_skip(self, step: int) -> bool:
        """True while ``step`` sits inside the poison data window of the
        last rewind: the caller drops that step's batch (advancing its
        data stream) instead of training on it."""
        w = self._skip_window
        return w is not None and w[0] <= step <= w[1]

    def should_skip_block(self, start: int, k: int) -> bool:
        """K-step-block variant of :meth:`should_skip`: True when ANY of
        the block's steps ``[start, start + k)`` overlaps the poison
        window. A K-step block is one fused executable — it cannot drop
        a single interior step, so the caller drops the WHOLE block
        (advancing its ring cursor by one block). The window is measured
        in steps but consumed in K-blocks; the boundary over-skip is at
        most K-1 known-adjacent batches."""
        w = self._skip_window
        return w is not None and start <= w[1] and w[0] <= start + k - 1

    # -- per-step poll -------------------------------------------------------
    def poll(self, step: int, block_steps: int = 1) -> str:
        """Call once per training step, AFTER the step ran (state holds
        replay outputs, safe to snapshot). Returns a TrainerAction.

        Under multi-step capture the caller polls once per K-step block
        with ``block_steps=K``; periodic snapshots then fire on the
        first block boundary at or past each ``snapshot_every`` multiple
        (a crossing condition — ``step % snapshot_every == 0`` alone
        never fires when ``snapshot_every`` is not a multiple of K)."""
        preempted = self._poll_preempted()
        death = False
        if not preempted:
            death = self._poll_rank_death(step)
            if death:
                # a peer's notice can land BETWEEN the two store reads:
                # its departure from membership and its broadcast are
                # not atomic. Preemption outranks death — re-check, or
                # this rank restarts instead of checkpointing.
                preempted = self._poll_preempted()
        if preempted:
            if not self._preempted:
                self._preempted = True
                _M_PREEMPTIONS.inc()
                _record("resilience.preempted", (step,))
            # the host is about to die: the snapshot must be durable
            # before this process exits, so this save blocks
            self.checkpointer.save(self.state_fn(),
                                   self._agree_preempt_step(step),
                                   block=True)
            if self.checkpointer.last_error is not None:
                # the snapshot did NOT commit (disk full, barrier timed
                # out on a dead peer): exiting "clean" would claim a
                # durability this process doesn't have — restart instead,
                # and the relaunch restores the last committed generation
                _record("resilience.preempt_save_failed",
                        (step, repr(self.checkpointer.last_error)))
                return TrainerAction.RESTART
            return TrainerAction.CHECKPOINT_EXIT
        if death:
            if not self._rank_death:
                self._rank_death = True
                _M_RANK_DEATHS.inc()
                _record("resilience.rank_death", (step,))
            return TrainerAction.RESTART
        # "did the last block_steps steps cross a snapshot_every
        # multiple?" — reduces to `step % snapshot_every == 0` when
        # block_steps is 1, and stays correct when K-misaligned epoch
        # tails shift the block phase off multiples of K
        bk = max(1, int(block_steps))
        if self.snapshot_every and step > 0 \
                and (step // self.snapshot_every) \
                > max(0, (step - bk) // self.snapshot_every):
            if self.anomaly is not None \
                    and self.anomaly.first_bad_step is not None:
                # mid-bad-streak: loss spikes do NOT skip the update
                # (only the device sentinel's nonfinite path does), so a
                # snapshot here could commit already-poisoned params —
                # the very generation a rewind would then restore.
                # Skip the periodic save until the streak resolves
                _record("anomaly.snapshot_suppressed",
                        (step, self.anomaly.first_bad_step))
            else:
                self.checkpointer.save(self.state_fn(), step)
        return TrainerAction.CONTINUE

    def _poll_preempted(self) -> bool:
        if self.handler is not None and self.handler.process():
            return True
        return self.elastic is not None and self.elastic.should_checkpoint()

    def _agree_preempt_step(self, step: int) -> int:
        """Agree on ONE generation tag for the preemption snapshot.

        Peers observe a preemption notice at slightly different local
        steps, and the commit barrier keys on the generation name — a
        per-rank tag would leave every rank's snapshot uncommitted. The
        first observer claims the tag (atomic store add) with its own
        step; everyone else adopts it, scoped by the notice payload so a
        later preemption in a relaunched generation negotiates afresh."""
        store = self.checkpointer.store
        if store is None or self.checkpointer.world_size <= 1 \
                or self.elastic is None:
            return step
        raw = store.get(f"{self.elastic.prefix}/preempt_any", wait=False)
        scope = raw.decode().replace("/", "_") if raw else "local"
        key = f"{self.elastic.prefix}/ckpt_tag/{scope}"
        try:
            if store.add(f"{key}/claim", 1) == 1:
                store.set(key, str(step))
                return step
            return int(store.get(key, wait=True, timeout_ms=10_000))
        except Exception:
            # store unreachable mid-preemption: save under the local tag
            # anyway — worst case the barrier times the commit out and
            # the last periodic generation stays the restore point
            return step

    def _poll_rank_death(self, step: int) -> bool:
        if self._comm_timeout.is_set():
            return True
        if self.elastic is None:
            return False
        # membership needs O(n) store reads — poll at lease granularity,
        # not step granularity (the one-pass snapshot keeps it 1 scan)
        now = time.monotonic()
        if now < self._next_member_check:
            return False
        self._next_member_check = now + max(0.5, self.elastic.ttl / 2)
        from ..fleet.elastic import ElasticStatus
        return self.elastic.pod_status() in (ElasticStatus.RESTART,
                                             ElasticStatus.HOLD)

    def close(self) -> None:
        """Drain pending writes and detach the signal/watchdog hooks
        (restores the previous SIGTERM handler — test and notebook
        hygiene; a real job just exits)."""
        self.checkpointer.wait()
        if self.handler is not None:
            self.handler.uninstall()
            self.handler = None
        if self._watchdog is not None:
            try:
                self._watchdog._handlers.remove(self._on_comm_timeout)
            except ValueError:
                # already detached (double close)
                pass
            self._watchdog = None

    # -- convenience loop ----------------------------------------------------
    def run(self, step_fn: Callable[[int], Any], max_steps: int,
            final_snapshot: bool = True,
            skip_fn: Optional[Callable[[int], None]] = None) -> str:
        """Restore, then drive ``step_fn(step)`` with a poll per step.

        With an :class:`AnomalyDetector` configured, ``step_fn``'s
        return value is observed as the loss each step; a REWIND
        escalation restores the newest committed generation in process
        and replays, calling ``skip_fn(step)`` instead of ``step_fn``
        for every step inside the poison data window (the caller drops
        that step's batch there, keeping its stream aligned).

        Also catches the captured-step "donated inputs were consumed"
        replay failure: when a committed generation exists, the loop
        restores in process and resumes (bounded-loss) instead of dying
        with unusable state."""
        step = self.restore()
        recovered_at = -1
        while step < max_steps:
            if self.should_skip(step):
                if skip_fn is not None:
                    skip_fn(step)
                step += 1
                continue
            seq0 = _perf_mod.step_seq()
            t0 = time.perf_counter()
            try:
                out = step_fn(step)
            except RuntimeError as e:
                if ("donated inputs were consumed" in str(e)
                        and recovered_at != step
                        and latest_checkpoint(self.checkpointer.root)
                        is not None):
                    recovered_at = step
                    step = self.restore()
                    continue
                raise
            if _perf_mod.step_seq() == seq0:
                # step_fn did not self-report (raw closure, not hapi):
                # record the wall total so decomposition still counts it
                _perf_mod.record_step(time.perf_counter() - t0)
            if self.anomaly is not None \
                    and self.observe(step, out) == TrainerAction.REWIND:
                resumed = self.rewind(step)
                if resumed is not None:
                    step = resumed
                    continue
            action = self.poll(step)
            if action != TrainerAction.CONTINUE:
                self.checkpointer.wait()
                return action
            step += 1
        if final_snapshot:
            self.checkpointer.save(self.state_fn(), max_steps - 1,
                                   block=True)
        self.checkpointer.wait()
        return TrainerAction.COMPLETED

    def run_data(self, train_fn: Callable[[int, Any], Any],
                 max_steps: int, final_snapshot: bool = True) -> str:
        """Like :meth:`run`, but the trainer OWNS the data iteration
        over its ``data_loader``: ``train_fn(step, batch)`` trains one
        step. Epochs chain automatically; a restore or rewind drops the
        live iterator so the next batch comes from the loader's restored
        stream position, and poison-window steps consume (drop) their
        batch without training — which is exactly what makes the replay
        deterministic: every step index maps to the same batch on every
        incarnation."""
        if self.data_loader is None:
            raise ValueError("run_data requires the data_loader the "
                             "trainer was constructed with")
        it = [None]

        def next_batch():
            empties = 0
            while True:
                if it[0] is None:
                    it[0] = iter(self.data_loader)
                try:
                    return next(it[0])
                except StopIteration:
                    # one empty pass is legal (a resume positioned at an
                    # epoch boundary); two in a row = an empty loader
                    empties += 1
                    if empties >= 2:
                        raise RuntimeError(
                            "run_data: data_loader yielded no batches")
                    it[0] = None   # epoch boundary: roll into the next

        step = self.restore()
        recovered_at = -1
        while step < max_steps:
            t_w = time.perf_counter()
            batch = next_batch()
            _perf_mod.note_data_wait(time.perf_counter() - t_w)
            if self.should_skip(step):
                step += 1
                continue
            seq0 = _perf_mod.step_seq()
            t0 = time.perf_counter()
            try:
                out = train_fn(step, batch)
            except RuntimeError as e:
                if ("donated inputs were consumed" in str(e)
                        and recovered_at != step
                        and latest_checkpoint(self.checkpointer.root)
                        is not None):
                    recovered_at = step
                    step = self.restore()
                    it[0] = None
                    continue
                raise
            if _perf_mod.step_seq() == seq0:
                _perf_mod.record_step(time.perf_counter() - t0)
            if self.anomaly is not None \
                    and self.observe(step, out) == TrainerAction.REWIND:
                resumed = self.rewind(step)
                if resumed is not None:
                    step = resumed
                    it[0] = None
                    continue
            action = self.poll(step)
            if action != TrainerAction.CONTINUE:
                self.checkpointer.wait()
                return action
            step += 1
        if final_snapshot:
            self.checkpointer.save(self.state_fn(), max_steps - 1,
                                   block=True)
        self.checkpointer.wait()
        return TrainerAction.COMPLETED

    def run_blocks(self, train_block_fn: Callable[[int, Any], Any],
                   max_steps: int, k: int,
                   final_snapshot: bool = True) -> str:
        """Multi-step variant of :meth:`run_data`: the trainer drives
        the loader's K-step ring (``fill_ring(k)``) and
        ``train_block_fn(start_step, block)`` trains ``block.size``
        steps at once, returning the block's per-step losses. The
        loader's committed cursor only ever advances to block
        boundaries, so snapshots, restores and rewinds all land exactly
        on one; poison windows are consumed whole-block (the ring draws
        the batches — advancing the committed cursor — without
        training). Losses are observed per step in order, so anomaly
        escalation fires at the same loss index it would single-step."""
        if self.data_loader is None:
            raise ValueError("run_blocks requires the data_loader the "
                             "trainer was constructed with")
        gen = [None]

        def next_block():
            empties = 0
            while True:
                if gen[0] is None:
                    gen[0] = self.data_loader.fill_ring(k)
                try:
                    return next(gen[0])
                except StopIteration:
                    empties += 1
                    if empties >= 2:
                        raise RuntimeError(
                            "run_blocks: data_loader yielded no batches")
                    gen[0] = None   # epoch boundary: roll into the next

        step = self.restore()
        recovered_at = -1
        while step < max_steps:
            t_w = time.perf_counter()
            block = next_block()
            _perf_mod.note_data_wait(time.perf_counter() - t_w)
            if self.should_skip_block(step, block.size):
                self.data_loader._commit_stream_state(block.stream_state)
                step += block.size
                continue
            seq0 = _perf_mod.step_seq()
            t0 = time.perf_counter()
            try:
                out = train_block_fn(step, block)
            except RuntimeError as e:
                if ("donated inputs were consumed" in str(e)
                        and recovered_at != step
                        and latest_checkpoint(self.checkpointer.root)
                        is not None):
                    recovered_at = step
                    step = self.restore()
                    gen[0] = None
                    continue
                raise
            if _perf_mod.step_seq() == seq0:
                _perf_mod.record_step(time.perf_counter() - t0)
            self.data_loader._commit_stream_state(block.stream_state)
            if self.anomaly is not None:
                outs = list(out) if isinstance(out, (list, tuple)) else [out]
                rewound = None
                for i, lv in enumerate(outs):
                    if self.observe(step + i, lv) == TrainerAction.REWIND:
                        rewound = self.rewind(step + i)
                        break
                if rewound is not None:
                    step = rewound
                    gen[0] = None
                    continue
            last = step + block.size - 1
            action = self.poll(last, block_steps=block.size)
            if action != TrainerAction.CONTINUE:
                self.checkpointer.wait()
                return action
            step += block.size
        if final_snapshot:
            self.checkpointer.save(self.state_fn(), step - 1, block=True)
        self.checkpointer.wait()
        return TrainerAction.COMPLETED
