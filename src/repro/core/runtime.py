"""Funky runtime: the OCI-compliant low-level task runtime (paper §3.5).

Beyond the standard OCI lifecycle (create/start/kill/delete) it implements
the five Funky commands of Table 3:

    evict <cid>                  save device context to host RAM, free slot
    resume <cid[, node_id]>      resume locally or migrate from node_id
    checkpoint <cid>             snapshot VM+device state to disk
    replicate <cid, node_id>     clone a (possibly running) task onto a node
    update <cid, vfpga_num>      vertical scaling

One runtime daemon runs per worker node; each task gets a driver thread (the
guest vCPU) that calls ``task.step()`` through a run-gate, so orchestration
commands always land on request boundaries.
"""

from __future__ import annotations

import enum
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from repro import obs
from repro.core.guest import FunkyCL
from repro.core.monitor import Monitor, MonitorState, NoSliceAvailable
from repro.core.state import GuestState, TaskSnapshot
from repro.core.tasks import GuestTask, TaskImage
from repro.core.vslice import SliceAllocator


class TaskStatus(enum.Enum):
    CREATED = "created"
    RUNNING = "running"
    EVICTED = "evicted"
    DONE = "done"
    FAILED = "failed"
    REMOVED = "removed"


@dataclass
class TaskRecord:
    cid: str
    image: TaskImage
    task: GuestTask
    monitor: Monitor
    guest_state: GuestState
    status: TaskStatus = TaskStatus.CREATED
    priority: int = 0
    preemptible: bool = True
    vfpga_num: int = 1
    annotations: dict = field(default_factory=dict)
    driver: Optional[threading.Thread] = None
    run_gate: threading.Event = field(default_factory=threading.Event)
    stop_flag: bool = False
    step_lock: threading.Lock = field(default_factory=threading.Lock)
    error: Optional[BaseException] = None
    latest_snapshot: Optional[str] = None
    boot_seconds: float = 0.0
    timeline: list = field(default_factory=list)

    def log(self, event: str, **kw):
        self.timeline.append((time.time(), event, kw))


class FunkyRuntime:
    def __init__(self, node_id: str, allocator: SliceAllocator,
                 ckpt_root: str = "/tmp/funky-ckpt", telemetry=None,
                 chaos=None):
        self.node_id = node_id
        self.allocator = allocator
        self.ckpt_root = ckpt_root
        # fault-injection plan (repro.chaos.FaultPlan); threaded into every
        # Monitor this runtime builds and into the checkpoint writer
        self.chaos = chaos
        self.tasks: Dict[str, TaskRecord] = {}
        self._lock = threading.Lock()
        self.alive = True
        # node-level program ("bitstream") cache: tasks sharing an image hit
        # warm compiled executables — the paper's warmed-up-FPGA behavior
        from repro.core.programs import ProgramCache
        from repro.scaling.metrics import MetricsRegistry

        self.programs = ProgramCache()
        self.telemetry = (telemetry if telemetry is not None
                          else MetricsRegistry())
        os.makedirs(ckpt_root, exist_ok=True)

    # ------------------------------------------------------------------
    # OCI lifecycle
    # ------------------------------------------------------------------
    def create(self, cid: str, image: TaskImage,
               annotations: Optional[dict] = None) -> TaskRecord:
        t0 = time.perf_counter()
        annotations = dict(annotations or {})
        rec = TaskRecord(
            cid=cid, image=image, task=image.instantiate(),
            monitor=Monitor(cid, self.allocator, programs=self.programs,
                            telemetry=self.telemetry, chaos=self.chaos),
            guest_state=GuestState(seed=image.seed),
            priority=int(annotations.get("priority", 0)),
            preemptible=annotations.get("preemptible", "true") == "true",
            annotations=annotations,
        )
        rec.boot_seconds = time.perf_counter() - t0
        rec.log("create", node=self.node_id)
        with self._lock:
            self.tasks[cid] = rec
        return rec

    def start(self, cid: str):
        rec = self.tasks[cid]
        if rec.status is TaskStatus.EVICTED:
            return self.resume(cid)
        rec.log("start", node=self.node_id)
        self._spawn_driver(rec, restore=False)

    def _spawn_driver(self, rec: TaskRecord, restore: bool):
        rec.run_gate.set()
        rec.stop_flag = False

        def drive():
            cl = FunkyCL(rec.monitor)
            try:
                rec.task.setup(cl, rec.guest_state, restore=restore)
                rec.status = TaskStatus.RUNNING
                done = False
                while not done:
                    rec.run_gate.wait()
                    if rec.stop_flag:
                        return
                    with rec.step_lock:
                        # re-check under the lock: we may have been parked
                        # (evict/checkpoint) while waiting to acquire it
                        if not rec.run_gate.is_set():
                            continue
                        with obs.span("runtime.step"):
                            done = rec.task.step(cl, rec.guest_state)
                rec.task.teardown(cl, rec.guest_state)
                rec.status = TaskStatus.DONE
                rec.log("done", step=rec.guest_state.step)
            except NoSliceAvailable as e:
                rec.status = TaskStatus.FAILED
                rec.error = e
                rec.log("failed", error="NoSliceAvailable")
            except BaseException as e:  # noqa: BLE001
                rec.status = TaskStatus.FAILED
                rec.error = e
                rec.log("failed", error=repr(e))

        rec.driver = threading.Thread(
            target=drive, name=f"driver-{rec.cid}", daemon=True)
        rec.driver.start()

    def _park_driver(self, rec: TaskRecord):
        """Block the driver between steps (cooperative pause)."""
        rec.run_gate.clear()
        # wait until the in-flight step (if any) finishes its enqueues
        with rec.step_lock:
            pass

    def drain(self, cid: str, timeout_s: float = 30.0) -> dict:
        """Graceful decommission: flip the task into its draining state
        (no new admissions) and wait until the work it already holds has
        finished — request-boundary scale-in without requeueing.  Tasks
        with no drain hook return immediately; a wedged drain times out
        and the caller falls back to the hard kill."""
        rec = self.tasks[cid]
        if rec.status is not TaskStatus.RUNNING:
            return {"drained": True, "waited_s": 0.0}
        if type(rec.task).drain is GuestTask.drain:
            # no draining notion (train tasks etc.): don't stall the
            # scale-in for the full timeout waiting on a no-op hook
            return {"drained": True, "waited_s": 0.0}
        t0 = time.perf_counter()
        rec.task.drain()
        # the driver notices the drained state on its next step and runs
        # teardown, flipping the status off RUNNING — wait (bounded) for
        # that so the follow-up kill finds a finished task
        deadline = t0 + timeout_s
        while (time.perf_counter() < deadline
               and rec.status is TaskStatus.RUNNING):
            time.sleep(0.005)
        waited = time.perf_counter() - t0
        stats = {"drained": rec.status is not TaskStatus.RUNNING
                 or rec.task.drained, "waited_s": waited}
        rec.log("drain", **stats)
        return stats

    def kill(self, cid: str):
        rec = self.tasks[cid]
        rec.stop_flag = True
        rec.run_gate.set()
        if rec.driver is not None:
            rec.driver.join(timeout=30)
        if rec.monitor.state in (MonitorState.RUNNING,):
            rec.monitor.vfpga_exit()
        try:
            rec.task.on_kill()
        except Exception:  # noqa: BLE001 - best-effort cleanup hook
            pass
        rec.status = TaskStatus.REMOVED
        rec.log("kill")

    def crash(self, cid: str):
        """Simulated hard crash of one task: the driver is stopped and the
        slice freed, but — unlike ``kill`` — the graceful ``on_kill`` hook
        never runs, so nothing is evacuated or requeued from inside the
        task.  Whatever recovery happens must come from outside (router
        lease replay + snapshot restore)."""
        rec = self.tasks[cid]
        rec.stop_flag = True
        rec.run_gate.set()
        if rec.driver is not None:
            rec.driver.join(timeout=30)
        if rec.monitor.state in (MonitorState.RUNNING,):
            rec.monitor.vfpga_exit()
        rec.status = TaskStatus.FAILED
        rec.log("crash")

    def delete(self, cid: str):
        with self._lock:
            self.tasks.pop(cid, None)

    # ------------------------------------------------------------------
    # Funky commands (Table 3)
    # ------------------------------------------------------------------
    def evict(self, cid: str, setup_timeout: float = 300.0) -> dict:
        rec = self.tasks[cid]
        # A task may still be booting (program compilation); eviction waits
        # for the context to exist, like the paper's sync-before-evict.
        deadline = time.time() + setup_timeout
        while rec.status is TaskStatus.CREATED and time.time() < deadline:
            time.sleep(0.005)
        if rec.status is not TaskStatus.RUNNING:
            raise RuntimeError(f"evict: {cid} is {rec.status}")
        t0 = time.perf_counter()
        self._park_driver(rec)
        stats = rec.monitor.evict()
        rec.status = TaskStatus.EVICTED
        stats["total_seconds"] = time.perf_counter() - t0
        rec.log("evict", **{k: v for k, v in stats.items()})
        return stats

    def resume(self, cid: str, source: Optional["FunkyRuntime"] = None) -> dict:
        """Resume an evicted task; if ``source`` is a remote runtime, pull the
        task context from it first (migration, Table 3)."""
        t0 = time.perf_counter()
        if source is not None and source is not self:
            rec = source.migrate_out(cid)
            rec.monitor.allocator = self.allocator
            # programs compile into this node's cache, for this node's device
            rec.monitor.programs = self.programs
            with self._lock:
                self.tasks[cid] = rec
        rec = self.tasks[cid]
        stats = rec.monitor.resume(self.allocator)
        rec.status = TaskStatus.RUNNING
        if rec.driver is None or not rec.driver.is_alive():
            self._spawn_driver(rec, restore=True)
        else:
            rec.run_gate.set()
        stats["total_seconds"] = time.perf_counter() - t0
        rec.log("resume", node=self.node_id, **stats)
        return stats

    def migrate_out(self, cid: str) -> TaskRecord:
        """Hand the full evicted context to a peer runtime."""
        rec = self.tasks[cid]
        if rec.status is TaskStatus.RUNNING:
            self.evict(cid)
        rec.stop_flag = True
        rec.run_gate.set()
        if rec.driver is not None:
            rec.driver.join(timeout=30)
        rec.driver = None
        rec.run_gate = threading.Event()
        rec.stop_flag = False
        with self._lock:
            self.tasks.pop(cid, None)
        rec.log("migrate_out", node=self.node_id)
        return rec

    def _await_setup(self, rec: TaskRecord, timeout: float = 300.0):
        """Snapshots are only meaningful once the guest finished setup()."""
        deadline = time.time() + timeout
        while rec.status is TaskStatus.CREATED and time.time() < deadline:
            time.sleep(0.005)
        if rec.status is TaskStatus.CREATED:
            raise RuntimeError(f"{rec.cid}: setup did not finish in time")

    def checkpoint(self, cid: str, keep_running: bool = True) -> str:
        from repro.ckpt.checkpoint import save_snapshot

        rec = self.tasks[cid]
        self._await_setup(rec)
        if rec.status in (TaskStatus.DONE, TaskStatus.FAILED,
                          TaskStatus.REMOVED):
            raise RuntimeError(
                f"checkpoint: {cid} already {rec.status.value} "
                "(device context released)")
        self._park_driver(rec)
        try:
            snap = rec.monitor.checkpoint(rec.guest_state,
                                          keep_running=keep_running)
            snap.program_ids = tuple(rec.monitor.programs.program_ids())
            path = os.path.join(self.ckpt_root, f"{cid}-step{snap.step}")
            stats = save_snapshot(path, snap, image=rec.image,
                                  prev_path=rec.latest_snapshot,
                                  chaos=self.chaos)
            rec.latest_snapshot = path
            rec.log("checkpoint", path=path, bytes=snap.nbytes(),
                    reused_buffers=stats["reused_buffers"])
            return path
        finally:
            if keep_running:
                rec.run_gate.set()
            else:
                rec.status = TaskStatus.EVICTED

    def restore(self, cid: str, snapshot_path: str) -> TaskRecord:
        """Re-create a task from a disk snapshot and resume it here.

        Verifies digests; a corrupt snapshot falls back along its
        incremental ``prev_path`` chain to the last-good ancestor (each
        skip recorded as a ``restore_fallback`` event).  Raises
        ``CheckpointCorruptError`` only when no ancestor verifies."""
        from repro.ckpt.checkpoint import load_latest_good

        if self.chaos is not None:
            self.chaos.raise_if("ckpt.restore",
                                key=f"{self.node_id}:{cid}")
        snap, image, used_path, skipped = load_latest_good(snapshot_path)
        for bad_path, reason in skipped:
            self.telemetry.record_event(
                "restore_fallback", task=cid, node=self.node_id,
                skipped=bad_path, reason=reason, used=used_path)
        snapshot_path = used_path
        rec = TaskRecord(
            cid=cid, image=image, task=image.instantiate(),
            monitor=Monitor(cid, self.allocator, programs=self.programs,
                            telemetry=self.telemetry, chaos=self.chaos),
            guest_state=snap.guest_state.clone(),
        )
        rec.monitor.load_snapshot(snap)
        with self._lock:
            self.tasks[cid] = rec
        rec.status = TaskStatus.EVICTED
        rec.latest_snapshot = snapshot_path
        rec.log("restore", path=snapshot_path, fallbacks=len(skipped))
        self.resume(cid)
        return rec

    def replicate(self, cid: str, target: "FunkyRuntime",
                  new_cid: Optional[str] = None) -> str:
        """Horizontal scaling: clone a running task onto another node."""
        rec = self.tasks[cid]
        new_cid = new_cid or f"{cid}-rep{int(time.time() * 1000) % 100000}"
        self._await_setup(rec)
        self._park_driver(rec)
        try:
            snap = rec.monitor.checkpoint(rec.guest_state, keep_running=True)
        finally:
            rec.run_gate.set()
        clone = TaskRecord(
            cid=new_cid, image=rec.image, task=rec.image.instantiate(),
            monitor=Monitor(new_cid, target.allocator,
                            programs=target.programs,
                            telemetry=target.telemetry,
                            chaos=target.chaos),
            guest_state=snap.guest_state.clone(),
            priority=rec.priority, preemptible=rec.preemptible,
        )
        clone.monitor.load_snapshot(snap)
        with target._lock:
            target.tasks[new_cid] = clone
        clone.log("replicate_from", source=cid, node=target.node_id)
        target.resume(new_cid)
        return new_cid

    def update(self, cid: str, vfpga_num: int):
        """Vertical scaling: adjust the task's vSlice allowance."""
        rec = self.tasks[cid]
        rec.vfpga_num = vfpga_num
        rec.task.on_update(vfpga_num)
        rec.log("update", vfpga_num=vfpga_num)

    # ------------------------------------------------------------------
    def status(self, cid: str) -> TaskStatus:
        return self.tasks[cid].status

    def wait(self, cid: str, timeout: float = 300.0) -> TaskStatus:
        rec = self.tasks[cid]
        deadline = time.time() + timeout
        while time.time() < deadline:
            if rec.status in (TaskStatus.DONE, TaskStatus.FAILED,
                              TaskStatus.REMOVED):
                return rec.status
            time.sleep(0.005)
        return rec.status
