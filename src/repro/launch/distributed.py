"""Multi-host MSC serving over `jax.distributed` (DESIGN.md §7.9).

The paper's §VI system is distributed-memory — "data produced on the
processes themselves" — and this layer is what turns the repo's
single-host continuous engine into that system: N python processes,
each owning a subset of the devices, run ONE (slice, inner) mesh whose
shard_map executables span processes, while a master/worker control
plane keeps every process dispatching the same executable sequence in
lockstep.

Architecture (master = jax process 0):

  * control channel — a tiny length-prefixed TCP protocol (JSON header
    + raw .npy array payloads) from the master to every worker.  The
    master owns admission and queueing; each scheduler tick it
    broadcasts the admitted tensors and a checkpoint flag, gathers
    ready-acks, and only then does anyone dispatch — so the engines
    (deterministic replicas of `MSCContinuousEngine`) replay the exact
    same submit/step sequence on every process and stay bit-identical
    without ever communicating engine state.
  * lockstep collectives — the engine's chunk/refill executables are
    compiled AOT identically on every process (same mesh, same bucket
    stream) and entered together; host-read outputs are constrained
    replicated (`replicate_outputs=True`) so each process can read
    `finished` and evicted results locally.
  * two-phase multi-host checkpoints — on a checkpoint tick every
    process writes its own addressable shards of the carry state
    (`checkpoint/store.py:write_process_shards`, phase 1) and acks;
    the master then writes the host bookkeeping and the manifest
    (`commit_sharded_checkpoint`, phase 2).  A host dying anywhere in
    between leaves a `.tmp` step that `restorable_steps` never selects.
  * host-loss recovery — worker acks double as heartbeats.  A SIGKILLed
    worker closes its socket, so the master sees EOF at the next
    gather (or a heartbeat timeout if the worker merely hangs) BEFORE
    issuing a collective that would block on the dead peer.  The master
    then aborts the surviving workers, rebuilds the engine from the
    last committed checkpoint onto its OWN local devices
    (`launch/elastic.py:restore_after_host_loss` — `best_msc_shape`
    picks the shrunk factorization), resubmits every in-flight request
    the checkpoint didn't capture, and keeps serving.  Masks and
    `power_iters_run` are bit-identical to the uninterrupted run.
    (Re-admitting *additional* hosts is the restart controller's job —
    relaunch and restore, as in §7.8; the in-process path never tries
    to re-initialize a half-dead `jax.distributed` backend.)
  * exit after loss — jax's atexit hook runs a coordination-service
    shutdown barrier that LOG(FATAL)s when a peer is gone; after a host
    loss the driver flushes its outputs and `os._exit(0)`s past it.

This is a CPU path: processes talk through gloo collectives, and
`--devices-per-process` simulates each process's devices on the host.
Every process it starts or re-execs runs with JAX_PLATFORMS=cpu, so none
of them reaches for a TPU.  It is not a chip path; on a TPU host, one
process drives all local chips (`chip_smoke.py --four-chips`).

`num_processes=1` degenerates to the plain in-process engine — no
sockets, no replication constraints, byte-identical behavior and
`ServeStats` (pinned by tests/test_msc_distributed.py) — so this layer
is on by default in the serving CLI.

Two-process CPU launch (one command; the master spawns the worker and
splits 4 forced host-platform devices 2+2 across the processes):

  PYTHONPATH=src python -m repro.launch.distributed \\
      --num-processes 2 --devices-per-process 2 --spawn-workers \\
      --requests 6 --sizes 8,12 --ckpt-dir /tmp/msc_ckpt --ckpt-every 4

or explicitly, one process per terminal:

  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \\
      python -m repro.launch.distributed --num-processes 2 \\
      --process-id 0 --coordinator localhost:12655 \\
      --control localhost:12656 --requests 6
  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \\
      python -m repro.launch.distributed --num-processes 2 \\
      --process-id 1 --coordinator localhost:12655 \\
      --control localhost:12656
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import json
import os
import socket
import struct
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.faults import DistKillPlan

_LEN = struct.Struct(">Q")


# ---- jax.distributed bring-up ----------------------------------------

@dataclasses.dataclass
class DistributedSpec:
    """One process's coordinates in the multi-host run.

    coordinator is the `jax.distributed` rendezvous address (owned by
    process 0); control_address is this layer's master→worker TCP
    channel.  heartbeat_timeout_s bounds how long the master waits for
    a worker ack before declaring the host lost (EOF on the socket —
    the SIGKILL case — is detected immediately, not after the
    timeout)."""

    num_processes: int = 1
    process_id: int = 0
    coordinator: str = "localhost:12655"
    control_address: str = "localhost:12656"
    heartbeat_timeout_s: float = 60.0
    connect_timeout_s: float = 60.0

    @property
    def is_master(self) -> bool:
        return self.process_id == 0


def init_distributed(spec: DistributedSpec):
    """Initialize the jax.distributed runtime for this process (no-op
    for num_processes=1).  Must run before any device computation;
    cross-process collectives go through gloo on the CPU — this layer
    is a CPU path, not a chip path."""
    if spec.num_processes <= 1:
        return
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=spec.coordinator,
                               num_processes=spec.num_processes,
                               process_id=spec.process_id)


# ---- control-channel framing -----------------------------------------

class ChannelClosed(ConnectionError):
    """Peer's socket hit EOF — on SIGKILL the kernel closes the socket
    immediately, so this is the instant host-loss signal."""


class HostLossError(RuntimeError):
    """One or more worker processes were declared lost."""

    def __init__(self, lost: Sequence[int]):
        super().__init__(f"lost worker process(es) {sorted(lost)}")
        self.lost = sorted(lost)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ChannelClosed(f"peer closed after {len(buf)}/{n} bytes")
        buf += chunk
    return buf


def send_msg(sock: socket.socket, header: Dict,
             arrays: Sequence[np.ndarray] = ()):
    """One framed message: len+JSON header, then len+npy per array."""
    blobs = [json.dumps({**header, "n_arrays": len(arrays)}).encode()]
    for a in arrays:
        buf = io.BytesIO()
        np.save(buf, np.asarray(a))  # NOT ascontiguousarray: it 1-d-ifies 0-d
        blobs.append(buf.getvalue())
    sock.sendall(b"".join(_LEN.pack(len(b)) + b for b in blobs))


def recv_msg(sock: socket.socket) -> Tuple[Dict, List[np.ndarray]]:
    header = json.loads(_recv_exact(sock, _LEN.unpack(
        _recv_exact(sock, _LEN.size))[0]))
    arrays = []
    for _ in range(header.pop("n_arrays", 0)):
        blob = _recv_exact(sock, _LEN.unpack(
            _recv_exact(sock, _LEN.size))[0])
        arrays.append(np.load(io.BytesIO(blob), allow_pickle=False))
    return header, arrays


def _parse_addr(address: str) -> Tuple[str, int]:
    host, _, port = address.rpartition(":")
    return host or "localhost", int(port)


class MasterChannel:
    """Master side: accepts one connection per worker, broadcasts
    commands, gathers acks (= heartbeats) with loss detection."""

    def __init__(self, address: str, num_workers: int):
        host, port = _parse_addr(address)
        self._listener = socket.create_server((host, port))
        self.address = f"{host}:{self._listener.getsockname()[1]}"
        self.num_workers = num_workers
        self._socks: Dict[int, socket.socket] = {}
        self.lost: set = set()

    def accept_workers(self, timeout_s: float):
        self._listener.settimeout(timeout_s)
        deadline = time.monotonic() + timeout_s
        while len(self._socks) < self.num_workers:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {len(self._socks)}/{self.num_workers} workers "
                    f"connected within {timeout_s}s")
            sock, _ = self._listener.accept()
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            hello, _ = recv_msg(sock)
            self._socks[int(hello["process_id"])] = sock

    @property
    def live(self) -> List[int]:
        return sorted(p for p in self._socks if p not in self.lost)

    def broadcast(self, header: Dict, arrays: Sequence[np.ndarray] = ()):
        for pid in self.live:
            try:
                send_msg(self._socks[pid], header, arrays)
            except (ConnectionError, OSError):
                self.lost.add(pid)

    def gather(self, tag: str, timeout_s: float) -> Tuple[Dict[int, Dict],
                                                          List[int]]:
        """One ack per live worker.  Returns (acks by pid, pids newly
        lost this gather — EOF or heartbeat timeout)."""
        acks: Dict[int, Dict] = {}
        newly_lost: List[int] = []
        for pid in self.live:
            sock = self._socks[pid]
            sock.settimeout(timeout_s)
            try:
                header, _ = recv_msg(sock)
                if header.get("tag") != tag:
                    raise ChannelClosed(
                        f"worker {pid}: expected ack {tag!r}, got {header}")
                acks[pid] = header
            except (ChannelClosed, socket.timeout, ConnectionError,
                    OSError):
                self.lost.add(pid)
                newly_lost.append(pid)
        return acks, newly_lost

    def close(self):
        for sock in self._socks.values():
            try:
                sock.close()
            except OSError:
                pass
        self._listener.close()


class WorkerChannel:
    """Worker side: one connection to the master, blocking recv loop."""

    def __init__(self, address: str, process_id: int,
                 connect_timeout_s: float):
        host, port = _parse_addr(address)
        deadline = time.monotonic() + connect_timeout_s
        while True:
            try:
                self._sock = socket.create_connection((host, port),
                                                      timeout=5.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        self._sock.settimeout(None)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        send_msg(self._sock, {"cmd": "hello", "process_id": process_id})

    def recv(self) -> Tuple[Dict, List[np.ndarray]]:
        return recv_msg(self._sock)

    def send(self, header: Dict):
        send_msg(self._sock, header)

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass


# ---- the distributed serving driver ----------------------------------

class MSCDistributedServer:
    """Master/worker lockstep driver around `MSCContinuousEngine`.

    Construct AFTER `init_distributed(spec)`.  The master exposes
    `submit()` / `step()` / `serve()`; workers run `run_worker()` until
    shutdown.  With num_processes=1 there is no channel at all and
    every call forwards straight to the in-process engine (the
    degenerate mode tier-1 regression-pins against the plain engine).

    Checkpointing is coordinated by the master (the engine's own
    auto-checkpoint stays disabled in distributed mode): after the tick
    whose chunk advanced `ckpt_every_chunks` past the last snapshot,
    every process writes its carry shards into the staging dir and the
    master commits (two-phase, see checkpoint/store.py).  After a host
    loss `host_loss_occurred` is True and the process must exit via
    `os._exit` once its outputs are flushed (see module docstring).
    """

    def __init__(self, spec: DistributedSpec, cfg, *,
                 mesh_shape: Optional[Tuple[int, int]] = None,
                 checkpoint_dir: Optional[str] = None,
                 ckpt_every_chunks: int = 8, keep_checkpoints: int = 3,
                 kill_plan: Optional[DistKillPlan] = None,
                 **engine_kwargs):
        import jax

        from repro.launch.elastic import best_msc_shape
        from repro.launch.mesh import make_msc_mesh
        from repro.serving.msc_engine import MSCContinuousEngine

        self.spec = spec
        self.checkpoint_dir = checkpoint_dir
        self.ckpt_every_chunks = int(ckpt_every_chunks)
        self.keep_checkpoints = int(keep_checkpoints)
        self.host_loss_occurred = False
        self.lost_hosts: List[int] = []
        self.recovery_s: Optional[float] = None
        # snapshot taken at recovery time (the torn .tmp may later be
        # legitimately consumed by the restored engine checkpointing at
        # the same step id — save_checkpoint clears stale tmp dirs)
        self.torn_steps_at_loss: List[int] = []
        self.restored_step: Optional[int] = None
        self._kill = kill_plan
        self._engine_kwargs = dict(engine_kwargs)
        distributed = spec.num_processes > 1
        if distributed and jax.process_count() != spec.num_processes:
            raise RuntimeError(
                f"jax.distributed reports {jax.process_count()} processes, "
                f"spec says {spec.num_processes} — call init_distributed "
                f"first")
        devices = jax.devices()
        shape = mesh_shape or best_msc_shape(len(devices))
        self.mesh = make_msc_mesh("flat", devices=devices, shape=shape)
        self.engine = MSCContinuousEngine(
            self.mesh, cfg,
            # single-process: the engine checkpoints itself (format 1),
            # byte-identical to PR 6; distributed: the control plane owns
            # checkpoint timing and the format-2 two-phase write
            checkpoint_dir=None if distributed else checkpoint_dir,
            ckpt_every_chunks=ckpt_every_chunks,
            keep_checkpoints=keep_checkpoints,
            replicate_outputs=distributed,
            **engine_kwargs)
        self._chan = None
        if distributed:
            if spec.is_master:
                chan = MasterChannel(spec.control_address,
                                     spec.num_processes - 1)
                chan.accept_workers(spec.connect_timeout_s)
                self._chan = chan
            else:
                self._chan = WorkerChannel(spec.control_address,
                                           spec.process_id,
                                           spec.connect_timeout_s)
        # master-side request bookkeeping (srid = server request id)
        self._next_srid = 0
        self._admit_buf: List[Tuple[int, np.ndarray]] = []
        self._inflight: Dict[int, np.ndarray] = {}
        self._srid2rid: Dict[int, int] = {}
        self._rid2srid: Dict[int, int] = {}
        self._tick = 0

    # ---- master API ---------------------------------------------------
    @property
    def stats(self):
        return self.engine.stats

    def submit(self, tensor) -> int:
        """Master only: queue one request for the next tick's broadcast.
        Returns the server request id its result comes back under."""
        arr = np.asarray(tensor, self.engine.dtype)
        srid = self._next_srid
        self._next_srid += 1
        self._admit_buf.append((srid, arr))
        self._inflight[srid] = arr
        return srid

    def has_work(self) -> bool:
        return bool(self._admit_buf) or bool(self._inflight)

    def step(self) -> Dict[int, object]:
        """One lockstep scheduler tick; returns {srid: MSCResult} for
        requests that finished.  Handles checkpoint coordination and
        host-loss recovery internally — after a loss the tick returns
        no results (they re-finish post-restore)."""
        admits, self._admit_buf = self._admit_buf, []
        if self.spec.num_processes == 1 or self._chan is None \
                or self.host_loss_occurred:
            return self._local_tick(admits)
        try:
            return self._distributed_tick(admits)
        except HostLossError as e:
            return self._recover(e, admits)

    def serve(self, tensors: Sequence, max_ticks: int = 100_000
              ) -> List[object]:
        """Master only: submit everything, drive ticks to completion."""
        srids = [self.submit(t) for t in tensors]
        got: Dict[int, object] = {}
        ticks = 0
        while any(s not in got for s in srids):
            got.update(self.step())
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"requests still unfinished after "
                                   f"{max_ticks} ticks")
        return [got[s] for s in srids]

    def shutdown(self):
        """Master: release the workers (normal completion)."""
        if self._chan is not None and self.spec.is_master \
                and not self.host_loss_occurred:
            self._chan.broadcast({"cmd": "shutdown"})
            self._chan.gather("bye", self.spec.heartbeat_timeout_s)
        if self._chan is not None:
            self._chan.close()

    # ---- tick internals -----------------------------------------------
    def _apply_admissions(self, arrs: Sequence[np.ndarray]) -> List[int]:
        """Deterministic on every process: same tensors, same order ⇒
        same rids, same queue state, same executable sequence."""
        return [self.engine.submit(a) for a in arrs]

    def _deliver(self, finished: Dict[int, object]) -> Dict[int, object]:
        out = {}
        for rid, res in finished.items():
            srid = self._rid2srid.get(rid)
            if srid is None or srid not in self._inflight:
                continue  # duplicate re-finish after a restore
            out[srid] = res
            del self._inflight[srid]
        return out

    def _map_rids(self, srids_arrs, rids):
        for (srid, _), rid in zip(srids_arrs, rids):
            self._srid2rid[srid] = rid
            self._rid2srid[rid] = srid

    def _local_tick(self, admits) -> Dict[int, object]:
        rids = self._apply_admissions([a for _, a in admits])
        self._map_rids(admits, rids)
        fin = self.engine.step() if self.engine.has_work() else {}
        self._tick += 1
        return self._deliver(fin)

    def _quiesce(self):
        """Drain this process's device queue (async dispatches — e.g. an
        admit-only refill whose outputs nobody reads — may still have
        cross-process collectives in flight).  Every done-ack certifies
        a drained queue, so a host dying between ticks can never tear a
        gloo op mid-stream on a survivor and abort it."""
        import jax

        for tb in self.engine._tables.values():
            jax.block_until_ready((tb.blocks, tb.carries))

    def _distributed_tick(self, admits) -> Dict[int, object]:
        spec, chan, eng = self.spec, self._chan, self.engine
        self._tick += 1
        chan.broadcast({"cmd": "tick", "tick": self._tick},
                       [a for _, a in admits])
        self._gather_or_lose("ready")
        rids = self._apply_admissions([a for _, a in admits])
        self._map_rids(admits, rids)
        try:
            if eng.has_work():
                fin = eng.step()
                self._quiesce()
            else:
                fin = {}
        except Exception:
            # a collective died under us (gloo surfaces peer failures as
            # errors after its own timeout) — let the socket tell us who
            _, newly = chan.gather("done", 1.0)
            raise HostLossError(newly or chan.lost or
                                list(range(1, spec.num_processes)))
        self._gather_or_lose("done")
        if (self.checkpoint_dir is not None and self.ckpt_every_chunks > 0
                and eng._chunks_since_ckpt >= self.ckpt_every_chunks):
            self._coordinated_checkpoint()
        return self._deliver(fin)

    def _gather_or_lose(self, tag: str) -> Dict[int, Dict]:
        acks, newly_lost = self._chan.gather(
            tag, self.spec.heartbeat_timeout_s)
        if newly_lost:
            self.engine.note_ft_event(heartbeats_missed=len(newly_lost))
            raise HostLossError(newly_lost)
        return acks

    # ---- two-phase multi-host checkpoint ------------------------------
    def _coordinated_checkpoint(self):
        from repro.checkpoint.store import (begin_sharded_checkpoint,
                                            commit_sharded_checkpoint,
                                            gc_checkpoints,
                                            write_process_shards)

        eng = self.engine
        step_id = eng._total_chunks
        begin_sharded_checkpoint(self.checkpoint_dir, step_id)
        self._chan.broadcast({"cmd": "ckpt", "step": step_id,
                              "dir": self.checkpoint_dir})
        tmp = os.path.join(self.checkpoint_dir, f"step_{step_id:08d}.tmp")
        device, host, meta = eng._export_split()
        n_files = write_process_shards(tmp, self.spec.process_id, device)
        acks = self._gather_or_lose("shard")
        n_files += sum(int(a.get("files", 0)) for a in acks.values())
        commit_sharded_checkpoint(
            self.checkpoint_dir, step_id,
            num_processes=self.spec.num_processes, full_leaves=host,
            extra=meta)
        gc_checkpoints(self.checkpoint_dir, self.keep_checkpoints)
        eng._chunks_since_ckpt = 0
        eng.note_ft_event(checkpoints_written=1,
                          shard_files_written=n_files)

    # ---- host-loss recovery -------------------------------------------
    def _recover(self, loss: HostLossError, admits) -> Dict[int, object]:
        """Rebuild on the surviving host set (this process's local
        devices), resume from the last committed checkpoint, resubmit
        whatever it didn't capture.  Collectives never touch the dead
        peer again; the caller keeps ticking through _local_tick."""
        import warnings

        import jax

        from repro.launch.elastic import restore_after_host_loss

        t0 = time.monotonic()
        self.host_loss_occurred = True
        self.lost_hosts = sorted(set(self.lost_hosts) | set(loss.lost))
        self._chan.broadcast({"cmd": "abort"})  # best-effort to survivors
        self._chan.close()
        old_stats = self.engine.stats
        restored = None
        from repro.checkpoint.store import latest_restorable
        # slots/dtype are structural — restore() takes them from the
        # checkpoint, so only forward the non-structural engine knobs
        knobs = {k: v for k, v in self._engine_kwargs.items()
                 if k not in ("slots", "dtype")}
        if self.checkpoint_dir is not None and \
                os.path.isdir(self.checkpoint_dir):
            self.torn_steps_at_loss = sorted(
                int(n[len("step_"):-len(".tmp")])
                for n in os.listdir(self.checkpoint_dir)
                if n.startswith("step_") and n.endswith(".tmp")
                and n[len("step_"):-len(".tmp")].isdigit())
        if self.checkpoint_dir is not None and \
                latest_restorable(self.checkpoint_dir,
                                  verify_sha=False) is not None:
            self.restored_step = latest_restorable(self.checkpoint_dir,
                                                   verify_sha=False)
            restored = restore_after_host_loss(
                self.checkpoint_dir,
                checkpoint_dir=self.checkpoint_dir,
                ckpt_every_chunks=self.ckpt_every_chunks,
                keep_checkpoints=self.keep_checkpoints,
                **knobs)
        if restored is None:
            warnings.warn("host loss with no committed checkpoint — "
                          "rebuilding a fresh engine and resubmitting "
                          "everything")
            from repro.launch.elastic import best_msc_shape
            from repro.launch.mesh import make_msc_mesh
            from repro.serving.msc_engine import MSCContinuousEngine

            local = jax.local_devices()
            mesh = make_msc_mesh("flat", devices=local,
                                 shape=best_msc_shape(len(local)))
            restored = MSCContinuousEngine(
                mesh, self.engine.cfg, checkpoint_dir=self.checkpoint_dir,
                ckpt_every_chunks=self.ckpt_every_chunks,
                keep_checkpoints=self.keep_checkpoints,
                **self._engine_kwargs)
        self.engine = restored
        self.mesh = restored.mesh
        # FT counters survive the engine swap (the restored engine's
        # stats predate the loss; carry the master-side counters over)
        restored.note_ft_event(
            heartbeats_missed=old_stats.heartbeats_missed
            - restored.stats.heartbeats_missed,
            host_losses=old_stats.host_losses + len(loss.lost)
            - restored.stats.host_losses,
            reinits=old_stats.reinits + 1 - restored.stats.reinits,
            shard_files_written=old_stats.shard_files_written
            - restored.stats.shard_files_written)
        # reconcile requests: rids live in the restored engine iff the
        # checkpoint captured them in flight; everything else (including
        # this tick's never-broadcast admissions) resubmits under a new
        # rid.  Results delivered before the checkpoint stay delivered
        # (not inflight); re-finishes of already-delivered rids are
        # dropped by _deliver.
        known = set(restored._pending)
        for tb in restored._tables.values():
            known.update(r for r in tb.slot_req if r is not None)
        for srid, arr in list(self._inflight.items()):
            rid = self._srid2rid.get(srid)
            if rid is not None and rid in known:
                continue  # checkpoint carries it mid-solve
            if rid is not None:
                self._rid2srid.pop(rid, None)
            new_rid = restored.submit(arr)
            self._srid2rid[srid] = new_rid
            self._rid2srid[new_rid] = srid
        self.recovery_s = time.monotonic() - t0
        return {}

    # ---- worker loop --------------------------------------------------
    def run_worker(self) -> int:
        """Worker main loop: obey ticks until shutdown/abort.  Returns a
        process exit code; after an abort (master saw a host loss) or a
        master death the caller must exit via os._exit to skip the
        jax.distributed shutdown barrier (which aborts on dead peers)."""
        from repro.checkpoint.store import write_process_shards

        chan, eng, kill = self._chan, self.engine, self._kill
        while True:
            try:
                header, arrays = chan.recv()
            except ChannelClosed:
                return 3  # master died — nothing useful left to do
            cmd = header.get("cmd")
            if cmd == "shutdown":
                chan.send({"tag": "bye"})
                chan.close()
                return 0
            if cmd == "abort":
                chan.close()
                return 4
            if cmd == "tick":
                if kill is not None:
                    kill.hit("tick")
                chan.send({"tag": "ready"})
                self._apply_admissions(arrays)
                if eng.has_work():
                    eng.step()
                    self._quiesce()
                if kill is not None:
                    kill.hit("step")
                chan.send({"tag": "done"})
            elif cmd == "ckpt":
                if kill is not None:
                    kill.hit("shard")
                tmp = os.path.join(header["dir"],
                                   f"step_{int(header['step']):08d}.tmp")
                device, _, _ = eng._export_split()
                n = write_process_shards(tmp, self.spec.process_id, device)
                eng._chunks_since_ckpt = 0
                chan.send({"tag": "shard", "files": n})
            else:
                raise RuntimeError(f"unknown control command {header}")


# ---- CLI --------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_workers(args, coordinator: str, control: str):
    """Master convenience: fork the worker processes locally with the
    same device split (the one-command two-process demo)."""
    import subprocess

    procs = []
    for pid in range(1, args.num_processes):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if args.devices_per_process:
            env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                                f"{args.devices_per_process}")
        if args.worker_kill_at:
            env["MSC_DIST_KILL"] = args.worker_kill_at
        cmd = [sys.executable, "-m", "repro.launch.distributed",
               "--num-processes", str(args.num_processes),
               "--process-id", str(pid),
               "--coordinator", coordinator, "--control", control,
               "--slots", str(args.slots),
               "--ckpt-every", str(args.ckpt_every)]
        if args.mesh_shape:
            cmd += ["--mesh-shape", args.mesh_shape]
        if args.ckpt_dir:
            cmd += ["--ckpt-dir", args.ckpt_dir]
        procs.append(subprocess.Popen(cmd, env=env))
    return procs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Multi-host MSC serving over jax.distributed "
                    "(DESIGN.md §7.9)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed rendezvous host:port "
                         "(default: auto-picked by the master in "
                         "--spawn-workers mode)")
    ap.add_argument("--control", default=None,
                    help="master→worker control channel host:port")
    ap.add_argument("--spawn-workers", action="store_true",
                    help="master spawns the worker processes locally "
                         "(one-command demo / CI)")
    ap.add_argument("--devices-per-process", type=int, default=0,
                    help="with --spawn-workers: set XLA_FLAGS host-"
                         "platform device count for every process "
                         "(master re-execs itself if needed)")
    ap.add_argument("--worker-kill-at", default=None, metavar="POINT:K",
                    help="with --spawn-workers: inject MSC_DIST_KILL "
                         "into the workers (tick:K | step:K | shard:K)")
    ap.add_argument("--mesh-shape", default=None,
                    help="(slice, inner) factorization, e.g. '4,1'")
    ap.add_argument("--sizes", default="8,12")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--slow-every", type=int, default=0)
    ap.add_argument("--submit-per-tick", type=int, default=0,
                    help="stagger submissions N per tick (0 = all "
                         "upfront)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--power-tol", type=float, default=1e-2)
    ap.add_argument("--outdir", default=None,
                    help="write results.npz + stats.json here (tests/"
                         "benches parse these)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the forced device count must be in XLA_FLAGS before jax's backend
    # initializes; re-exec with it when the caller didn't set it
    want = (f"--xla_force_host_platform_device_count="
            f"{args.devices_per_process}")
    if args.devices_per_process and want not in os.environ.get(
            "XLA_FLAGS", ""):
        env = dict(os.environ, XLA_FLAGS=want, JAX_PLATFORMS="cpu")
        os.execve(sys.executable,
                  [sys.executable, "-m", "repro.launch.distributed"]
                  + (argv if argv is not None else sys.argv[1:]), env)

    multi = args.num_processes > 1
    is_master = args.process_id == 0
    coordinator = args.coordinator or f"localhost:{_free_port()}"
    control = args.control or f"localhost:{_free_port()}"
    workers = []
    if multi and is_master and args.spawn_workers:
        workers = _spawn_workers(args, coordinator, control)

    spec = DistributedSpec(num_processes=args.num_processes,
                           process_id=args.process_id,
                           coordinator=coordinator,
                           control_address=control)
    init_distributed(spec)

    import jax

    from repro.core import MSCConfig
    from repro.launch.msc_serve import build_request_stream

    cfg = MSCConfig(epsilon=3e-4, power_tol=args.power_tol)
    shape = (tuple(int(s) for s in args.mesh_shape.split(","))
             if args.mesh_shape else None)
    server = MSCDistributedServer(
        spec, cfg, mesh_shape=shape, checkpoint_dir=args.ckpt_dir,
        ckpt_every_chunks=args.ckpt_every, slots=args.slots,
        kill_plan=DistKillPlan.from_env())

    if not is_master:
        rc = server.run_worker()
        if rc == 0:
            # clean completion: rendezvous in the distributed shutdown
            # barrier (the master calls shutdown() too) so no side ever
            # sees a vanished peer
            jax.distributed.shutdown()
            return 0
        sys.stdout.flush()
        sys.stderr.flush()
        # abort / master death: a barrier would block on (or abort over)
        # the dead peer — see module docstring
        os._exit(rc)

    sizes = [int(s) for s in args.sizes.split(",")]
    specs, tensors = build_request_stream(sizes, args.requests, args.seed,
                                          slow_every=args.slow_every)
    print(f"MSC distributed serve: {args.num_processes} process(es), "
          f"{jax.device_count()} devices, mesh {dict(server.mesh.shape)}, "
          f"{args.requests} requests over sizes {sizes}", flush=True)

    t0 = time.time()
    got: Dict[int, object] = {}
    srids = []
    nxt = 0
    per_tick = args.submit_per_tick or len(tensors)
    while nxt < len(tensors) or any(s not in got for s in srids):
        while nxt < len(tensors) and len(srids) - len(got) < per_tick:
            srids.append(server.submit(tensors[nxt]))
            nxt += 1
        got.update(server.step())
    serve_s = time.time() - t0
    results = [got[s] for s in srids]
    server.shutdown()

    for i in (0, len(results) - 1):
        sw = [int(results[i][j].power_iters_run) for j in range(3)]
        print(f"  req {i}: sweeps={sw}", flush=True)
    s = server.stats
    print(f"served {len(results)} requests in {serve_s:.2f}s "
          f"({len(results) / serve_s:.2f} req/s)", flush=True)
    print(f"  fault tolerance: {s.checkpoints_written} checkpoints, "
          f"{s.restores} restores, {s.heartbeats_missed} heartbeats "
          f"missed, {s.host_losses} host losses, {s.reinits} reinits, "
          f"{s.shard_files_written} shard files", flush=True)

    if args.outdir:
        import dataclasses as dc

        os.makedirs(args.outdir, exist_ok=True)
        payload = {}
        for i, res in enumerate(results):
            for j in range(3):
                payload[f"mask_{i}_{j}"] = np.asarray(res[j].mask)
                payload[f"d_{i}_{j}"] = np.asarray(res[j].d)
            payload[f"iters_{i}"] = np.asarray(
                [int(res[j].power_iters_run) for j in range(3)])
        np.savez(os.path.join(args.outdir, "results.npz"), **payload)
        with open(os.path.join(args.outdir, "stats.json"), "w") as f:
            json.dump({**dc.asdict(s),
                       "serve_s": serve_s,
                       "n_results": len(results),
                       "lost_hosts": server.lost_hosts,
                       "recovery_s": server.recovery_s,
                       "torn_steps_at_loss": server.torn_steps_at_loss,
                       "restored_step": server.restored_step,
                       "mesh": [[a, int(v)] for a, v in
                                server.mesh.shape.items()]}, f)

    if server.host_loss_occurred:
        for p in workers:  # abort was broadcast; don't leave orphans
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)  # skip the shutdown barrier (see module docstring)
    if multi:
        # enter the shutdown barrier NOW (workers are already waiting in
        # it after their "bye" ack) so they can exit before we reap them
        jax.distributed.shutdown()
    for p in workers:
        try:
            p.wait(timeout=30)
        except Exception:
            p.kill()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
