"""Multi-process serving data plane: per-replica worker PROCESSES
behind the fleet front door (docs/serving.md "Worker processes").

:class:`~paddle_tpu.serve.fleet.ReplicaSet` scales serving across
shared-nothing engine replicas, but every replica still shares ONE
Python interpreter: the router threads, N engine workers and the
open-loop clients all contend for the same GIL, which is exactly the
plateau the replicas-ab bench keeps hitting on CPU hosts. The
reference escaped this wall by running a multi-process runtime (the
C++ trainer/pserver pair, later the go master/pserver); `WorkerSet`
is that shape for the serve tier:

* each replica runs as its own **OS worker process** (``spawn`` start
  method, so JAX state never forks dirty): the bundle loads once per
  worker, the device is pinned per worker, and the worker hosts an
  ordinary :class:`InferenceEngine` / :class:`ContinuousScheduler`
  with its own metrics labels and per-worker steplog file
  (``<run>-w<i>.steps.jsonl`` — the per-replica telemetry convention,
  one process further apart);
* the router process holds only sockets, queues and routing state —
  dispatch is the same least-queued + consistent-hash-session front
  `ReplicaSet` runs, duck-typed like a single engine so the Router and
  the HTTP front door host a `WorkerSet` unchanged;
* the hot path crosses the process boundary over a **length-prefixed
  request/response ring in shared memory** (:class:`ShmRing`):
  fixed-capacity slots sized from the manifest's bucket specs,
  seqlock-style per-slot state headers, busy-poll-then-``Event`` wait
  per direction. Rows are written as raw array bytes next to a small
  JSON header — ONE memcpy into the slot, zero pickling;
* control traffic (readiness, stats, metric snapshots, session
  export/import, stop/drain, heartbeat) rides a small pipe-based RPC
  (:class:`_Rpc`) with the same no-pickle frame codec.

Failure model: a worker killed ``-9`` is detected by heartbeat +
``Process.is_alive``, excluded from dispatch, its in-flight requests
re-routed to surviving workers, and its sessions re-homed: every
completed session chunk leaves a **committed carry backup** at the
router (the worker snapshots the carry through its scheduler's
export/import path after the chunk retires), so a conversation resumes
bitwise-identically from its last acknowledged chunk on the new home —
zero committed sessions lost. ``respawn=True`` additionally restarts a
replacement worker in the dead one's slot.

Shutdown never leaks: ``stop()`` drains the rings, stops each worker
over RPC (engine drain + steplog flush), joins children against a
deadline, escalates to terminate/kill, closes + unlinks every shared
memory segment, and a module ``atexit`` sweep covers the crash path.
"""

import atexit
import collections
import itertools
import json
import os
import struct
import threading
import time
import weakref
from concurrent.futures import Future
from multiprocessing import shared_memory

import numpy as np

from paddle_tpu.observe import health as observe_health
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.serve.engine import Overloaded
from paddle_tpu.serve.sessions import (ConsistentHashRing, SessionGone,
                                       SessionState)

# the fleet's session->worker assignment memory is a ROUTING HINT (the
# carries live in each worker's scheduler/store); same bound as
# serve/fleet.py so a million one-shot sessions cannot grow the router
_SESSION_HOME_CAP = 1 << 20
# committed-carry backups kept at the router for dead-worker re-homing
_SESSION_BACKUP_CAP = 4096

# -- frame codec -------------------------------------------------------------
#
# One wire format for both transports (ring slots and the control
# pipe): [u32 header_len][header JSON][raw array bytes...]. The header
# carries an ``arrays`` list of {dtype, shape} specs in write order, so
# the reader reconstructs each ndarray with ``np.frombuffer`` over the
# received buffer — no pickle on either side, and array payloads cross
# the boundary as exactly one memcpy into/out of shared memory.

_U32 = struct.Struct("<I")


def encode_frames(header, arrays=()):
    """``(frames, total_bytes)`` for one message: a list of bytes-like
    chunks (header blob + one raw view per array) the transport writes
    back to back."""
    specs = []
    frames = [None, None]  # length prefix + header, filled below
    total = 0
    for arr in arrays:
        arr = np.ascontiguousarray(arr)
        specs.append({"dtype": str(arr.dtype), "shape": list(arr.shape)})
        view = memoryview(arr).cast("B")
        frames.append(view)
        total += view.nbytes
    blob = json.dumps(dict(header, arrays=specs),
                      separators=(",", ":")).encode("utf-8")
    frames[0] = _U32.pack(len(blob))
    frames[1] = blob
    return frames, total + len(blob) + _U32.size


def decode_buffer(buf):
    """``(header, [ndarray])`` from one received message buffer. Arrays
    are zero-copy ``np.frombuffer`` views over ``buf`` (read-only)."""
    hlen = _U32.unpack_from(buf, 0)[0]
    header = json.loads(bytes(buf[_U32.size:_U32.size + hlen])
                        .decode("utf-8"))
    off = _U32.size + hlen
    arrays = []
    for spec in header.pop("arrays", []):
        dtype = np.dtype(spec["dtype"])
        shape = tuple(int(d) for d in spec["shape"])
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        arr = np.frombuffer(buf, dtype=dtype, count=count,
                            offset=off).reshape(shape)
        arrays.append(arr)
        off += count * dtype.itemsize
    return header, arrays


def encode_state(state):
    """Session-carry frames for cross-process migration: the carry's
    leaf arrays ride as raw bytes (restore is bitwise-equal), the
    layer/leaf layout + pos/priority in the header."""
    layout, arrays = [], []
    for layer in sorted(state.carry):
        leaves = state.carry[layer]
        layout.append([layer, len(leaves)])
        arrays.extend(leaves)
    header = {"pos": int(state.pos), "priority": state.priority,
              "layout": layout}
    return header, arrays


def decode_state(sid, header, arrays):
    carry, i = {}, 0
    for layer, n in header["layout"]:
        carry[layer] = [np.asarray(a) for a in arrays[i:i + n]]
        i += n
    return SessionState(sid, carry, header["pos"],
                        header.get("priority") or "normal")


def ring_slot_bytes(bundle, margin=1 << 16):
    """Ring slot size from the manifest's bucket specs: the largest
    request (max bucket's flat feeds) or response (max bucket x
    ``seq_len`` output rows) plus header margin, page-rounded. Sizing
    from the manifest keeps the ring a fixed-capacity allocation the
    operator can reason about, not a grow-on-demand heap."""
    rows = int(bundle.max_batch())
    steps = int(bundle.seq_len or 1)
    req = 0
    for spec in bundle.inputs:
        shape = bundle.feed_shape(spec, rows)
        req += (int(np.prod(shape, dtype=np.int64))
                * np.dtype(spec["dtype"]).itemsize)
        if spec["kind"] in ("seq_index", "seq_dense"):
            req += rows * 4  # the :lens side array
    resp = 0
    for out in bundle.outputs:
        suffix = int(np.prod(out.get("shape_suffix") or [1],
                             dtype=np.int64))
        resp += (max(rows, 1) * max(steps, 1) * suffix
                 * np.dtype(out["dtype"]).itemsize)
    nbytes = max(req, resp, 1 << 12) + margin
    return (nbytes + 4095) & ~4095


# -- the shared-memory ring --------------------------------------------------

_FREE, _WRITING, _READY, _READING = 0, 1, 2, 3
_SLOT_HDR = struct.Struct("<II")  # state, payload length
_SPIN = 200  # busy-poll iterations before falling back to the Event


class ShmRing:
    """Fixed-capacity SPSC message ring over one ``SharedMemory``
    segment: ``slots`` slots of ``slot_bytes`` payload each, a
    seqlock-style state word per slot (FREE -> WRITING -> READY ->
    READING -> FREE), and one ``Event`` per direction for the
    busy-poll-then-wait handoff. Single producer and single consumer
    per ring (the router serializes its writers on a lock); the state
    word is written LAST on publish, so a reader never observes a
    half-written slot."""

    def __init__(self, name, slots, slot_bytes, data_evt, space_evt,
                 create=False):
        self.slots = int(slots)
        self.slot_bytes = int(slot_bytes)
        self._stride = _SLOT_HDR.size + self.slot_bytes
        self._data_evt = data_evt
        self._space_evt = space_evt
        size = self._stride * self.slots
        self.shm = shared_memory.SharedMemory(
            name=name, create=create, size=size if create else 0)
        # Note bpo-38119: attaching registers the segment with the
        # resource tracker a second time. Spawned workers INHERIT the
        # router's tracker, whose cache is a set — the duplicate
        # register is a no-op and the router's unlink balances it, so
        # no explicit unregister is needed (an extra one would make the
        # tracker log spurious KeyErrors at exit).
        self.name = self.shm.name
        self._buf = self.shm.buf
        if create:
            for i in range(self.slots):
                _SLOT_HDR.pack_into(self._buf, i * self._stride, _FREE, 0)
        self._w = 0
        self._r = 0

    def _state(self, off):
        return _SLOT_HDR.unpack_from(self._buf, off)[0]

    def put_frames(self, frames, nbytes, timeout=30.0):
        """Publish one message (pre-encoded frames) into the next slot;
        blocks (busy-poll then Event) while the ring is full. Raises
        ``TimeoutError`` when the consumer never frees a slot — a dead
        peer, surfaced loudly instead of wedging the producer."""
        if nbytes > self.slot_bytes:
            raise ValueError(
                "message of %d bytes exceeds the ring slot size %d "
                "(sized from the bundle manifest's bucket specs)"
                % (nbytes, self.slot_bytes))
        off = (self._w % self.slots) * self._stride
        deadline = time.monotonic() + timeout
        spins = 0
        while self._state(off) != _FREE:
            spins += 1
            if spins < _SPIN:
                continue
            self._space_evt.clear()
            if self._state(off) == _FREE:
                break
            if not self._space_evt.wait(0.05) \
                    and time.monotonic() > deadline:
                raise TimeoutError(
                    "ring full for %.0fs: consumer not draining"
                    % timeout)
        _SLOT_HDR.pack_into(self._buf, off, _WRITING, 0)
        pos = off + _SLOT_HDR.size
        for frame in frames:
            view = memoryview(frame).cast("B")
            self._buf[pos:pos + view.nbytes] = view
            pos += view.nbytes
        # publish: the state word flips to READY only after the payload
        # landed (the seqlock convention readers rely on)
        _SLOT_HDR.pack_into(self._buf, off, _READY, nbytes)
        self._w += 1
        self._data_evt.set()

    def get(self, timeout=0.05):
        """One message payload (bytes) or ``None`` on timeout."""
        off = (self._r % self.slots) * self._stride
        spins = 0
        while self._state(off) != _READY:
            spins += 1
            if spins < _SPIN:
                continue
            self._data_evt.clear()
            if self._state(off) == _READY:
                break
            if not self._data_evt.wait(timeout):
                return None
        _SLOT_HDR.pack_into(self._buf, off,
                            _READING,
                            _SLOT_HDR.unpack_from(self._buf, off)[1])
        length = _SLOT_HDR.unpack_from(self._buf, off)[1]
        pos = off + _SLOT_HDR.size
        out = bytes(self._buf[pos:pos + length])  # the one memcpy out
        _SLOT_HDR.pack_into(self._buf, off, _FREE, 0)
        self._r += 1
        self._space_evt.set()
        return out

    def close(self):
        self._buf = None
        try:
            self.shm.close()
        except Exception:  # noqa: BLE001 — idempotent teardown
            pass

    def unlink(self):
        try:
            self.shm.unlink()
        except Exception:  # noqa: BLE001 — already gone is fine
            pass


# -- pipe RPC ----------------------------------------------------------------

class _Rpc:
    """Tiny request/response RPC over a duplex ``Pipe`` using the
    shared frame codec (``send_bytes``/``recv_bytes`` — no pickle).
    One outstanding call at a time per side; the caller serializes on
    its own lock (control traffic is rare by design)."""

    def __init__(self, conn):
        self.conn = conn

    def send(self, header, arrays=()):
        frames, _total = encode_frames(header, arrays)
        self.conn.send_bytes(b"".join(bytes(f) if not isinstance(f, bytes)
                                      else f for f in frames))

    def recv(self, timeout=None):
        if timeout is not None and not self.conn.poll(timeout):
            raise TimeoutError("rpc peer silent for %.1fs" % timeout)
        return decode_buffer(self.conn.recv_bytes())

    def close(self):
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001 — idempotent teardown
            pass


def _error_header(exc):
    """Serialize a serving exception class by value for the response
    ring; the router re-raises the matching type."""
    if isinstance(exc, Overloaded):
        return {"error": "Overloaded", "message": str(exc),
                "model": exc.model, "priority": exc.priority,
                "reason": exc.reason, "queued": exc.queued}
    if isinstance(exc, SessionGone):
        return {"error": "SessionGone", "message": str(exc),
                "session_id": exc.session_id, "reason": exc.reason}
    if isinstance(exc, (ValueError, KeyError, TypeError)):
        return {"error": type(exc).__name__, "message": str(exc)}
    return {"error": "RuntimeError",
            "message": "%s: %s" % (type(exc).__name__, exc)}


def _raise_error(header):
    kind, msg = header.get("error"), header.get("message", "")
    if kind == "Overloaded":
        raise Overloaded(msg, model=header.get("model"),
                         priority=header.get("priority"),
                         reason=header.get("reason"),
                         queued=header.get("queued"))
    if kind == "SessionGone":
        raise SessionGone(msg, session_id=header.get("session_id"),
                          reason=header.get("reason"))
    if kind == "KeyError":
        raise KeyError(msg)
    if kind == "ValueError":
        raise ValueError(msg)
    if kind == "TypeError":
        raise TypeError(msg)
    raise RuntimeError(msg)


# -- the worker process ------------------------------------------------------

def _op_traces():
    """``traces`` control verb: this worker's exemplar reservoir +
    trace counters, slowest-first — the router merges the dumps fleet-
    wide (observe.health.collect_traces) with ``worker=`` provenance.
    Pure host dict copies; nothing on this path may touch a device
    value (it runs on the control thread but is lint-hot by contract)."""
    from paddle_tpu.observe import tracing

    return {"ok": True, "traces": tracing.debug_traces()}


def _op_history():
    """``history`` control verb: this worker's windowed health-history
    snapshot (torn-read free by HealthHistory's lock), merged at the
    router by epoch (observe.health.collect_history)."""
    from paddle_tpu.observe import health

    return {"ok": True, "history": health.get_history().snapshot()}


def _worker_main(index, bundle_dir, continuous, engine_kwargs, model,
                 run_name, conn, ring_spec, warmup):
    """Entry point of one worker process (``spawn``): load the bundle,
    pin the device, build the engine, then serve the request ring and
    the control pipe until told to stop. Runs with inherited env, so
    test/CLI platform pins (JAX_PLATFORMS, XLA_FLAGS) apply here too."""
    import signal

    # Ctrl-C lands on the whole foreground process group: the ROUTER
    # owns the graceful path (stop RPC -> drain -> join); a worker that
    # died to the same SIGINT would drop its queued requests mid-drain
    signal.signal(signal.SIGINT, signal.SIG_IGN)

    import contextlib

    import jax

    from paddle_tpu.utils import compile_cache

    compile_cache.enable()  # before the child's first compile
    from paddle_tpu.observe import steplog as slog_mod
    from paddle_tpu.observe import tracing as tracing_mod
    from paddle_tpu.serve.bundle import load_bundle
    from paddle_tpu.serve.engine import InferenceEngine
    from paddle_tpu.serve.scheduler import ContinuousScheduler

    rpc = _Rpc(conn)
    with contextlib.ExitStack() as stack:
        # process-lifetime compile watcher: the router's zero-compile
        # gate reads this over RPC ("compiles"), so the bench can pin
        # that the serving phase minted nothing INSIDE the worker
        watcher = stack.enter_context(slog_mod.watch_compiles())
        req_ring = ShmRing(ring_spec["req"], ring_spec["slots"],
                           ring_spec["slot_bytes"],
                           ring_spec["req_data"], ring_spec["req_space"])
        resp_ring = ShmRing(ring_spec["resp"], ring_spec["slots"],
                            ring_spec["slot_bytes"],
                            ring_spec["resp_data"],
                            ring_spec["resp_space"])
        stack.callback(req_ring.close)
        stack.callback(resp_ring.close)

        bundle = load_bundle(bundle_dir)
        devices = jax.devices()
        view = bundle.view(devices[index % len(devices)])
        slog = slog_mod.from_env(run_name="%s-w%d" % (run_name, index),
                                 meta={"phase": "serve",
                                       "worker": index},
                                 flush_every=32)
        engine_cls = ContinuousScheduler if continuous else InferenceEngine
        engine = engine_cls(view, warmup="async" if warmup else False,
                            metrics_registry=observe_metrics.get_registry(),
                            model=model, replica=index, steplog=slog,
                            **dict(engine_kwargs or {}))

        # worker-local knob registry (docs/control.md): the router-side
        # WorkerSet discovers these over the "knobs" verb and fans
        # controller moves out over "set_knob" — the apply hooks run
        # HERE, in the process that owns the engine's locks
        knob_reg = None
        if hasattr(engine, "register_knobs"):
            from paddle_tpu.control.knobs import KnobRegistry

            knob_reg = KnobRegistry()
            engine.register_knobs(knob_reg)

        stop_evt = threading.Event()
        out_q = collections.deque()
        out_cv = threading.Condition()
        # serializes session submits against the backup/export path so
        # a backup's export->import window can never interleave with a
        # fresh chunk for the same session (which would zero-carry it)
        session_mu = threading.Lock()

        def _complete(req_id, fut):
            try:
                result = fut.result()
                header = {"id": req_id,
                          "outputs": list(result.keys())}
                arrays = list(result.values())
            except Exception as exc:  # noqa: BLE001 — shipped by value
                header = dict(_error_header(exc), id=req_id)
                arrays = []
            with out_cv:
                out_q.append((header, arrays))
                out_cv.notify()

        def _rx_loop():
            while not stop_evt.is_set():
                buf = req_ring.get(timeout=0.05)
                if buf is None:
                    continue
                header, arrays = decode_buffer(buf)
                req_id = header["id"]
                inputs = dict(zip(header["inputs"], arrays))
                trace = None
                parent = header.get("traceparent")
                if parent:
                    trace = tracing_mod.TraceContext.from_traceparent(
                        parent)
                try:
                    sid = header.get("session")
                    if sid is not None:
                        with session_mu:
                            fut = engine.submit(
                                inputs, session_id=sid,
                                priority=header.get("priority"),
                                end_session=bool(
                                    header.get("end_session")),
                                trace=trace)
                    else:
                        fut = engine.submit(inputs, trace=trace)
                except Exception as exc:  # noqa: BLE001 — by value
                    with out_cv:
                        out_q.append((dict(_error_header(exc),
                                           id=req_id), []))
                        out_cv.notify()
                    continue
                fut.add_done_callback(
                    lambda f, rid=req_id: _complete(rid, f))

        def _tx_loop():
            while True:
                with out_cv:
                    while not out_q:
                        if stop_evt.is_set():
                            return
                        out_cv.wait(0.05)
                    header, arrays = out_q.popleft()
                frames, nbytes = encode_frames(header, arrays)
                try:
                    resp_ring.put_frames(frames, nbytes)
                except Exception:  # noqa: BLE001 — router died; drop
                    return

        rx = threading.Thread(target=_rx_loop,
                              name="serve-worker-rx-%d" % index,
                              daemon=True)
        tx = threading.Thread(target=_tx_loop,
                              name="serve-worker-tx-%d" % index,
                              daemon=True)
        rx.start()
        tx.start()

        def _session_op(op, header, arrays):
            sid = str(header["session"])
            if op == "has_session":
                return {"ok": True, "has": bool(engine.has_session(sid))}, ()
            if op == "close_session":
                engine.close_session(sid)
                return {"ok": True}, ()
            if op == "export_session":
                state = engine.export_session(sid)
                h, arrs = encode_state(state)
                return dict(h, ok=True), arrs
            if op == "import_session":
                state = decode_state(sid, header, arrays)
                engine.import_session(sid, state)
                return {"ok": True}, ()
            if op == "backup_session":
                # committed-carry snapshot: export then immediately
                # re-import (both host-store ops after the forced
                # spill), atomically vs data-plane submits for the id
                with session_mu:
                    state = engine.export_session(sid)
                    engine.import_session(sid, state)
                h, arrs = encode_state(state)
                return dict(h, ok=True), arrs
            raise ValueError("unknown session op %r" % op)

        # control loop (the worker's main thread): request/response
        # only, one message at a time — heartbeats, stats, session
        # migration and the stop handshake all arrive here
        while True:
            try:
                header, arrays = rpc.recv(timeout=1.0)
            except TimeoutError:
                continue
            except (EOFError, OSError):
                break  # router gone: fall through to the drain path
            op = header.get("op")
            try:
                if op == "ping":
                    rpc.send({"ok": True, "ready": engine.ready(),
                              "live": engine.live(),
                              "queue_depth": engine.queue_depth(),
                              "compiles": watcher.compiles,
                              "pid": os.getpid()})
                elif op == "stats":
                    rpc.send({"ok": True, "stats": engine.stats()})
                elif op == "metrics":
                    rpc.send({"ok": True,
                              "families": engine.metrics.dump_series()})
                elif op == "traces":
                    rpc.send(_op_traces())
                elif op == "history":
                    rpc.send(_op_history())
                elif op == "compiles":
                    rpc.send({"ok": True,
                              "compiles": watcher.compiles})
                elif op == "knobs":
                    rpc.send({"ok": True,
                              "knobs": (knob_reg.snapshot()
                                        if knob_reg is not None else {})})
                elif op == "set_knob":
                    if knob_reg is None:
                        raise KeyError(str(header.get("knob")))
                    old, new = knob_reg.set(str(header["knob"]),
                                            header["value"])
                    rpc.send({"ok": True, "old": old, "new": new})
                elif op == "stop":
                    break
                elif op in ("has_session", "close_session",
                            "export_session", "import_session",
                            "backup_session"):
                    h, arrs = _session_op(op, header, arrays)
                    rpc.send(h, arrs)
                else:
                    rpc.send({"error": "ValueError",
                              "message": "unknown rpc op %r" % op})
            except Exception as exc:  # noqa: BLE001 — shipped by value
                rpc.send(_error_header(exc))

        # drain: stop the engine (flushes its queue + per-worker
        # steplog), let the tx thread push the last responses out
        stop_evt.set()
        try:
            engine.stop(timeout=30.0)
        except Exception:  # noqa: BLE001 — still ack the stop
            pass
        rx.join(timeout=5.0)
        with out_cv:
            pending = list(out_q)
            out_q.clear()
        for header, arrays in pending:
            frames, nbytes = encode_frames(header, arrays)
            try:
                resp_ring.put_frames(frames, nbytes, timeout=1.0)
            except Exception:  # noqa: BLE001 — router stopped reading
                break
        tx.join(timeout=5.0)
        if slog is not None:
            slog.close()
        try:
            rpc.send({"ok": True, "stopped": True})
        except Exception:  # noqa: BLE001 — pipe may be gone
            pass
        rpc.close()


# -- router-side worker handle ----------------------------------------------

class _WorkerHandle:
    """One worker process as seen from the router: the process, its
    two rings, the control RPC, and the pending-request table whose
    size IS the worker's queue-depth signal (no RPC on the dispatch
    path)."""

    def __init__(self, owner, index):
        self._owner = owner
        self.index = index
        self._tx_lock = threading.Lock()      # request-ring writers
        self._rpc_lock = threading.Lock()     # control-pipe callers
        self._pending_lock = threading.Lock()  # pending futures table
        self._state_lock = threading.Lock()   # liveness/readiness
        self._pending = {}
        self._dead = False
        self._ready = False
        self._ping_failures = 0
        self.process = None
        self._rpc = None
        self._req_ring = None
        self._resp_ring = None
        self._rx_thread = None
        self._spawn()

    # -- lifecycle -----------------------------------------------------------
    def _spawn(self):
        owner = self._owner
        ctx = owner._ctx
        tag = "%s-%d-w%d-%d" % (owner._shm_prefix, os.getpid(),
                                self.index, owner._spawn_seq())
        ring_spec = {
            "slots": owner.ring_slots,
            "slot_bytes": owner.slot_bytes,
            "req": "%s-req" % tag, "resp": "%s-resp" % tag,
            "req_data": ctx.Event(), "req_space": ctx.Event(),
            "resp_data": ctx.Event(), "resp_space": ctx.Event(),
        }
        req_ring = ShmRing(ring_spec["req"], owner.ring_slots,
                           owner.slot_bytes, ring_spec["req_data"],
                           ring_spec["req_space"], create=True)
        resp_ring = ShmRing(ring_spec["resp"], owner.ring_slots,
                            owner.slot_bytes, ring_spec["resp_data"],
                            ring_spec["resp_space"], create=True)
        parent_conn, child_conn = ctx.Pipe()
        process = ctx.Process(
            target=_worker_main,
            args=(self.index, owner.bundle.directory, owner.continuous,
                  owner._engine_kwargs, owner.model, owner._run_name,
                  child_conn, ring_spec, True),
            name="paddle-tpu-serve-worker-%d" % self.index,
            daemon=True)
        process.start()
        child_conn.close()
        rx = threading.Thread(
            target=self._rx_loop, args=(resp_ring,),
            name="serve-worker-rx-%d" % self.index, daemon=True)
        with self._state_lock:
            self.process = process
            self._dead = False
            self._ready = False
            self._ping_failures = 0
        with self._rpc_lock:
            self._rpc = _Rpc(parent_conn)
        with self._tx_lock:
            self._req_ring = req_ring
        self._resp_ring_ref = resp_ring
        self._rx_thread = rx
        rx.start()

    def respawn(self):
        """Start a replacement process in this slot (fresh rings; the
        old segments were torn down when the slot was marked dead)."""
        self._teardown_transport()
        self._spawn()

    def dead(self):
        with self._state_lock:
            return self._dead

    def mark_dead(self):
        """Exclude this worker from dispatch; reap what the OS left."""
        with self._state_lock:
            if self._dead:
                return False
            self._dead = True
            self._ready = False
            process = self.process
        if process is not None:
            process.join(timeout=0.5)
        return True

    def is_alive(self):
        with self._state_lock:
            if self._dead:
                return False
            process = self.process
        return process is not None and process.is_alive()

    def ready(self):
        with self._state_lock:
            if self._dead:
                return False
            warm = self._ready
            process = self.process
        if process is not None and not process.is_alive():
            # a killed worker must drop out of /readyz immediately,
            # not a heartbeat interval later when it is marked dead
            return False
        if warm:
            return True
        return self._refresh_ready()

    def _refresh_ready(self):
        try:
            reply = self.rpc({"op": "ping"}, timeout=2.0)[0]
        except Exception:  # noqa: BLE001 — not ready if unreachable
            return False
        ready = bool(reply.get("ready"))
        with self._state_lock:
            self._ready = ready
        return ready

    # -- data plane ----------------------------------------------------------
    def queue_depth(self):
        with self._pending_lock:
            return len(self._pending)

    def submit_encoded(self, req_id, header, arrays, future, entry):
        """Register the pending future, then publish the request into
        the ring (registration first: the response can race back before
        the writer returns)."""
        frames, nbytes = encode_frames(header, arrays)
        with self._pending_lock:
            self._pending[req_id] = entry
        try:
            with self._tx_lock:
                self._req_ring.put_frames(frames, nbytes)
        except Exception:
            with self._pending_lock:
                self._pending.pop(req_id, None)
            raise
        return future

    def _rx_loop(self, ring):
        """Per-worker response pump: decode, look up the pending
        future, resolve. The ring is handed in as an arg so a respawned
        worker's pump never reads another incarnation's segment."""
        while True:
            with self._state_lock:
                if self._dead:
                    break
            buf = ring.get(timeout=0.05)
            if buf is None:
                continue
            self._dispatch_response(buf)

    def join_rx(self, timeout=2.0):
        rx = self._rx_thread
        if rx is not None and rx is not threading.current_thread():
            rx.join(timeout=timeout)

    def drain_responses(self, ring=None):
        """Pull every already-published response out of the ring — the
        last read before a dead worker's pending table is failed over,
        so an acknowledged result is never replayed. The ring is SPSC:
        callers must stop the rx pump (mark dead + ``join_rx``) first,
        so this is the sole consumer."""
        ring = ring or self._resp_ring_ref
        if ring is None:
            return
        while True:
            buf = ring.get(timeout=0.0)
            if buf is None:
                return
            self._dispatch_response(buf)

    def _dispatch_response(self, buf):
        header, arrays = decode_buffer(buf)
        req_id = header.get("id")
        with self._pending_lock:
            entry = self._pending.pop(req_id, None)
        if entry is None:
            return  # duplicate/late response after failover
        future = entry["future"]
        if future.done():
            return
        if "error" in header:
            try:
                _raise_error(header)
            except Exception as exc:  # noqa: BLE001 — future carries it
                future.set_exception(exc)
            return
        result = dict(zip(header["outputs"], arrays))
        self._owner._note_completed(self, entry)
        future.set_result(result)

    def take_pending(self):
        with self._pending_lock:
            pending = dict(self._pending)
            self._pending.clear()
        return pending

    # -- control plane -------------------------------------------------------
    def rpc(self, header, arrays=(), timeout=10.0):
        with self._rpc_lock:
            self._rpc.send(header, arrays)
            reply, out = self._rpc.recv(timeout=timeout)
        if "error" in reply:
            _raise_error(reply)
        return reply, out

    def try_rpc(self, header, timeout=2.0):
        """Best-effort control call (heartbeat/stats): ``None`` when
        the worker is busy stopping, dead, or silent."""
        # timed acquire instead of `with`: the heartbeat must not wedge
        # behind a slow stop RPC — _rpc_lock IS held for the accesses
        # below (released in the finally), the AST checker just cannot
        # see a timed acquire
        got = self._rpc_lock.acquire(timeout=timeout)
        if not got:
            return None
        try:
            self._rpc.send(header)  # paddle-lint: disable=PTA005
            reply, _ = self._rpc.recv(timeout=timeout)  # paddle-lint: disable=PTA005
            return reply
        except Exception:  # noqa: BLE001 — heartbeat decides liveness
            return None
        finally:
            self._rpc_lock.release()

    def ping(self):
        reply = self.try_rpc({"op": "ping"})
        with self._state_lock:
            if reply is None:
                self._ping_failures += 1
                failures = self._ping_failures
            else:
                self._ping_failures = 0
                self._ready = bool(reply.get("ready"))
                failures = 0
        return failures

    # -- teardown ------------------------------------------------------------
    def _teardown_transport(self):
        with self._rpc_lock:
            if self._rpc is not None:
                self._rpc.close()
                self._rpc = None
        rx = self._rx_thread
        if rx is not None and rx is not threading.current_thread():
            rx.join(timeout=2.0)
        with self._tx_lock:
            if self._req_ring is not None:
                self._req_ring.close()
                self._req_ring.unlink()
                self._req_ring = None
        ring = self._resp_ring_ref
        if ring is not None:
            ring.close()
            ring.unlink()
            self._resp_ring_ref = None

    def shutdown(self, timeout=30.0):
        """Graceful stop: stop RPC (worker drains + flushes), join
        against the deadline, escalate terminate -> kill, then tear
        down rings/pipe. Never leaks a child or a segment."""
        deadline = time.monotonic() + timeout
        with self._state_lock:
            process = self.process
            was_dead = self._dead
        if process is not None and process.is_alive() and not was_dead:
            reply = self.try_rpc({"op": "stop"},
                                 timeout=max(timeout - 1.0, 1.0))
            if reply is not None:
                # the worker acked the drain: its final responses are in
                # the ring — let the rx pump (the ring's sole consumer)
                # resolve them before it is stopped below
                drain_deadline = time.monotonic() + min(
                    2.0, max(deadline - time.monotonic(), 0.1))
                while time.monotonic() < drain_deadline:
                    with self._pending_lock:
                        if not self._pending:
                            break
                    time.sleep(0.01)
        with self._state_lock:
            self._dead = True
            self._ready = False
        self.join_rx()
        self.drain_responses()  # leftovers, now as the sole consumer
        if process is not None:
            process.join(timeout=max(deadline - time.monotonic(), 0.1))
            if process.is_alive():
                process.terminate()
                process.join(timeout=2.0)
            if process.is_alive():
                process.kill()
                process.join(timeout=2.0)
        self._teardown_transport()
        pending = self.take_pending()
        for entry in pending.values():
            future = entry["future"]
            if not future.done():
                future.set_exception(Overloaded(
                    "worker %d stopped before the request completed"
                    % self.index, model=self._owner.model,
                    reason="no_replica"))


# -- merged metrics view -----------------------------------------------------

class _MergedMetrics:
    """``/metrics`` view of a WorkerSet: the router registry's families
    merged with each worker's snapshot (pulled over control RPC) under
    an injected ``{worker=}`` label — one scrape shows the whole
    multi-process fleet."""

    def __init__(self, owner, registry):
        self._owner = owner
        self.registry = registry

    def _worker_dumps(self):
        dumps = []
        for handle in self._owner.workers():
            if handle.dead():
                continue
            reply = handle.try_rpc({"op": "metrics"}, timeout=2.0)
            if reply and reply.get("families") is not None:
                dumps.append((reply["families"],
                              {"worker": str(handle.index)}))
        return dumps

    def to_prometheus(self):
        return observe_metrics.merged_exposition(self.registry,
                                                 self._worker_dumps())

    def snapshot(self):
        snap = self.registry.snapshot()
        snap["workers"] = {
            labels["worker"]: families
            for families, labels in self._worker_dumps()}
        return snap

    # instrument passthrough: router-side series (shed counter etc.)
    # keep registering against the underlying registry
    def counter(self, *args, **kwargs):
        return self.registry.counter(*args, **kwargs)

    def gauge(self, *args, **kwargs):
        return self.registry.gauge(*args, **kwargs)

    def histogram(self, *args, **kwargs):
        return self.registry.histogram(*args, **kwargs)


# -- the worker fleet --------------------------------------------------------

_live_sets_lock = threading.Lock()
_live_sets = weakref.WeakSet()
_sweep_registered = False


def _atexit_sweep():
    with _live_sets_lock:
        sets = list(_live_sets)
    for ws in sets:
        try:
            ws.stop(timeout=10.0)
        except Exception:  # noqa: BLE001 — best-effort crash sweep
            pass


class WorkerSet:
    """N serving replicas as N OS worker processes behind the fleet
    front door — duck-type compatible with
    :class:`~paddle_tpu.serve.fleet.ReplicaSet` (submit/infer/ready/
    live/stats/queue_depth/stop + session affinity), so the Router, the
    HTTP server and ``cli serve`` host it unchanged
    (``cli serve <bundle> --workers N|auto``).

    ``bundle`` is the router-side load (manifest + specs for ring
    sizing and routing); each worker process loads its OWN copy from
    ``bundle.directory`` and pins device ``i % len(devices)``.
    ``engine_kwargs`` passes through to every worker's engine;
    ``respawn=True`` restarts a dead worker in place;
    ``session_backup`` (default on) snapshots each session's carry to
    the router after every committed chunk, the state a dead worker's
    sessions re-home from."""

    def __init__(self, bundle, workers=None, continuous=False,
                 engine_kwargs=None, metrics_registry=None, model=None,
                 run_name="serve", respawn=False, session_backup=True,
                 ring_slots=64, slot_bytes=None,
                 heartbeat_interval=0.25):
        import multiprocessing

        n = 1 if workers is None else int(workers)
        if n < 1:
            raise ValueError("workers must be >= 1, got %r" % workers)
        from paddle_tpu.core.place import enforce_children_can_open_devices

        enforce_children_can_open_devices(n, "WorkerSet(workers=%d)" % n)
        self.bundle = bundle
        self.model = model
        self.continuous = bool(continuous)
        self.respawn = bool(respawn)
        self.session_backup = bool(session_backup)
        self.ring_slots = int(ring_slots)
        self.slot_bytes = int(slot_bytes or ring_slot_bytes(bundle))
        self.heartbeat_interval = float(heartbeat_interval)
        self._engine_kwargs = dict(engine_kwargs or {})
        self._run_name = run_name
        self._shm_prefix = "ptpu"
        # spawn: a forked child would inherit live JAX/engine state
        # mid-flight; a spawned one imports clean
        self._ctx = multiprocessing.get_context("spawn")
        registry = metrics_registry or observe_metrics.get_registry()
        self.metrics = _MergedMetrics(self, registry)
        # same static capacity gate as ReplicaSet: N processes hold N
        # parameter copies
        from paddle_tpu.serve.fleet import fleet_hbm_check

        self.hbm_estimate_bytes, self.hbm_note = fleet_hbm_check(bundle,
                                                                 n)
        shed_labels = {"reason": "no_replica"}
        if model:
            shed_labels["model"] = str(model)
        self._m_shed = registry.counter(
            "paddle_tpu_serve_shed_total",
            help="requests rejected by admission control",
            labels=shed_labels)
        self._lock = threading.Lock()
        self._rr = 0
        self._req_ids = itertools.count(1)
        self._stats = collections.Counter()
        self._stopped = False
        self._ring = (ConsistentHashRing(list(range(n)))
                      if continuous else None)
        self._session_home = collections.OrderedDict()
        self._session_backups = collections.OrderedDict()
        self._migrate_lock = threading.Lock()
        self._handles = tuple(_WorkerHandle(self, i) for i in range(n))
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name="serve-worker-heartbeat",
            daemon=True)
        self._hb_thread.start()
        global _sweep_registered
        with _live_sets_lock:
            _live_sets.add(self)
            if not _sweep_registered:
                atexit.register(_atexit_sweep)
                _sweep_registered = True

    _seq = itertools.count(1)

    def _spawn_seq(self):
        return next(WorkerSet._seq)

    def workers(self):
        """The worker handles, in index order (immutable tuple)."""
        return self._handles

    # ReplicaSet duck-type: tests/benches that iterate ``replicas()``
    # see the same member shape (index + a way to run a probe)
    def replicas(self):
        return self._handles

    @property
    def supports_sessions(self):
        return self.continuous

    # -- dispatch ------------------------------------------------------------
    def _eligible(self):
        return [h for h in self._handles
                if not h.dead() and h.is_alive() and h.ready()]

    def submit(self, inputs, session_id=None, priority=None,
               end_session=False, trace=None):
        """Dispatch one request to the least-queued eligible worker
        (round-robin tie-break) through its shared-memory ring; returns
        a Future. Session requests route by consistent-hash affinity
        with cross-process carry migration, exactly the ReplicaSet
        contract. Raises :class:`Overloaded` (reason ``no_replica``)
        when every worker is cold or dead."""
        eligible = self._eligible()
        if not eligible:
            self._m_shed.inc()
            observe_health.get_history().record_shed("no_replica")
            raise Overloaded(
                "no warm live worker (fleet of %d still warming or "
                "failed) — retry after /readyz goes green"
                % len(self._handles),
                model=self.model, reason="no_replica")
        if session_id is not None:
            if self._ring is None:
                raise ValueError(
                    "this worker fleet does not hold sessions (whole-"
                    "request engines); construct with continuous=True "
                    "over a decode-capable bundle")
            handle = self._route_session(str(session_id), eligible)
            return self._submit_to(handle, inputs,
                                   session_id=str(session_id),
                                   priority=priority,
                                   end_session=end_session, trace=trace)
        n = len(eligible)
        with self._lock:
            offset = self._rr
            self._rr = (self._rr + 1) % n
        order = [eligible[(offset + j) % n] for j in range(n)]
        depths = [h.queue_depth() for h in order]
        best = min(range(n), key=lambda j: (depths[j], j))
        return self._submit_to(order[best], inputs, trace=trace)

    def submit_to(self, index, inputs, timeout=None, trace=None):
        """Pin one request to worker ``index`` (the equivalence gate's
        through-every-worker probe)."""
        return self._submit_to(self._handles[index], inputs, trace=trace)

    def _submit_to(self, handle, inputs, session_id=None, priority=None,
                   end_session=False, trace=None):
        names, arrays = [], []
        for name, value in inputs.items():
            names.append(str(name))
            arrays.append(np.asarray(value))
        header = {"id": next(self._req_ids), "inputs": names}
        if session_id is not None:
            header["session"] = session_id
            if end_session:
                header["end_session"] = True
        if priority is not None:
            header["priority"] = str(priority)
        if trace is not None and getattr(trace, "trace_id", None):
            # trace context crosses the process boundary BY VALUE as
            # its W3C traceparent string — the worker re-mints the
            # span lane under the same trace id, so Perfetto links the
            # router and worker halves into one flow
            header["traceparent"] = trace.traceparent()
        future = Future()
        entry = {"future": future, "header": header, "arrays": arrays,
                 "session": session_id, "retries": 0}
        with self._lock:
            self._stats["dispatched"] += 1
        handle.submit_encoded(header["id"], header, arrays, future,
                              entry)
        return future

    def infer(self, inputs, timeout=60.0, session_id=None, priority=None,
              end_session=False, trace=None):
        return self.submit(inputs, session_id=session_id,
                           priority=priority, end_session=end_session,
                           trace=trace).result(timeout=timeout)

    def queue_depth(self):
        return sum(h.queue_depth() for h in self._handles)

    # -- session routing -----------------------------------------------------
    def _route_session(self, sid, eligible):
        eligible_idx = {h.index for h in eligible}
        target = None
        for idx in self._ring.order(sid):
            if idx in eligible_idx:
                target = self._handles[idx]
                break
        if target is None:  # unreachable: eligible is non-empty
            target = eligible[0]
        with self._lock:
            home = self._session_home.get(sid)
        if home is None:
            # the bounded hint table forgot: probe live workers before
            # treating the session as new (a wrong guess zero-carries
            # the conversation)
            for handle in eligible:
                if handle.index == target.index:
                    continue
                try:
                    reply, _ = handle.rpc({"op": "has_session",
                                           "session": sid}, timeout=5.0)
                except Exception:  # noqa: BLE001 — probe only
                    continue
                if reply.get("has"):
                    home = handle.index
                    break
        if home is not None and home != target.index:
            with self._migrate_lock:
                with self._lock:
                    current = self._session_home.get(sid)
                if current is not None:
                    home = current
                if home != target.index:
                    self._migrate(sid, home, target)
            return target
        if home is None and self._restore_backup(sid, target):
            pass  # re-homed from the committed-carry backup
        self._set_home(sid, target.index)
        return target

    def _migrate(self, sid, home, target):
        """Pull a session's carry across processes: export over the old
        home's control RPC, import at the target — serialized through
        the frame codec, so the restored carry is bitwise-equal."""
        old = self._handles[home]
        state = None
        if not old.dead() and old.is_alive():
            try:
                reply, arrays = old.rpc({"op": "export_session",
                                         "session": sid}, timeout=30.0)
                state = decode_state(sid, reply, arrays)
            except SessionGone:
                raise  # evicted at home is gone fleet-wide (410)
            except KeyError:
                state = None
            except Exception:  # noqa: BLE001 — home died mid-export
                state = None
        if state is None:
            # dead home: the committed-carry backup is the source
            if self._restore_backup(sid, target):
                self._set_home(sid, target.index)
                return
        if state is not None:
            header, arrays = encode_state(state)
            target.rpc(dict(header, op="import_session", session=sid),
                       arrays, timeout=30.0)
            with self._lock:
                self._stats["migrations"] += 1
        self._set_home(sid, target.index)

    def _restore_backup(self, sid, target):
        with self._lock:
            backup = self._session_backups.get(sid)
        if backup is None:
            return False
        header, arrays = backup
        try:
            target.rpc(dict(header, op="import_session", session=sid),
                       arrays, timeout=30.0)
        except Exception:  # noqa: BLE001 — target died; next route retries
            return False
        with self._lock:
            self._stats["backup_restores"] += 1
        return True

    def _set_home(self, sid, index):
        with self._lock:
            self._session_home[sid] = index
            self._session_home.move_to_end(sid)
            while len(self._session_home) > _SESSION_HOME_CAP:
                self._session_home.popitem(last=False)

    def _note_completed(self, handle, entry):
        """Response-path bookkeeping (runs on the handle's rx thread):
        count the completion and, for session chunks, refresh the
        committed-carry backup over control RPC — the state a dead
        worker's sessions will re-home from."""
        with self._lock:
            self._stats["completed"] += 1
        sid = entry.get("session")
        if sid is None or not self.session_backup:
            return
        if entry["header"].get("end_session"):
            with self._lock:
                self._session_backups.pop(sid, None)
            return
        try:
            reply, arrays = handle.rpc(
                {"op": "backup_session", "session": sid}, timeout=10.0)
        except Exception:  # noqa: BLE001 — a missed backup only means
            return  # the session replays from its previous snapshot
        reply.pop("ok", None)
        with self._lock:
            self._session_backups[sid] = (reply, arrays)
            self._session_backups.move_to_end(sid)
            while len(self._session_backups) > _SESSION_BACKUP_CAP:
                self._session_backups.popitem(last=False)

    def close_session(self, session_id):
        if self._ring is None:
            return
        sid = str(session_id)
        with self._lock:
            home = self._session_home.pop(sid, None)
            self._session_backups.pop(sid, None)
        handles = ([self._handles[home]] if home is not None
                   else self._handles)
        for handle in handles:
            if handle.dead() or not handle.is_alive():
                continue
            try:
                handle.rpc({"op": "close_session", "session": sid},
                           timeout=10.0)
            except Exception:  # noqa: BLE001 — close is best-effort
                pass

    # -- failure handling ----------------------------------------------------
    def _heartbeat_loop(self):
        while not self._hb_stop.is_set():
            for handle in self._handles:
                if self._hb_stop.is_set():
                    return
                if handle.dead():
                    continue
                if not handle.is_alive():
                    self._on_worker_death(handle)
                    continue
                failures = handle.ping()
                if failures >= 3:
                    self._on_worker_death(handle)
            self._hb_stop.wait(self.heartbeat_interval)

    def _on_worker_death(self, handle):
        """A worker died (kill -9, crash): exclude it from dispatch,
        read out every response it already committed, re-route its
        in-flight requests, drop its routing hints (sessions re-home
        from their committed backups on their next chunk), optionally
        respawn."""
        if not handle.mark_dead():
            return  # another path already handled it
        handle.join_rx()
        handle.drain_responses()
        with self._lock:
            self._stats["worker_deaths"] += 1
            stopped = self._stopped
            for sid, home in list(self._session_home.items()):
                if home == handle.index:
                    del self._session_home[sid]
        pending = handle.take_pending()
        for entry in pending.values():
            self._reroute(entry)
        handle._teardown_transport()
        if self.respawn and not stopped:
            handle.respawn()
            with self._lock:
                self._stats["respawns"] += 1

    def _reroute(self, entry):
        """Re-dispatch one in-flight request of a dead worker. Session
        chunks replay against the session's last committed carry (the
        backup restored by ``_route_session``), so a deterministic
        decode reproduces the lost chunk bitwise; sessionless requests
        simply run elsewhere."""
        future = entry["future"]
        if future.done():
            return
        entry["retries"] += 1
        if entry["retries"] > 3:
            future.set_exception(Overloaded(
                "request re-routed %d times without completing"
                % (entry["retries"] - 1), model=self.model,
                reason="no_replica"))
            return
        header = entry["header"]
        try:
            eligible = self._eligible()
            if not eligible:
                raise Overloaded("no surviving worker",
                                 model=self.model, reason="no_replica")
            sid = entry.get("session")
            if sid is not None:
                target = self._route_session(sid, eligible)
            else:
                target = min(eligible,
                             key=lambda h: (h.queue_depth(), h.index))
            arrays = entry["arrays"]
            new_header = dict(header, id=next(self._req_ids))
            target.submit_encoded(new_header["id"], new_header, arrays,
                                  future, dict(entry,
                                               header=new_header))
            with self._lock:
                self._stats["reroutes"] += 1
        except Exception as exc:  # noqa: BLE001 — future carries it
            if not future.done():
                future.set_exception(exc)

    # -- health / stats ------------------------------------------------------
    def ready(self):
        """True once EVERY worker finished warmup — the same
        all-replicas-warm ``/readyz`` contract as ReplicaSet (a dead
        worker keeps the aggregate not-ready until respawned or
        stopped)."""
        return all(h.ready() for h in self._handles)

    def ready_detail(self):
        return {str(h.index): h.ready() for h in self._handles}

    def live(self):
        return any(not h.dead() and h.is_alive()
                   for h in self._handles)

    def live_detail(self):
        return {str(h.index): (not h.dead() and h.is_alive())
                for h in self._handles}

    def wait_ready(self, timeout=300.0):
        """Block until every worker is warm (readiness polls over the
        control RPC); raises ``TimeoutError`` otherwise."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.ready():
                return self
            time.sleep(0.05)
        raise TimeoutError(
            "worker fleet not ready within %.0fs: %r"
            % (timeout, self.ready_detail()))

    def stats(self):
        """Fleet view: router counters plus each live worker's engine
        stats (pulled over control RPC), aggregated under the same keys
        ReplicaSet exposes."""
        per = {}
        for handle in self._handles:
            if handle.dead() or not handle.is_alive():
                per[str(handle.index)] = {"dead": True}
                continue
            reply = handle.try_rpc({"op": "stats"}, timeout=5.0)
            per[str(handle.index)] = (reply or {}).get("stats", {})
        with self._lock:
            router = dict(self._stats)
            session_routes = len(self._session_home)
            backups = len(self._session_backups)
        out = {
            "workers": len(self._handles),
            "dispatch": "least_queued_rr",
            "transport": "shm_ring",
            "per_worker": per,
            "router": router,
        }
        for key in ("requests", "rows", "batches", "shed",
                    "queue_depth", "in_flight", "spills", "restores",
                    "evictions", "resident_sessions",
                    "suspended_sessions"):
            out[key] = sum(s.get(key, 0) for s in per.values()
                           if isinstance(s, dict))
        out["queue_depth"] += self.queue_depth()
        if self._ring is not None:
            out["session_routes"] = session_routes
            out["session_backups"] = backups
        if self.model:
            out["model"] = self.model
        if self.hbm_estimate_bytes is not None:
            out["hbm_estimate_bytes"] = self.hbm_estimate_bytes
        out["ready"] = self.ready()
        return out

    def register_knobs(self, registry):
        """Adopt the workers' knobs as fleet-wide proxies (docs/
        control.md): discover the knob table from the first worker
        that answers the ``knobs`` verb, then register one proxy per
        name whose apply broadcasts ``set_knob`` over every live
        worker's control pipe. Best-effort by design — a worker that
        is mid-restart misses a move and simply keeps its old value
        until the next one; the controller's rollback guard judges
        outcomes, not deliveries."""
        from paddle_tpu.control.knobs import Knob

        table = {}
        for handle in self._handles:
            if handle.dead() or not handle.is_alive():
                continue
            reply = handle.try_rpc({"op": "knobs"}, timeout=5.0)
            if reply is not None and reply.get("knobs"):
                table = reply["knobs"]
                break
        for name in sorted(table):
            desc = table[name]

            def _broadcast(v, name=name):
                for handle in self._handles:
                    if handle.dead() or not handle.is_alive():
                        continue
                    handle.try_rpc({"op": "set_knob", "knob": name,
                                    "value": v}, timeout=5.0)

            registry.register(Knob(
                name, value=desc["value"], min=desc["min"],
                max=desc["max"], step=desc["step"],
                cost_hint=desc.get("cost_hint", "cheap"),
                integer=bool(desc.get("integer")), apply=_broadcast))

    def compile_counts(self):
        """Per-worker compile counters (the in-worker ``watch_compiles``
        reading) — what the workers-ab zero-post-warmup-compile gate
        diffs across the measured phase."""
        out = {}
        for handle in self._handles:
            if handle.dead() or not handle.is_alive():
                continue
            reply = handle.try_rpc({"op": "compiles"}, timeout=5.0)
            if reply is not None:
                out[handle.index] = int(reply.get("compiles", 0))
        return out

    # -- teardown ------------------------------------------------------------
    def stop(self, timeout=30.0):
        """Stop every worker (drain + flush + join, escalating to
        terminate/kill at the deadline), then unlink every shared
        memory segment. Idempotent."""
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
        self._hb_stop.set()
        if self._hb_thread is not threading.current_thread():
            self._hb_thread.join(timeout=5.0)
        for handle in self._handles:
            handle.shutdown(timeout=timeout)
        with _live_sets_lock:
            _live_sets.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def __repr__(self):
        return "WorkerSet(%r, workers=%d, continuous=%s)" % (
            self.bundle.name, len(self._handles), self.continuous)
