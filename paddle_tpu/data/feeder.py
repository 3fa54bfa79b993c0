"""DeviceFeeder: pipelined batch assembly + device prefetch.

The reference ran data ingestion as its own concurrent subsystem:
PyDataProvider2's pool thread double-buffered host batches while the
trainer consumed them (gserver/dataproviders/PyDataProvider2.cpp:334).
Our trainer historically called ``convert_feed`` synchronously on the
step thread — on a fast device the step blocks on host-side numpy
work. The DeviceFeeder moves the whole feed span off the critical
thread:

* a background **producer** thread runs the reader, converts each
  minibatch (``topology.convert_feed``, honoring a BucketBatch's exact
  pad target) and PLACES it on the device — sharding-aware: with a
  ``parallelism`` (parallel.mesh.DataParallel) the batch lands on the
  global-mesh 'data' axis exactly where ``shard_train_step`` would have
  put it, so the transfer happens ahead of the step instead of inside
  it (the layout distributed/worker.py trains with). A recycled column
  goes there in ONE crossing, each device's rows straight from the host
  buffer; every other leaf arrives on one device and ``shard_batch``
  moves it, a second crossing that runs as a program on that device;
* a bounded queue keeps up to ``depth`` batches device-resident ahead
  of the step;
* the consumer (`batches()`) yields :class:`FeedBatch` records carrying
  the feed plus its timing/waste accounting; the time the step thread
  spends blocked on the queue is the **feed stall** — the number that
  tells you a run is input-bound.

Shutdown/cancellation is clean in both directions: a consumer that
stops early (break / exception / GC of the generator) cancels the
producer, which exits promptly even while blocked on a full queue; a
producer error (reader or conversion raising) is re-raised on the
consumer thread with the original traceback.

Observability: every yielded batch updates the process-wide metrics
registry (``paddle_tpu_data_*`` series: feed-stall histogram, queue
depth, per-bucket fill/waste gauges — the training twins of the serve
engine's per-bucket series) and the stall is recorded as a ``feed``
span so traces show the step thread's wait. The producer's cycle is
four spans on its own thread, each with a histogram of its own:
``feed_read`` (the reader's ``next()``), ``feed_convert`` (rows to a
feed on the device: its ``feed_place`` children are the hand-overs of
host bytes to a device, its ``feed_buffer_wait`` children the waits for
a recycled host buffer's last transfer, its self time is host assembly)
and ``feed_put`` (blocked on a full queue: the feed is ahead, the device
is the bound). The trainer additionally writes a ``feed`` steplog record
per step (docs/observability.md).
"""

import queue
import threading

import numpy as np

from paddle_tpu.data.bucketing import BucketBatch, batch_waste
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils.logger import logger
# ONE cancellation handshake for every producer/consumer thread pair in
# the codebase (poll interval, shutdown ordering): the reader
# decorators' helpers are reused here, not re-implemented
from paddle_tpu.reader.decorator import _cancellable_put, _drain


class _End:
    pass


class _Error:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class FeedBatch:
    """One pipelined batch: the device-resident ``feed`` dict plus its
    accounting — ``seq`` (the producer's count of batches, the pass's
    batch number), ``examples`` (rows), the producer thread's
    durations ``read_ms`` (the reader's ``next()``), ``convert_ms`` =
    ``host_ms`` (host assembly) + ``place_ms`` (handing the bytes to the
    device) + ``buffer_wait_ms`` (waiting for the last transfer out of a
    recycled host buffer; 0 for a batch that recycled none), and
    ``backpressure_ms`` (the producer blocked on a full
    queue, written once the batch is in the queue), ``stall_ms`` (time
    the consumer blocked waiting for it), and for sequence feeds
    ``bucket`` (padded length), ``fill_tokens``/``pad_tokens`` (summed over
    the sequence slots) and the step's ``tokens``/``positions`` (of its
    widest sequence slot: valid tokens, rows x padded length)."""

    __slots__ = ("feed", "seq", "examples", "read_ms", "convert_ms",
                 "place_ms", "buffer_wait_ms", "backpressure_ms", "stall_ms",
                 "bucket", "fill_tokens", "pad_tokens", "tokens", "positions")

    def __init__(self, feed, examples, convert_ms, bucket=None,
                 fill_tokens=None, pad_tokens=None, seq=None, read_ms=0.0,
                 place_ms=0.0, buffer_wait_ms=0.0, tokens=None,
                 positions=None):
        self.feed = feed
        self.seq = seq
        self.examples = examples
        self.read_ms = read_ms
        self.convert_ms = convert_ms
        self.place_ms = place_ms
        self.buffer_wait_ms = buffer_wait_ms
        self.backpressure_ms = 0.0  # set by the producer after the put
        self.stall_ms = None  # set by the consumer
        self.bucket = bucket
        self.fill_tokens = fill_tokens
        self.pad_tokens = pad_tokens
        self.tokens = tokens  # of the step: its widest sequence slot's
        self.positions = positions

    @property
    def host_ms(self):
        return self.convert_ms - self.place_ms - self.buffer_wait_ms


class ChunkBatch:
    """The train loop's dispatch unit: n >= 1 consecutive batches handed
    to the device in one call (``trainer.SGD._train_passes``). Under
    ``steps_per_call=K`` up to K pipelined batches grouped for one fused
    dispatch (docs/data.md); otherwise one batch.

    ``feed`` is what the trainer hands to the step: for
    ``steps > 1`` a length-K TUPLE of the member device trees
    (``stacked=True``) — the fused program stacks them into the
    ``lax.scan`` xs layout inside the jit, so chunk assembly costs the
    host zero extra dispatches; a single-batch chunk keeps its member's
    feed untouched (``stacked=False`` — the trainer runs it through the
    ordinary jitted step, so a K=1 run is the byte-identical program).
    ``batches`` keeps the member :class:`FeedBatch` records for per-step
    accounting; ``examples``/``stall_ms``/``convert_ms`` are the chunk
    totals."""

    __slots__ = ("feed", "steps", "batches", "examples", "stall_ms",
                 "convert_ms", "stacked")

    def __init__(self, feed, batches, stacked):
        self.feed = feed
        self.batches = list(batches)
        self.steps = len(self.batches)
        self.stacked = stacked
        self.examples = sum(fb.examples for fb in self.batches)
        self.stall_ms = sum(fb.stall_ms or 0.0 for fb in self.batches)
        self.convert_ms = sum(fb.convert_ms or 0.0 for fb in self.batches)

    def materialize(self):
        """Called by the step thread once the unit's steps are announced
        (``BeginIteration``), before it reads ``feed``. Nothing to do for
        a feeder's unit: its producer made the members before it was
        yielded. An :func:`inline_units` unit converts here."""


def _feed_shape_key(feed):
    """Hashable (treedef, leaf shapes/dtypes) key: batches may only share
    a fused chunk when their feeds compile to the same program."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(feed)
    return treedef, tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


def _seq_stats(feed):
    """(padded_len, fill_tokens, pad_tokens, step_tokens, step_positions)
    of a converted feed, all None when it has no sequence slot. Fill and
    pad are summed over the sequence slots; a step's tokens and positions
    are those of its widest slot (a language model's tokens and targets
    are one sequence, counted once)."""
    from paddle_tpu.core.sequence import SequenceBatch

    bucket = fill = slots = tokens = positions = 0
    for value in feed.values():
        if isinstance(value, SequenceBatch):
            lens = np.asarray(value.lengths)
            held = int(lens.shape[0]) * int(value.max_len)
            bucket = max(bucket, int(value.max_len))
            fill += int(lens.sum())
            slots += held
            if held > positions:
                tokens, positions = int(lens.sum()), held
    if slots == 0:
        return None, None, None, None, None
    return bucket, fill, slots - fill, tokens, positions


def step_tokens(feed):
    """(valid tokens, batch x padded length) of a step over ``feed``, or
    (None, None) for a feed without sequence slots."""
    return _seq_stats(feed)[3:]


class _InlineUnit(ChunkBatch):
    """A one-batch unit of :func:`inline_units`: its rows stay on the
    host, and it has no ``feed`` or ``batches``, until ``materialize()``."""

    __slots__ = ("_convert_args",)

    def __init__(self, topo, rows, feeding, seq):
        self._convert_args = (topo, rows, feeding, seq)
        self.steps = 1
        self.stacked = False

    def materialize(self):
        from paddle_tpu import topology

        topo, rows, feeding, seq = self._convert_args
        self._convert_args = None  # the rows die with this call
        with observe_spans.span("feed", args={"batch": seq}) as scope:
            # through the module attribute, as the producer's call: a
            # stand-in for convert_feed takes here too
            feed = topology.convert_feed(
                topo, rows, feeding, max_len=getattr(rows, "bucket", None))
        tokens, positions = step_tokens(feed)
        fb = FeedBatch(feed, len(rows), scope.dur * 1e3, seq=seq,
                       tokens=tokens, positions=positions)
        fb.stall_ms = fb.convert_ms  # all of it on the step thread
        ChunkBatch.__init__(self, feed, [fb], stacked=False)


def inline_units(reader, topo, feeding=None, skip=0):
    """The batch source without a feeder (``SGD.train``'s default,
    ``feed_pipeline=False``): one-batch :class:`ChunkBatch` units, each
    converted and placed on the caller's own thread when it calls
    ``materialize()``, so that the trainer announces batch b
    (``BeginIteration``) before it pays for b's conversion — the
    historical synchronous order. The conversion is the ``feed`` span and
    the unit's ``stall_ms``. ``skip=N`` drops the reader's first N
    batches unconverted (the resume cursor). Every batch gets fresh host
    arrays: nobody here knows when a batch's bytes have left the host
    (:class:`HostBuffers`)."""
    batch_iter = iter(reader())
    for _ in range(skip):  # deterministic resume skip
        if next(batch_iter, None) is None:
            break
    for seq, data_batch in enumerate(batch_iter, skip):
        yield _InlineUnit(topo, data_batch, feeding, seq)


class _Slot:
    """One recycled host array and the last device leaf made from it."""

    __slots__ = ("host", "leaf")

    def __init__(self, host):
        self.host = host
        self.leaf = None


class _Ring:
    """A column's slots, oldest first, all of one shape and dtype."""

    __slots__ = ("shape", "dtype", "slots", "next")

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype
        self.slots = []
        self.next = 0  # of the slot the next batch is assembled into


class HostBuffers:
    """The host memory a :class:`DeviceFeeder` assembles its batches in.

    For each fixed-shape dense or index column a ring of ``slots`` host
    arrays, taken in turn, so that once the ring has filled no batch
    allocates, first-touches or unmaps host memory. A fresh 154 MB array
    costs ten times its copy in page faults (PERF.md).

    The owner calls :meth:`assemble` for a column of each batch (through
    ``topology.convert_feed`` inside ``topology.recycling_into``) and
    :meth:`sent` once the batch is in its final place. The rule that makes
    recycling safe: a slot remembers the device leaf made from it, and
    before the slot is written again the producer waits for that leaf
    (``block_until_ready``) and lets go of it. A transfer reads the host
    array for tens of milliseconds after the placing call has returned,
    and nothing is written under it. The placement itself is a copy the
    slot does not share (``topology._place``). With as many slots as
    batches can be alive at once the wait is zero whenever the feed is
    ahead.

    On a mesh (``sharding_of``, the owner's rule for where a batch leaf of
    a shape goes: ``DataParallel.batch_leaf_sharding``) the one crossing
    is :meth:`place_sharded`: every device receives its own rows out of
    the slot's host array, the leaf is the sharded array, and ready means
    all of those transfers are done. No array of the whole batch exists on
    any one device.

    What it does not take, by what it sees: rows that do not form one
    rectangular array (``assemble`` returns None and the caller's own
    ``np.asarray`` raises what it raised), and a batch of fewer rows than
    the column's ring, the short last batch of a pass, which gets a fresh
    array while the full-size slots stay. Any other new shape starts the
    column's ring afresh. Not safe for two threads at once: one producer
    uses it at a time.
    """

    def __init__(self, slots, reused, allocated, sharding_of=None,
                 placed_sharded=None):
        self.slots = int(slots)  # of each column's ring
        self._reused = reused        # counters of the owner's registry
        self._allocated = allocated
        self._sharding_of = sharding_of  # shape -> sharding on the mesh
        self._placed_sharded = placed_sharded  # counter, with the above
        self._rings = {}    # column name -> _Ring
        self._filled = {}   # column name -> the slot this batch was put in
        self._waited = None  # seconds this batch waited; None: reused none

    def assemble(self, name, col, dtype):
        """``col``'s rows in one of the column's host arrays, equal to
        ``np.asarray(col, dtype=dtype)``, or None for rows this does not
        take."""
        try:
            rows = [row if isinstance(row, np.ndarray)
                    else np.asarray(row, dtype=dtype) for row in col]
        except (TypeError, ValueError, OverflowError):
            return None  # the caller's np.asarray says what is wrong
        if not rows or any(row.shape != rows[0].shape for row in rows):
            return None
        shape = (len(rows),) + rows[0].shape
        ring = self._rings.get(name)
        if ring is None or (shape[1:], dtype) != (ring.shape[1:], ring.dtype) \
                or shape[0] > ring.shape[0]:
            ring = self._rings[name] = _Ring(shape, dtype)
        elif shape[0] < ring.shape[0]:
            return None  # a short last batch
        if len(ring.slots) < self.slots:
            slot = _Slot(np.empty(shape, dtype))
            ring.slots.append(slot)
            self._allocated.inc()
        else:
            slot = ring.slots[ring.next % len(ring.slots)]
            self._reused.inc()
            self._wait_for(slot)
        ring.next += 1
        # rows of another dtype are cast as np.asarray casts them
        np.stack(rows, out=slot.host, casting="unsafe")
        self._filled[name] = slot
        return slot.host

    def place_sharded(self, host):
        """``host`` (an array of :meth:`assemble`) on the owner's mesh,
        each device's rows straight from the host array, or None where
        the owner has no mesh or its rule does not split these rows: the
        caller then places on one device as ever, and the owner's
        ``shard_batch`` copies from there."""
        if self._sharding_of is None:
            return None
        want = self._sharding_of(host.shape)
        if want.is_fully_replicated:
            return None
        import jax

        self._placed_sharded.inc()
        return jax.device_put(host, want)

    def _wait_for(self, slot):
        with observe_spans.span("feed_buffer_wait") as wait:
            if slot.leaf is not None:
                slot.leaf.block_until_ready()
                slot.leaf = None
        self._waited = (self._waited or 0.0) + wait.dur

    def sent(self, feed):
        """The batch is in its final place: every slot it was assembled
        into holds its leaf of ``feed`` until that slot's next turn.
        Returns the milliseconds the batch waited for its slots, None for
        a batch that recycled none."""
        for name, slot in self._filled.items():
            slot.leaf = feed[name]
        waited, self._waited = self._waited, None
        self._filled = {}
        return None if waited is None else waited * 1e3


class DeviceFeeder:
    """Background-thread feed pipeline over a minibatch reader.

    ``DeviceFeeder(reader, topology).batches()`` yields FeedBatch items;
    each call to ``batches()`` starts a fresh producer thread (one per
    training pass, mirroring the per-pass ``reader()`` iterator). Use
    ``convert=`` to override batch conversion (e.g. ``pack_feed``) —
    signature ``convert(topology, data_batch, feeding, max_len)``.

    The feeder owns the host memory its batches are assembled in
    (:class:`HostBuffers`): one pool a feeder, so it lives across the
    passes of one ``train`` call and dies with it. Fixed-shape dense and
    index columns are copied into a ring of ``depth + 2`` recycled arrays
    (the queue, the batch being assembled and the one in the step), and
    a reader's rows are copied, never kept; with a ``parallelism`` that
    has the rule (``batch_leaf_sharding``) they go from there onto the
    mesh in one crossing. Sequence, nested and sparse
    slots, a short last batch and a custom ``convert=`` get fresh arrays
    as before; so does every caller of ``convert_feed`` that is not a
    feeder, since nobody there can say when a batch is dead.
    """

    def __init__(self, reader, topology, feeding=None, depth=2,
                 parallelism=None, convert=None, metrics_registry=None):
        if depth < 1:
            raise ValueError("DeviceFeeder depth must be >= 1")
        self.reader = reader
        self.topology = topology
        self.feeding = feeding
        self.depth = int(depth)
        self.parallelism = parallelism
        self._convert = convert
        m = metrics_registry or observe_metrics.get_registry()
        self.metrics = m
        self._m_stall = m.histogram(
            "paddle_tpu_data_feed_stall_ms",
            help="time the step thread blocked waiting for a pipelined "
                 "batch")
        self._m_convert = m.histogram(
            "paddle_tpu_data_feed_convert_ms",
            help="producer-thread batch conversion + device dispatch time")
        self._m_read = m.histogram(
            "paddle_tpu_data_feed_read_ms",
            help="producer-thread time in the reader's next()")
        self._m_host = m.histogram(
            "paddle_tpu_data_feed_host_ms",
            help="producer-thread host assembly: conversion less placement")
        self._m_place = m.histogram(
            "paddle_tpu_data_feed_place_ms",
            help="producer-thread hand-over of a batch's bytes to the "
                 "device(s)")
        self._m_backpressure = m.histogram(
            "paddle_tpu_data_feed_backpressure_ms",
            help="time the producer blocked on a full queue: the feed is "
                 "ahead of the step")
        self._m_buffer_wait = m.histogram(
            "paddle_tpu_data_feed_buffer_wait_ms",
            help="producer-thread wait for the last transfer out of the "
                 "host buffers a batch recycled (batches that recycled "
                 "one)")
        self._buffers = HostBuffers(
            self.depth + 2,
            reused=m.counter(
                "paddle_tpu_data_feed_buffers_reused_total",
                help="columns assembled into a recycled host buffer"),
            allocated=m.counter(
                "paddle_tpu_data_feed_buffers_allocated_total",
                help="host buffers allocated for the feeder's rings"),
            # a plan without the rule gets its shard_batch alone
            sharding_of=getattr(parallelism, "batch_leaf_sharding", None),
            placed_sharded=m.counter(
                "paddle_tpu_data_feed_placed_sharded_total",
                help="columns placed from a host buffer straight onto the "
                     "mesh, each device its own rows"))
        self._m_batches = m.counter(
            "paddle_tpu_data_batches_total",
            help="batches assembled by the feed pipeline")
        self._m_depth = m.gauge(
            "paddle_tpu_data_queue_depth",
            help="device-resident batches waiting ahead of the step")
        self._per_bucket = {}

    # -- producer side ------------------------------------------------------
    def _convert_batch(self, data_batch):
        from paddle_tpu import topology

        max_len = data_batch.bucket if isinstance(data_batch, BucketBatch) \
            else None
        if self._convert is not None:
            feed = self._convert(self.topology, data_batch, self.feeding,
                                 max_len)
        else:
            with topology.recycling_into(self._buffers):
                feed = topology.convert_feed(self.topology, data_batch,
                                             self.feeding, max_len=max_len)
        if self.parallelism is not None:
            # the DataParallel global-mesh placement shard_train_step
            # would apply — done HERE so the transfer overlaps compute.
            # The recycled columns are at their target already and pass
            # through by identity; what came on one device (sequence and
            # sparse slots, a short last batch, a convert= of the
            # caller's) is moved from there, and that array dies with
            # this rebinding
            feed = self.parallelism.shard_batch(feed)
        return feed

    def _produce(self, q, cancel, skip=0):
        def put(item):
            return _cancellable_put(q, item, cancel)

        span = observe_spans.span
        seq = 0  # of the batch being made: the pass's batch number
        try:
            batch_iter = iter(self.reader())
            while True:
                with span("feed_read", args={"batch": seq}) as read:
                    data_batch = next(batch_iter, _End)
                if data_batch is _End:
                    break
                if seq < skip:
                    # deterministic-resume cursor (trainer train(resume=)):
                    # the already-trained batch prefix is consumed from
                    # the reader (so ordering downstream is untouched)
                    # but never converted or device-placed. Still honor
                    # cancellation: a consumer abandoning mid-prefix
                    # must not leak this thread for the rest of it
                    if cancel.is_set():
                        return
                    seq += 1
                    continue
                if cancel.is_set():
                    # an abandoned producer that outlived its join must
                    # not touch the buffers its successor is using
                    return
                with span("feed_convert", args={"batch": seq}) as convert:
                    feed = self._convert_batch(data_batch)
                waited = self._buffers.sent(feed)  # ms, None: recycled none
                bucket, fill, pad, tokens, positions = _seq_stats(feed)
                # feed_convert's children are its placements and its
                # waits for a recycled buffer: place_ms is the former only
                wait_ms = waited or 0.0
                fb = FeedBatch(feed, len(data_batch), convert.dur * 1e3,
                               bucket=bucket, fill_tokens=fill,
                               pad_tokens=pad, seq=seq,
                               read_ms=read.dur * 1e3,
                               place_ms=convert.child_dur * 1e3 - wait_ms,
                               buffer_wait_ms=wait_ms, tokens=tokens,
                               positions=positions)
                self._m_read.observe(fb.read_ms)
                self._m_host.observe(fb.host_ms)
                self._m_place.observe(fb.place_ms)
                if waited is not None:
                    self._m_buffer_wait.observe(waited)
                with span("feed_put", args={"batch": seq}) as blocked:
                    taken = put(fb)
                if not taken:
                    return
                fb.backpressure_ms = blocked.dur * 1e3
                self._m_backpressure.observe(fb.backpressure_ms)
                seq += 1
                if cancel.is_set():
                    return
        except BaseException as exc:  # re-raised on the consumer thread
            put(_Error(exc))
            return
        put(_End)

    # -- consumer side ------------------------------------------------------
    def batches(self, skip=0):
        """Generator of FeedBatch items; owns the producer thread for
        its lifetime (closing the generator cancels and joins it).
        ``skip=N`` drops the reader's first N batches unconverted — the
        resume cursor of a checkpointed run (docs/distributed.md)."""
        q = queue.Queue(maxsize=self.depth)
        # as many slots as batches can be alive at once: the queue, the
        # one being assembled, the one in the step (chunks() deepens the
        # queue to k for the members of an open chunk)
        self._buffers.slots = self.depth + 2
        cancel = threading.Event()
        thread = threading.Thread(
            target=self._produce, args=(q, cancel, int(skip)),
            name="data-feeder-producer", daemon=True)
        thread.start()
        seq = int(skip)  # of the batch about to be taken: FIFO, one producer
        try:
            while True:
                with observe_spans.span("feed", args={"pipelined": True,
                                                      "batch": seq}) as scope:
                    item = q.get()
                seq += 1
                if item is _End:
                    return
                if isinstance(item, _Error):
                    raise item.exc
                item.stall_ms = scope.dur * 1e3
                self._m_stall.observe(item.stall_ms)
                self._m_convert.observe(item.convert_ms)
                self._m_batches.inc()
                self._m_depth.set(q.qsize())
                if item.bucket:
                    self._bucket_gauges(item)
                yield item
        finally:
            cancel.set()
            # wake a producer blocked on a full queue, then let it finish
            _drain(q)
            thread.join(timeout=5.0)

    def chunks(self, k, skip=0):
        """Generator of :class:`ChunkBatch` groups of up to ``k``
        consecutive, shape-compatible batches (the fused-loop feed,
        ``trainer.SGD.train steps_per_call=``). ``skip`` passes through
        to :meth:`batches` — the resume cursor counts batches, so a
        resumed fused run regroups the remainder into fresh chunks.

        A queue shallower than ``k`` would silently serialize the fused
        loop — the producer could never stage a full chunk ahead of the
        step — so the depth is raised to ``k`` up front (loudly, with
        both numbers). A shape boundary (bucket change, partial final
        batch) closes the open chunk early: chunks never mix programs,
        so every chunk lowers to one already-compiled scan shape."""
        k = int(k)
        if k < 1:
            raise ValueError("chunk size must be >= 1, got %d" % k)
        if k > self.depth:
            logger.info(
                "DeviceFeeder queue depth %d is shallower than the fused "
                "chunk size %d: deepening to %d so a chunk never starves "
                "the dispatch", self.depth, k, k)
            self.depth = k
        if k == 1:
            # one-batch units (the trainer's loop without steps_per_call):
            # nothing to group, no shape to compare
            for fb in self.batches(skip=skip):
                yield self._stack_chunk([fb])
            return
        group, key = [], None
        sizes, split = [], 0

        def close(group, was_split=False):
            nonlocal split
            split += bool(was_split)
            sizes.append(len(group))
            # shape churn (per-batch pad lengths without buckets=) would
            # close every chunk at size 1 and silently hand back per-step
            # dispatch — the very overhead steps_per_call exists to kill.
            # Same loudness rule as the depth mismatch above.
            if k > 1 and len(sizes) == 8 and split >= 6:
                logger.warning(
                    "fused chunks are splitting on shape boundaries "
                    "(%d of the first %d chunks, avg %.1f of %d steps): "
                    "consecutive batches rarely share a jit shape — pass "
                    "buckets= (trainer.SGD.train / docs/data.md) so "
                    "same-length batches group and chunks actually fuse",
                    split, len(sizes), sum(sizes) / len(sizes), k)
            return self._stack_chunk(group)

        for fb in self.batches(skip=skip):
            fb_key = _feed_shape_key(fb.feed)
            if group and fb_key != key:
                yield close(group, was_split=True)
                group = []
            key = fb_key
            group.append(fb)
            if len(group) == k:
                yield close(group)
                group = []
        if group:
            yield close(group)

    def _stack_chunk(self, group):
        """Group K device-resident feeds into one ChunkBatch. The members
        are already converted and mesh-placed by the producer thread, so
        grouping is pure bookkeeping — the fused program stacks them
        inside the jit. Single-batch chunks pass the member feed through
        untouched so a K=1 (or remainder-1) chunk reuses the plain
        per-step program."""
        if len(group) == 1:
            return ChunkBatch(group[0].feed, group, stacked=False)
        return ChunkBatch(tuple(fb.feed for fb in group), group,
                          stacked=True)

    def _bucket_gauges(self, fb):
        """Cumulative per-bucket fill/waste — the training twins of the
        serve engine's paddle_tpu_serve_*_ratio{bucket=} series."""
        pb = self._per_bucket.setdefault(fb.bucket, [0, 0])
        pb[0] += fb.fill_tokens
        pb[1] += fb.pad_tokens
        fill, pad = pb
        slots = fill + pad
        label = {"bucket": str(fb.bucket)}
        self.metrics.gauge("paddle_tpu_data_bucket_fill_ratio",
                           help="sequence tokens / padded slots "
                                "(cumulative, per padded length)",
                           labels=label).set(fill / slots)
        self.metrics.gauge("paddle_tpu_data_padding_waste_ratio",
                           help="padding slots / padded slots "
                                "(cumulative, per padded length)",
                           labels=label).set(pad / slots)


