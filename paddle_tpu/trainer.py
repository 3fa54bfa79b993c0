"""The SGD trainer: event-driven training loop around one jitted train step.

Parity with python/paddle/v2/trainer.py (SGD.train :106-176 event loop,
test :178) and the C++ hot loop TrainerInternal::trainOneBatch
(paddle/trainer/TrainerInternal.cpp:66-140). The reference's per-batch
sequence — startBatch → forwardBackward (layer loop) → per-parameter
updateCallback → finishBatch — collapses into ONE XLA program here:
forward + backward (jax.grad) + optimizer update + BN-state update + metric
stats, compiled once and reused every batch. GradientMachine has no separate
existence: the topology IS the gradient machine.

Data parallelism: pass ``parallelism=paddle_tpu.parallel.DataParallel(...)``
to shard the batch over a device mesh — the train step is then pjit-ed with
batch-sharded inputs and replicated (or ZeRO-sharded) parameters, replacing
MultiGradientMachine and the pserver path (SURVEY.md §2.4).
"""

import contextlib
import functools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import event as v2_event
from paddle_tpu.graph import LayerNode
from paddle_tpu.parameters import Parameters
from paddle_tpu.data.feeder import DeviceFeeder, inline_units
from paddle_tpu.topology import Topology, convert_feed
from paddle_tpu.utils import flags
from paddle_tpu.utils.error import enforce
from paddle_tpu.utils.logger import logger


from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import sentinel as observe_sentinel
from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.observe import step_counts
from paddle_tpu.observe import steplog as observe_steplog
from paddle_tpu.observe import tracing as observe_tracing
from paddle_tpu.observe import trainview as observe_trainview
from paddle_tpu.utils.stat import global_stats


# a finalized step's phases: the step thread's own, and the producer
# thread's for the batch it waited for (FeedBatch.<name>_ms)
_STEP_PHASES = ("wait", "dispatch", "readback", "handler")
_FEED_PHASES = ("read", "host", "place", "buffer_wait", "backpressure")


def _make_replica(trainable):
    """Compute-dtype copy of the trainable carry (bf16 read replica)."""
    from paddle_tpu.core import dtype as dtype_mod

    return jax.tree.map(dtype_mod.to_compute, trainable)


class SGD:
    """v2-API trainer. ``update_equation`` is a paddle_tpu.optimizer.Optimizer."""

    def __init__(self, cost, parameters, update_equation, extra_layers=None,
                 is_local=True, feeding=None, parallelism=None):
        from paddle_tpu.optimizer import Optimizer

        enforce(isinstance(parameters, Parameters),
                "parameters must be a Parameters object")
        enforce(isinstance(update_equation, Optimizer),
                "update_equation must be an Optimizer")
        from paddle_tpu.multi_network import MultiNetwork

        # the set-up span `trainer_prepare` (docs/observability.md): the
        # topology, the step functions, and masters, replica and optimizer
        # slots handed to the device (the calls, no wait for the device)
        placed = sum(int(getattr(v, "nbytes", 0))
                     for v in parameters.as_dict().values())
        with self._phase("trainer_prepare", args={"bytes": placed}):
            if isinstance(cost, MultiNetwork):
                # multi_nn parity: joint cost = sum_i w_i * mean(cost_i)
                self.costs = list(cost.costs)
                self._cost_weights = list(cost.weights)
            else:
                self.costs = ([cost] if isinstance(cost, LayerNode)
                              else list(cost))
                self._cost_weights = [1.0] * len(self.costs)
            extra = [e for e in (extra_layers or [])]
            self.evaluators = [e for e in extra
                               if getattr(e, "is_evaluator", False)]
            self.extra_outputs = [e for e in extra
                                  if not getattr(e, "is_evaluator", False)]
            self.topology = Topology(self.costs + self.evaluators
                                     + self.extra_outputs)
            self.parameters = parameters
            self.optimizer = update_equation
            self.feeding = feeding
            self.parallelism = parallelism
            # the slowest steps with the phases of their wall interval
            # (_close_step); dumped and reset per pass under
            # PADDLE_TPU_STATS=1
            self.slow_steps = observe_tracing.TraceExemplars(capacity=5)
            self.__prepare__()

    def __prepare__(self):
        trainable_names, static_names, state_names = self.parameters.partition()
        self._trainable_names = trainable_names
        self._static_names = static_names
        self._state_names = state_names
        specs = {n: self.parameters.spec(n) for n in self.parameters.names()}
        self._param_meta = {
            n: s.attr for n, s in specs.items() if s is not None and not s.is_state
        }
        cost_names = [c.name for c in self.costs]
        cost_weights = self._cost_weights
        eval_nodes = self.evaluators

        topo = self.topology
        optimizer = self.optimizer
        param_meta = self._param_meta

        # flat master-parameter pool: uniform trainables ride the train
        # step as ONE array (single fused optimizer update instead of
        # hundreds of tiny per-buffer kernels — optimizer.ParamPool)
        from paddle_tpu.optimizer import ParamPool

        host = self.parameters.as_dict()
        pool = ParamPool({n: host[n] for n in trainable_names},
                         self._param_meta)
        self._pool = pool if (pool.enabled()
                              and ParamPool.compatible_with(optimizer)) \
            else None
        use_pool = self._pool is not None

        def split(params):
            t = {n: params[n] for n in trainable_names}
            s = {n: params[n] for n in static_names}
            st = {n: params[n] for n in state_names}
            return t, s, st

        self._split = split

        def forward_all(params, feed, mode, rng):
            wanted = cost_names + [e.name for e in eval_nodes] \
                + [o.name for o in self.extra_outputs]
            counts = {}
            values, updates = topo.apply(params, feed, mode=mode, rng=rng,
                                         outputs=wanted, counts=counts)
            cost_total = sum(w * jnp.mean(values[c])
                             for c, w in zip(cost_names, cost_weights))
            eval_stats = {e.name: values[e.name] for e in eval_nodes}
            if counts and mode == "train":
                # the step's counters whose values are data leave beside
                # the cost and are read in its readback
                eval_stats[step_counts.KEY] = counts
            return cost_total, values, updates, eval_stats

        def train_step(trainable, replica, static, state, opt_state, feed,
                       rng):
            # Mixed precision runs fwd/bwd on a bf16 READ REPLICA of the
            # f32 masters, written in the same fused update as the
            # optimizer's master write: the passes stop re-reading the f32
            # masters every step (AlexNet: 9.49 -> 9.27 ms/step device,
            # benchmark/exp_bf16_replica.py) and gradients materialize in
            # the compute dtype (they were bf16 at every interior edge
            # already); optimizer arithmetic stays f32 on the f32 masters.
            def loss_fn(tr):
                full = pool.expand(tr) if use_pool else tr
                params = {**full, **static, **state}
                cost_total, values, updates, eval_stats = forward_all(
                    params, feed, "train", rng)
                return cost_total, (updates, eval_stats)

            (loss, (updates, eval_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                    replica if replica is not None else trainable)
            if replica is not None:
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32), grads)
            new_trainable, new_opt_state = optimizer.step(
                trainable, grads, opt_state, param_meta)
            new_state = {**state, **updates}
            new_replica = (_make_replica(new_trainable)
                           if replica is not None else None)
            return (loss, new_trainable, new_replica, new_state,
                    new_opt_state, eval_stats)

        def eval_step(trainable, static, state, feed):
            full = pool.expand(trainable) if use_pool else trainable
            params = {**full, **static, **state}
            cost_total, values, _, eval_stats = forward_all(
                params, feed, "test", None)
            outs = {o.name: values[o.name] for o in self.extra_outputs}
            return cost_total, eval_stats, outs

        def train_chunk(trainable, replica, static, state, opt_state,
                        feeds, rng):
            # Multi-step fused region (train steps_per_call=K): K
            # optimizer steps as ONE lax.scan dispatch. ``feeds`` arrives
            # as a length-K tuple of device-resident trees and is stacked
            # INSIDE the program, and the per-step rng keys are split
            # from the ``rng`` carry in here too — the same sequential
            # threefry splits the per-step loop does eagerly, so the key
            # stream (dropout masks etc.) is K-invariant, but without
            # per-step host dispatches (eager split + eager jnp.stack
            # are exactly the overhead the scan exists to kill). The
            # trainable/replica/running-state/optimizer carries stay
            # device-resident across the whole chunk and are donated
            # exactly like the per-step program's, so the host is visited
            # once per K steps: losses/eval stats come back as length-K
            # stacks read at chunk finalize, with the advanced rng carry.
            step_rngs = []
            for _ in range(len(feeds)):
                rng, step_rng = jax.random.split(rng)
                step_rngs.append(step_rng)
            xs = (jax.tree.map(lambda *x: jnp.stack(x), *feeds),
                  jnp.stack(step_rngs))

            def body(carry, x):
                tr, rep, st, opt = carry
                feed, step_rng = x
                (loss, tr, rep, st, opt, stats) = train_step(
                    tr, rep, static, st, opt, feed, step_rng)
                return (tr, rep, st, opt), (loss, stats)

            carry = (trainable, replica, state, opt_state)
            (tr, rep, st, opt), (losses, stats) = jax.lax.scan(
                body, carry, xs)
            return losses, tr, rep, st, opt, stats, rng

        if self.parallelism is not None:
            self._train_step = self.parallelism.shard_train_step(
                train_step, self)
            self._eval_step = self.parallelism.shard_eval_step(eval_step, self)
            # fused chunks need a strategy-aware wrapper; strategies
            # without one reject steps_per_call loudly at train() time
            self._train_chunk = (
                self.parallelism.shard_train_chunk(train_chunk, self)
                if hasattr(self.parallelism, "shard_train_chunk") else None)
        else:
            self._train_step = jax.jit(train_step,
                                       donate_argnums=(0, 1, 3, 4))
            self._train_chunk = jax.jit(train_chunk,
                                        donate_argnums=(0, 1, 3, 4))
            self._eval_step = jax.jit(eval_step)

        # device-resident training state
        self._materialize_device_state()
        self._opt_state = optimizer.init_state(self._trainable,
                                               self._param_meta)
        # update hooks prune the initial values too (reference:
        # StaticPruningHook masks at init, not just per update)
        hooked = False
        for n, attr in self._param_meta.items():
            for hook in getattr(attr, "update_hooks", None) or ():
                if n in self._trainable:
                    self._trainable[n] = hook.apply(n, self._trainable[n])
                    hooked = True
        if self._replica is not None and hooked:
            # hooks mutated the masters above; the replica must mirror the
            # POST-hook weights or step 1 trains on unpruned values. The
            # old one goes first: two alive were 2 bytes a parameter of
            # peak_bytes_in_use that no step needs
            self._replica = None
            self._replica = _make_replica(self._trainable)
        self._rng = jax.random.PRNGKey(flags.get_flag("seed") or 0)
        self._step_count = 0
        # the live AsyncCheckpointer while train(checkpoint_dir=...) runs
        # (chaos harness/elastic runner poll .last_committed())
        self._ckpt_writer = None

    # -- main loop ----------------------------------------------------------
    def train(self, reader, num_passes=1, event_handler=None, feeding=None,
              sync_params=True, test_reader=None, feed_pipeline=False,
              buckets=None, steps_per_call=None, checkpoint_dir=None,
              checkpoint_every=0, checkpoint_keep=3, resume=False,
              checkpoint_sync=False):
        """Event-driven training (v2 SGD.train parity). ``reader`` yields
        minibatches (lists of sample tuples). With ``test_reader`` and a
        nonzero ``test_period`` flag, an evaluation pass runs every N
        batches (reference: Tester::testOnePeriod, --test_period).

        ``feed_pipeline`` (paddle_tpu.data, docs/data.md): move batch
        conversion + device placement onto a background thread that keeps
        N batches device-resident ahead of the step (True = depth 2, or
        an int depth) — PyDataProvider2's pool-thread double buffering,
        TPU-shaped. Off (default) is byte-identical to the historical
        synchronous feed; on, the fixed-seed loss trajectory is identical
        (tests/test_data_pipeline.py) and the steplog gains ``feed``
        records plus a ``paddle_tpu_data_feed_stall_ms`` histogram.

        ``buckets``: regroup the minibatch stream by sequence length
        (True = auto-derive boundaries from the observed distribution, or
        an explicit ascending list) so each batch pads only to its bucket
        — one jit cache entry per bucket (data/bucketing.py). Partial
        batches flush at end of pass with their own row counts (extra jit
        entries when pass-to-pass leftovers vary, e.g. under shuffling);
        pass the dict form ``buckets={"boundaries": [...],
        "drop_remainder": True}`` to drop them instead.

        ``steps_per_call=K`` (docs/data.md "Multi-step fused training
        loop"): run K optimizer steps per dispatch as one jitted
        ``lax.scan`` over a chunk of K device-resident feeds with the
        trainable/replica/state/optimizer carries donated — the host is
        visited once per chunk instead of once per step (the
        dispatch-bound fix for scan-heavy models, observe/attribution
        ``dispatch_gap``). Implies the pipelined feed (the DeviceFeeder
        queue is auto-deepened to >= K); losses and evaluator stats come
        back as length-K stacks read at chunk finalize, so per-step
        events (``EndIteration`` etc.), steplog ``step`` records, and
        sentinel checks still fire once per real step — one dispatch
        behind, at chunk granularity (sentinel latency, checkpoint
        boundaries and per-step wall timing all coarsen to the chunk; the
        chunk itself is the additive ``train_chunk`` steplog record).
        ``K=1`` runs the byte-identical per-step program with a chunk's
        records; the default (None/0) groups nothing. Partial final
        chunks (K does not divide the pass length, or a bucket boundary
        splits a chunk) scan at their own length — one extra compile per
        distinct chunk size.

        ``checkpoint_dir`` + ``checkpoint_every=N`` (docs/distributed.md):
        every N global steps a full training-state snapshot — parameters,
        BN state, optimizer slots, the threefry key and the reader
        position (pass id + batch cursor) — is committed durably.
        By default the save is OVERLAPPED: the step thread pays one
        jitted device-side buffer clone + an async device→host kick,
        and a named background writer (``ckpt-writer``) does the
        serialization + fsync + atomic rename (the additive
        ``checkpoint`` steplog record carries duration/bytes/overlap);
        ``checkpoint_sync=True`` blocks the step thread instead (the
        A/B contrast, ``benchmark/exp_checkpoint.py``). ``resume=True``
        restores the newest valid checkpoint in ``checkpoint_dir``
        before training and continues the IDENTICAL fixed-seed
        trajectory: earlier passes are skipped, the resumed pass's
        already-trained batches are skipped via the feeder's batch
        cursor, and the rng/optimizer state pick up exactly where the
        snapshot was taken. ``num_passes`` stays the TOTAL pass count
        (a run resumed from pass 1 of 3 trains passes 1..2). Under a
        fused loop (``steps_per_call=K``) checkpoints land at chunk
        boundaries — the first step boundary at or past the cadence.
        """
        # `train_enter` runs from here to the feeder built, `train_exit`
        # from the last step read back to the return (docs/observability.md
        # "Set-up spans"): both open and close inside `_train_passes`
        call_start = time.perf_counter()
        with contextlib.ExitStack() as enter, \
                contextlib.ExitStack() as leave:
            enter.enter_context(self._phase("train_enter"))
            # opens `train_exit` the first time it is called
            leaving = functools.cache(
                lambda: leave.enter_context(self._phase("train_exit")))
            user_handler = event_handler or default_event_handler
            # what the step thread did since the last finalized step, in ms:
            # each span of the loop below adds its duration as it closes
            phases = dict.fromkeys(_STEP_PHASES, 0.0)

            def event_handler(event):
                with observe_spans.span("handler") as scope:
                    user_handler(event)
                phases["handler"] += scope.dur * 1e3

            feeding = feeding or self.feeding
            if buckets is not None and buckets is not False:
                from paddle_tpu.data import bucketing as data_bucketing

                opts = dict(buckets) if isinstance(buckets, dict) else {
                    "boundaries": None if buckets is True else buckets}
                bounds = opts.get("boundaries")
                reader = data_bucketing.rebucket_batches(
                    reader, buckets=bounds,
                    drop_remainder=bool(opts.get("drop_remainder", False)),
                    length_of=data_bucketing.topology_length_of(
                        self.topology, feeding))
            k = int(steps_per_call or 0)
            if k:
                enforce(k >= 1, "steps_per_call must be >= 1, got %d", k)
                enforce(self._train_chunk is not None,
                        "steps_per_call requires a parallelism with a "
                        "shard_train_chunk wrapper (%s has none)",
                        type(self.parallelism).__name__)
            if os.environ.get("PADDLE_TPU_ANALYZE"):
                # pre-compile static checks (docs/analyze.md): packing
                # legality, dtype hazards, donation conflicts — warnings
                # log, errors raise before the first dispatch
                from paddle_tpu.analyze.topology_check import pretrain_check

                pretrain_check(self, steps_per_call=k or None)

            # observability: host spans around every phase (feed / device
            # step / evaluator read-back — they feed the global StatSet,
            # dumped per pass under PADDLE_TPU_STATS=1, reference: the
            # per-pass globalStat.printAllStatus dump) and, under
            # PADDLE_TPU_TELEMETRY=<dir>, a JSONL step log + Chrome-trace
            # export of the spans (docs/observability.md).
            tracer = observe_spans.get_tracer()
            meta = {"phase": "train", "num_passes": int(num_passes)}
            if k:
                meta["steps_per_call"] = k
            # training-fleet identity (observe/trainview.py): a distributed
            # worker stamps PADDLE_TPU_TRAIN_WORKER before training, and
            # every artifact this run emits carries it — the steplog meta
            # plus a per-worker file name (train-t<i>.steps.jsonl), so
            # `cli observe` can pool a shared telemetry directory by worker
            wid = observe_trainview.worker_id()
            run_name = "train"
            if wid is not None:
                meta["worker"] = wid
                run_name = observe_trainview.worker_run_name("train", wid)
            slog = observe_steplog.from_env(run_name=run_name, meta=meta)
            prev_recording = tracer.record_events
            if slog is not None:
                # telemetry may be flag-configured (no env var), so force
                # event recording on — this run WILL export a trace (restored
                # after, so later non-telemetry runs don't keep buffering)
                tracer.record_events = True
                # the exported trace covers exactly this run, from its
                # open `train_enter` on
                tracer.reset(at=call_start)
            # the in-flight loss sentinel + flight recorder (observe/
            # sentinel.py): cheap host checks on the already-read-back cost,
            # PADDLE_TPU_SENTINEL governs warn/halt/off; the crash artifact
            # lands next to the steplog when telemetry is on
            sentinel = observe_sentinel.from_env(steplog=slog,
                                                 run_name=run_name,
                                                 worker=wid)
            start_pass = start_cursor = 0
            if checkpoint_dir and resume:
                start_pass, start_cursor = self._resume_restore(
                    checkpoint_dir, mode=resume)
            ckpt_ctx = None
            if checkpoint_dir and checkpoint_every:
                ckpt_ctx = self._checkpoint_setup(
                    checkpoint_dir, checkpoint_every, checkpoint_keep,
                    checkpoint_sync, slog)
            # first step's wall interval is anchored at train start, so the
            # first record honestly includes compile time (the compile shows
            # up as an ``event`` record too when jax.monitoring emits it)
            completed = False
            last_final = {"t": time.perf_counter(), "phases": phases}
            try:
                self._train_passes(reader, num_passes, event_handler, feeding,
                                   sync_params, test_reader, slog, last_final,
                                   sentinel, k, feed_pipeline, start_pass,
                                   start_cursor, ckpt_ctx, enter.close,
                                   leaving)
                completed = True
            except BaseException as exc:
                # any escape from the training loop dumps the black box
                # (a sentinel halt already dumped; on_exception skips it)
                if sentinel is not None:
                    sentinel.on_exception(exc)
                if ckpt_ctx is not None and ckpt_ctx["writer"] is not None:
                    from paddle_tpu.distributed.elastic import (
                        SelfLeaseLost, WorkerLost)

                    if isinstance(exc, (WorkerLost, SelfLeaseLost)):
                        # reform abort: each worker stops at its OWN step
                        # boundary, so draining the pending snapshot here
                        # would advance the shared directory's rewind target
                        # differently per worker; everyone must rewind to
                        # the same committed checkpoint (run_elastic settles
                        # the directory before it restores). A self-lapsed
                        # worker especially: its peers have already
                        # reformed, so its pending snapshot is from the
                        # ABANDONED pre-reform branch — committing it would
                        # hand the next rewind pre-reform state.
                        ckpt_ctx["writer"].discard_pending()
                raise
            finally:
                # ``completed`` (not sys.exc_info(), which also reports an
                # OUTER handled exception when train() runs inside an except
                # block) decides who wins: on a normal exit a writer error
                # must surface, while an exception already unwinding must
                # stay visible over the writer's
                try:
                    # drain + join the ckpt-writer thread; a writer error
                    # surfaces HERE
                    if ckpt_ctx is not None:
                        self._checkpoint_close(ckpt_ctx)
                except Exception:
                    if completed:
                        raise
                    logger.exception(
                        "checkpoint writer error during unwind")
                finally:
                    if slog is not None:
                        try:
                            tracer.export(slog.trace_path)
                        finally:
                            tracer.record_events = prev_recording
                            slog.close()

    @staticmethod
    def _worker_labels():
        """A training-fleet worker labels its series so a shared scrape
        keeps the processes apart (observe/trainview.py)."""
        wid = observe_trainview.worker_id()
        return {"worker": wid} if wid is not None else None

    @classmethod
    def _phase(cls, name, args=None):
        """A set-up or call phase of this trainer: the span and its
        always-on histogram (observe/spans.py PHASE_HISTOGRAMS)."""
        return observe_spans.phase(name, args=args,
                                   labels=cls._worker_labels())

    # process-wide training metrics (observe/metrics.py; scraped through
    # any serve front end in the same process, snapshot()-able anywhere)
    @classmethod
    def _train_metrics(cls):
        m = observe_metrics.get_registry()
        labels = cls._worker_labels()
        return (m.counter("paddle_tpu_train_steps_total",
                          help="finalized training steps", labels=labels),
                m.counter("paddle_tpu_train_examples_total",
                          help="examples consumed by training steps",
                          labels=labels),
                m.gauge("paddle_tpu_train_loss",
                        help="last finalized step loss", labels=labels),
                m.gauge("paddle_tpu_train_examples_per_sec",
                        help="examples/s of the last finalized step",
                        labels=labels),
                (m.histogram("paddle_tpu_train_dispatch_ms",
                             help="step-thread time dispatching the jitted "
                                  "step, per finalized step", labels=labels),
                 m.histogram("paddle_tpu_train_readback_ms",
                             help="step-thread time blocked reading the "
                                  "loss and evaluator stats back, per "
                                  "finalized step", labels=labels),
                 m.histogram("paddle_tpu_train_handler_ms",
                             help="step-thread time inside the event "
                                  "handler, per finalized step",
                             labels=labels)),
                (m.histogram("paddle_tpu_train_step_tokens",
                             help="valid tokens of a dispatched step's "
                                  "widest sequence slot", labels=labels),
                 m.histogram("paddle_tpu_train_step_positions",
                             help="rows x padded length of a dispatched "
                                  "step's widest sequence slot",
                             labels=labels)))

    @staticmethod
    def _observe_tokens(m_tokens, tokens, positions):
        """One observation a dispatched step whose feed has a sequence
        slot: what the step computes over, and what of it is not padding."""
        if positions:
            m_tokens[0].observe(tokens)
            m_tokens[1].observe(positions)

    def _close_step(self, last_final, m_phases, step, batches=()):
        """A step's (a fused chunk's) wall interval ends here, its loss
        just read: observe what the step thread did in it, offer it with
        the producer's phases of the ``batches`` taken in it to the
        slowest-steps reservoir, and start the next. Returns its ms."""
        now = time.perf_counter()
        wall_ms = (now - last_final["t"]) * 1000.0
        phases = last_final["phases"]
        for hist, name in zip(m_phases, _STEP_PHASES[1:]):
            hist.observe(phases[name])
        entry = dict(phases)
        for fb in batches:
            for name in _FEED_PHASES:
                entry[name] = entry.get(name, 0.0) + getattr(fb, name + "_ms")
        self.slow_steps.offer(wall_ms, entry, step=step)
        self._reanchor(last_final, now)
        return wall_ms

    @staticmethod
    def _reanchor(last_final, now=None):
        """Start the next wall interval at ``now``: what came before it
        (an eval pass, pass-boundary work) is charged to no step."""
        last_final["t"] = time.perf_counter() if now is None else now
        phases = last_final["phases"]
        for name in phases:
            phases[name] = 0.0

    def _train_passes(self, reader, num_passes, event_handler, feeding,
                      sync_params, test_reader, slog, last_final, sentinel,
                      k, feed_pipeline, start_pass, start_cursor, ckpt,
                      entered, leaving):
        """The train loop, over dispatch units (data/feeder.py
        ChunkBatch): n >= 1 consecutive batches handed to the device in
        one call. Where a unit comes from is its source's business: the
        feeder's producer thread (``feed_pipeline``; ``steps_per_call=K``
        implies it and groups up to K batches a unit) or, by default,
        this thread (``inline_units``). A stacked unit is ONE
        ``lax.scan`` dispatch (``_train_chunk``); a one-batch unit (no
        ``steps_per_call``, K=1, a remainder, a bucket boundary) is the
        ordinary per-step program — byte-identical math, no scan-of-1
        compile.

        One-deep pipeline (PyDataProvider2 pool-thread parity,
        TPU-shaped): unit u+1 is converted and DISPATCHED before unit u's
        loss/stats are fetched from the device, so host-side data
        conversion and event handling overlap the accelerator — the loop
        never blocks on a per-batch device_get before launching the next
        step. Events, steplog ``step`` records, metrics and sentinel
        checks still fire once per real step, in order with exact values,
        one dispatch behind (a unit's n steps at a time); handlers
        reading live parameters mid-pass see the in-flight unit.

        What ``steps_per_call`` changes besides the grouping is what the
        wall interval between two readbacks is called: per-step wall time
        is unmeasurable inside a fused region, so ``step`` records then
        carry no wall_ms and the interval lands on the unit's
        ``train_chunk`` record (span, trainview and sentinel ring
        likewise); without it the interval is the step's own.

        ``entered()`` closes the call's ``train_enter`` span once the
        source of units is built; ``leaving()`` opens ``train_exit`` once
        the last pass's last step is read back, so that pass's end (its
        test, ``sync_back``, ``EndPass``) is the call's way out."""
        log_period = flags.get_flag("log_period")
        test_period = flags.get_flag("test_period")
        (m_steps, m_examples, m_loss, m_examples_per_sec,
         m_phases, m_tokens) = self._train_metrics()
        # per-worker windowed health (observe/trainview.py): the fleet
        # view's live counterpart to the steplog, O(1) memory
        thist = observe_trainview.get_train_history()
        phases = last_final["phases"]
        # ONE feeder across passes (each pass's generator starts a fresh
        # producer thread) so its cumulative per-bucket fill/waste gauges
        # span the whole run, like the serve engine's. Conversion and
        # device placement happen on that thread; the "feed" span on this
        # one measures only the STALL the step thread spent waiting for
        # data (also a paddle_tpu_data_feed_stall_ms histogram sample, and
        # each batch writes a ``feed`` steplog record), so feed_ms on the
        # step record is the host time actually charged to the step thread
        feeder = None
        if k or feed_pipeline:
            feeder = DeviceFeeder(
                reader, self.topology, feeding=feeding,
                depth=max(self._feed_depth(feed_pipeline), k),
                parallelism=self.parallelism)
        entered()
        for pass_id in range(start_pass, num_passes):
            # resumed pass: the first ``start_cursor`` batches were
            # already trained before the checkpoint — skip them on the
            # stream so batch numbering (and every event/record keyed on
            # it) continues exactly where the snapshot left off. The
            # cursor counts BATCHES, so a resume lands exactly even when
            # chunk regrouping differs — the fused math is K-invariant
            cursor0 = start_cursor if pass_id == start_pass else 0
            if feeder is not None:
                units = feeder.chunks(k or 1, skip=cursor0)
            else:
                units = inline_units(reader, self.topology, feeding,
                                     skip=cursor0)
            if cursor0:
                units = self._resume_pass_iter(units, pass_id)
                if units is None:
                    continue  # pass was complete at the checkpoint
            event_handler(v2_event.BeginPass(pass_id))
            eval_acc = {e.name: None for e in self.evaluators}
            batch_id = cursor0
            pending = None  # (batch_id, base_step, losses, stats, unit)
            taken = ()  # the producer's FeedBatches since the last finalize

            def finalize(item):
                b_id, base_step, losses, stats, unit = item
                with observe_spans.span("eval_readback",
                                        args={"batch": b_id}) as readback:
                    costs = np.atleast_1d(
                        np.asarray(jax.device_get(losses), dtype=np.float64))
                    host_stats = jax.device_get(stats) if stats else {}
                phases["readback"] += readback.dur * 1e3
                wall_ms = self._close_step(last_final, m_phases,
                                           base_step + 1, taken)
                if wall_ms > 0:
                    m_examples_per_sec.set(unit.examples / wall_ms * 1000.0)
                step_wall = step_feed = None
                if k:
                    if slog is not None:
                        slog.log_train_chunk(
                            step=base_step + 1, steps=unit.steps,
                            pass_id=pass_id, batch_id=b_id, wall_ms=wall_ms,
                            feed_ms=unit.stall_ms,
                            cost_first=float(costs[0]),
                            cost_last=float(costs[-1]),
                            examples=unit.examples)
                    thist.record_chunk(unit.steps, wall_ms,
                                       examples=unit.examples,
                                       feed_stall_ms=unit.stall_ms)
                    if sentinel is not None:
                        # chunk granularity: ONE ring record per chunk;
                        # the per-loss checks run in the per-step tail
                        # below, where the unfused sentinel.step does
                        sentinel.record_chunk(base_step + 1, costs,
                                              pass_id=pass_id, batch_id=b_id,
                                              wall_ms=round(wall_ms, 4))
                else:
                    step_wall, step_feed = wall_ms, unit.stall_ms
                    thist.record_step(wall_ms, examples=unit.examples,
                                      feed_stall_ms=unit.stall_ms)
                # the per-step tail: a unit of n steps runs it n times
                for i, fb in enumerate(unit.batches):
                    step, b = base_step + i + 1, b_id + i
                    metrics = {}
                    for e in self.evaluators:
                        per = host_stats[e.name]
                        # evaluator stats may be arbitrary pytrees; a
                        # stacked unit carries step i at leading index i
                        eval_acc[e.name] = e.merge(
                            eval_acc[e.name],
                            jax.tree.map(lambda a: a[i], per)
                            if unit.stacked else per)
                        metrics[e.name] = e.result(eval_acc[e.name])
                    cost = float(costs[i])
                    if step_counts.KEY in host_stats:
                        step_counts.observe(
                            observe_metrics.get_registry(),
                            host_stats[step_counts.KEY],
                            i if unit.stacked else None,
                            self._worker_labels())
                    if slog is not None:
                        slog.log_step(
                            step=step, pass_id=pass_id, batch_id=b,
                            wall_ms=step_wall, feed_ms=step_feed, cost=cost,
                            examples=fb.examples, metrics=metrics)
                    m_steps.inc()
                    m_examples.inc(fb.examples)
                    m_loss.set(cost)
                    if sentinel is not None:
                        # halt mode raises TrainingAnomaly here (black
                        # box already dumped by the sentinel itself): the
                        # anomalous step's record/metrics have landed, its
                        # events do not fire, and a halt-mode trip must
                        # not swallow the records/events of the unit's
                        # pre-anomaly steps
                        if k:
                            sentinel.check(step, cost, pass_id=pass_id,
                                           chunk_index=i)
                        else:
                            sentinel.step(step, cost=cost, pass_id=pass_id,
                                          batch_id=b,
                                          wall_ms=round(wall_ms, 4))
                    # reference per-batch sequence: forwardBackward done →
                    # EndForwardBackward → stats/periodic-test →
                    # EndIteration (TrainerInternal.cpp:66-140). With the
                    # one-deep pipeline both fire at finalize time, one
                    # dispatch behind.
                    event_handler(v2_event.EndForwardBackward(
                        pass_id, b, gm=self))
                    if log_period and b % log_period == 0:
                        logger.info("pass %d batch %d cost=%.6f %s", pass_id,
                                    b, cost, _fmt_metrics(metrics))
                        if flags.get_flag("show_layer_stat"):
                            self._log_layer_stats(fb.feed)
                    psp = flags.get_flag("show_parameter_stats_period")
                    if psp and step % max(psp, 1) == 0:
                        self._log_param_stats()
                    if (test_reader is not None and test_period
                            and step % test_period == 0):
                        result = self.test(test_reader, feeding=feeding,
                                           pass_id=pass_id)
                        logger.info("periodic test: cost=%.6f %s",
                                    result.cost,
                                    _fmt_metrics(result.metrics))
                        event_handler(result)
                        # the eval pass must not be charged to the next
                        # wall interval
                        self._reanchor(last_final)
                    event_handler(v2_event.EndIteration(
                        pass_id, b, cost, metrics))

            for unit in units:
                # every real step of the unit announces itself before the
                # dispatch, so the reference ordering BeginIteration(b) <
                # EndForwardBackward(b) < EndIteration(b) holds for any K;
                # and before an inline unit is converted, after a
                # feeder's was taken: each source's historical order
                for i in range(unit.steps):
                    event_handler(v2_event.BeginIteration(
                        pass_id, batch_id + i))
                unit.materialize()
                if feeder is not None:
                    taken = unit.batches
                phases["wait"] += unit.stall_ms
                if not unit.stacked:
                    self._rng, step_rng = jax.random.split(self._rng)
                with observe_spans.span(
                        "train_chunk" if k else "train_step",
                        args={"steps": unit.steps, "batch": batch_id} if k
                        else {"batch": batch_id}) as dispatch:
                    if unit.stacked:
                        # the rng carry advances INSIDE the fused program
                        # through the same sequential split stream as the
                        # per-step dispatch — fixed-seed trajectories are
                        # K-invariant
                        (losses, self._trainable, self._replica,
                         self._state, self._opt_state, stats,
                         self._rng) = self._train_chunk(
                            self._trainable, self._replica, self._static,
                            self._state, self._opt_state, unit.feed,
                            self._rng)
                    else:
                        (losses, self._trainable, self._replica,
                         self._state, self._opt_state,
                         stats) = self._train_step(
                            self._trainable, self._replica, self._static,
                            self._state, self._opt_state, unit.feed,
                            step_rng)
                phases["dispatch"] += dispatch.dur * 1e3
                for fb in unit.batches:
                    self._observe_tokens(m_tokens, fb.tokens, fb.positions)
                base_step = self._step_count
                self._step_count += unit.steps
                # unit boundary == step boundary: the first one at or
                # past the cadence commits the snapshot
                self._checkpoint_maybe(ckpt, pass_id, batch_id + unit.steps)
                if slog is not None:
                    for i, fb in enumerate(taken):
                        slog.log_feed(
                            step=base_step + i + 1, stall_ms=fb.stall_ms,
                            convert_ms=fb.convert_ms,
                            examples=fb.examples, depth=feeder.depth,
                            bucket=fb.bucket, fill_tokens=fb.fill_tokens,
                            pad_tokens=fb.pad_tokens)
                if pending is not None:
                    finalize(pending)
                pending = (batch_id, base_step, losses, stats, unit)
                batch_id += unit.steps
            taken = ()
            if pending is not None:
                finalize(pending)
            if pass_id == num_passes - 1:
                leaving()
            self._finish_pass(pass_id, eval_acc, event_handler, feeding,
                              sync_params, test_reader, test_period, slog,
                              last_final)
        leaving()  # here where the last pass was skipped, or none ran
        if sync_params:
            self._sync_back()

    def _finish_pass(self, pass_id, eval_acc, event_handler, feeding,
                     sync_params, test_reader, test_period, slog,
                     last_final):
        """Pass-boundary sequence (per-pass test, sync-back, pass
        metrics/record, stats dump, EndPass)."""
        if test_reader is not None and not test_period:
            # flag default 0 = one test pass per training pass
            result = self.test(test_reader, feeding=feeding,
                               pass_id=pass_id)
            logger.info("pass %d test: cost=%.6f %s", pass_id,
                        result.cost, _fmt_metrics(result.metrics))
            event_handler(result)
        if sync_params:
            self._sync_back()
        pass_metrics = {e.name: e.result(eval_acc[e.name])
                        for e in self.evaluators}
        if slog is not None:
            slog.log_pass(pass_id, metrics=pass_metrics)
        if observe_steplog.stats_enabled():
            # reference per-pass timer dump: globalStat.printAllStatus
            # + reset at FinishTrainPass (paddle/trainer/Trainer.cpp)
            global_stats.print_all()
            global_stats.reset()
            # and the pass's slowest steps, each with where its wall
            # interval went (docs/observability.md "Slow steps")
            logger.info("======= slowest steps of pass %d: step wall_ms "
                        "phase=ms =======", pass_id)
            for entry in self.slow_steps.slowest():
                logger.info("  step %d %.1f %s", entry["step"],
                            entry["latency_ms"],
                            " ".join("%s=%.1f" % kv
                                     for kv in entry["phases"].items()))
            self.slow_steps.reset()
        event_handler(v2_event.EndPass(pass_id, pass_metrics, gm=self))
        # pass-boundary work (the per-pass test, _sync_back, stats dump,
        # EndPass handlers — e.g. a checkpoint save) must not be charged
        # to the next pass's first step wall_ms
        self._reanchor(last_final)

    @staticmethod
    def _feed_depth(feed_pipeline):
        """Queue depth encoded in train()'s ``feed_pipeline`` argument —
        ONE interpretation with and without ``steps_per_call``: an
        explicit int is the depth; ``True`` (and off, for the pipeline
        that ``steps_per_call`` implies) means the default 2. Booleans checked
        first: ``1 == True`` in Python, so a membership/equality test
        would misread depth 1 as the bool."""
        if isinstance(feed_pipeline, bool) or not feed_pipeline:
            return 2
        return max(int(feed_pipeline), 1)

    @staticmethod
    def _resume_pass_iter(batch_iter, pass_id):
        """Peek the resumed pass's post-skip stream. A checkpoint cursor
        sitting exactly at the pass boundary (checkpoint_every divides
        the pass length) leaves NOTHING to train: every batch of the
        pass is already in the snapshot. Returns None then (the caller
        skips the pass), or an iterator equivalent to ``batch_iter``
        with the peeked item restored.

        The pass's EndPass either fired before the crash or its
        evaluator accumulator died in-memory with the process; either
        way the resumed run cannot reconstruct it — re-emitting EndPass
        here would read the EMPTY accumulator as a falsely-perfect pass
        record and re-run the per-pass test, so a crash landing in the
        narrow commit→EndPass window loses that pass's record rather
        than fabricating one."""
        first = next(batch_iter, None)
        if first is None:
            logger.info("resume: pass %d was already complete at the "
                        "checkpoint; continuing with the next pass",
                        pass_id)
            return None
        import itertools

        return itertools.chain([first], batch_iter)

    def test(self, reader, feeding=None, pass_id=0):
        """One evaluation pass; returns a TestResult event (v2 SGD.test)."""
        feeding = feeding or self.feeding
        eval_acc = {e.name: None for e in self.evaluators}
        total_cost, n_batches = 0.0, 0
        for data_batch in reader():
            with observe_spans.span("test_feed"):
                feed = convert_feed(self.topology, data_batch, feeding)
            with observe_spans.span("test_step"):
                cost, stats, _ = self._eval_step(
                    self._trainable, self._static, self._state, feed)
            with observe_spans.span("eval_readback"):
                total_cost += float(cost)
                n_batches += 1
                for e in self.evaluators:
                    eval_acc[e.name] = e.merge(eval_acc[e.name],
                                               jax.device_get(stats[e.name]))
        metrics = {e.name: e.result(eval_acc[e.name]) for e in self.evaluators}
        return v2_event.TestResult(
            pass_id, total_cost / max(n_batches, 1), metrics)

    # -- observability (Flags.cpp:71 --show_layer_stat;
    # TrainerInternal.cpp:100-110 --show_param_stats_period) ----------------
    def _log_layer_stats(self, feed):
        """Per-layer output mean/|mean|/max, the reference's per-layer
        debug line, computed from a plain forward on the current batch."""
        from paddle_tpu.layer.base import data_of

        params = {**self._expanded_trainable(), **self._static, **self._state}
        values, _ = self.topology.apply_all(params, feed, mode="test")
        for name, val in values.items():
            arr = np.asarray(jax.device_get(data_of(val)))
            if arr.dtype.kind not in "fc":
                continue
            logger.info("layer %s: avg=%.6g absavg=%.6g max=%.6g", name,
                        arr.mean(), np.abs(arr).mean(), arr.max())

    def _log_param_stats(self):
        for name, val in self._expanded_trainable().items():
            arr = np.asarray(jax.device_get(val))
            logger.info("param %s: avg_abs=%.6g max_abs=%.6g", name,
                        np.abs(arr).mean(), np.abs(arr).max())

    # -- state sync ---------------------------------------------------------
    def _materialize_device_state(self):
        """Stage host Parameters into device arrays, partitioned into
        trainable/static/running-state (single point: __prepare__ and
        checkpoint restore both go through here)."""
        t, s, st = self._split(self.parameters.as_dict())
        self._trainable = {k: jnp.asarray(v) for k, v in t.items()}
        if getattr(self, "_pool", None) is not None:
            self._trainable = self._pool.compress(self._trainable)
        self._static = {k: jnp.asarray(v) for k, v in s.items()}
        self._state = {k: jnp.asarray(v) for k, v in st.items()}
        from paddle_tpu.core import dtype as dtype_mod

        # replica only when the compute dtype actually differs from the
        # master dtype — with a float32 compute override to_compute is a
        # no-op and the "replica" would alias the donated masters (the
        # jit would then donate the same buffer at two argnums and fail)
        cd = dtype_mod.compute_dtype()
        self._replica = (_make_replica(self._trainable)
                         if cd is not None and cd != jnp.float32 else None)

    def _expanded_trainable(self):
        """Per-name view of the (possibly pooled) trainable carry."""
        if getattr(self, "_pool", None) is not None:
            return self._pool.expand(self._trainable)
        return self._trainable

    def _sync_back(self):
        """Copy device training state back into the Parameters object so
        save/inspect sees current values (v2's gm<->parameters append)."""
        read = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            (self._trainable, self._state)))
        with self._phase("sync_back", args={"bytes": read}):
            host = jax.device_get({**self._expanded_trainable(),
                                   **self._state})
            self.parameters.update_from(host)

    def save_parameter_to_tar(self, f):
        self._sync_back()
        self.parameters.to_tar(f)

    def export_inference_bundle(self, output_layer, out_dir, **export_kw):
        """Sync the trained parameters back and AOT-export the inference
        forward over ``output_layer`` as a serve bundle (docs/serving.md;
        paddle_tpu.serve.export_bundle kwargs pass through). The train →
        export → serve demo path: demos/fit_a_line/train.py."""
        from paddle_tpu.serve.export import export_bundle

        self._sync_back()
        return export_bundle(output_layer, self.parameters, out_dir,
                             **export_kw)

    # -- preemption-tolerant checkpointing (docs/distributed.md) ------------
    def _checkpoint_setup(self, directory, every, keep, sync, slog):
        """One checkpoint session per train() call. Returns the ctx dict
        the loops thread through ``_checkpoint_maybe``; ``sync=False``
        (the default) owns an AsyncCheckpointer whose writer thread this
        session must close in train()'s finally."""
        from paddle_tpu.distributed import checkpoint as ckpt

        every = int(every)
        enforce(every >= 1, "checkpoint_every must be >= 1, got %d", every)
        if getattr(self, "_ckpt_clone_jit", None) is None:
            pool = self._pool

            def clone(trainable, state, opt_state, rng):
                # fresh device buffers: the next step DONATES the live
                # carries, so the writer must never hold the originals.
                # One jitted dispatch; expansion to per-name (the
                # checkpoint wire format) rides the same program.
                full = (pool.expand(trainable) if pool is not None
                        else trainable)
                return jax.tree.map(jnp.copy,
                                    {"params": full, "state": state,
                                     "opt": opt_state, "rng": rng})

            # cached across train() calls: a fresh jit here would
            # retrace the snapshot program every call, charging each
            # resumed/repeated run a recompile on its first cadence step
            self._ckpt_clone_jit = jax.jit(clone)
        ctx = {"dir": directory, "every": every, "keep": int(keep),
               "sync": bool(sync), "slog": slog,
               "writer": (None if sync else ckpt.AsyncCheckpointer(
                   directory, keep=keep, steplog=slog)),
               "clone": self._ckpt_clone_jit,
               "next": (self._step_count // every + 1) * every}
        self._ckpt_writer = ctx["writer"]
        return ctx

    def _checkpoint_maybe(self, ctx, pass_id, cursor):
        """Step-boundary cadence check: commit a snapshot whenever the
        global step reached the next multiple of ``checkpoint_every``
        (under a fused loop the boundary is the first chunk boundary at
        or past it). ``cursor`` = batches consumed within ``pass_id``."""
        if ctx is None or self._step_count < ctx["next"]:
            return
        ctx["next"] = (self._step_count // ctx["every"] + 1) * ctx["every"]
        if ctx["sync"]:
            self._checkpoint_blocking(ctx, pass_id, cursor)
        else:
            self._checkpoint_overlapped(ctx, pass_id, cursor)

    def _checkpoint_overlapped(self, ctx, pass_id, cursor):
        """The step thread's whole share of an overlapped save: one
        jitted device-side clone + an async device→host kick, then the
        handoff to the ckpt-writer thread (serialization + fsync +
        atomic rename happen there)."""
        from paddle_tpu.distributed import checkpoint as ckpt

        t0 = time.perf_counter()
        with observe_spans.span("checkpoint_snapshot",
                                args={"step": self._step_count}):
            values = ctx["clone"](self._trainable, self._state,
                                  self._opt_state, self._rng)
            for leaf in jax.tree_util.tree_leaves(values):
                kick = getattr(leaf, "copy_to_host_async", None)
                if kick is not None:
                    kick()
        ms = (time.perf_counter() - t0) * 1e3
        observe_trainview.get_train_history().record_checkpoint(ms)
        unpool = self._pool.unpool_state if self._pool is not None else None
        ctx["writer"].submit(ckpt.CheckpointSnapshot(
            values, self.parameters.copy(), step=self._step_count,
            pass_id=pass_id, pass_cursor=cursor, unpool=unpool,
            step_thread_ms=ms))

    def _checkpoint_blocking(self, ctx, pass_id, cursor):
        """checkpoint_sync=True: the historical blocking save on the
        step thread — the A/B contrast for benchmark/exp_checkpoint.py
        (steplog: overlapped=False, step_thread_ms == duration_ms).
        The save itself is the public ``save_checkpoint`` (sync-back +
        unpool + trainer_state), so the two paths cannot diverge."""
        from paddle_tpu.distributed import checkpoint as ckpt

        t0 = time.perf_counter()
        with observe_spans.span("checkpoint_sync",
                                args={"step": self._step_count}):
            path = self.save_checkpoint(ctx["dir"], pass_id=pass_id,
                                        keep=ctx["keep"],
                                        resume_at=(pass_id, cursor))
        ms = (time.perf_counter() - t0) * 1e3
        observe_trainview.get_train_history().record_checkpoint(ms)
        if ctx["slog"] is not None:
            ctx["slog"].log_checkpoint(
                step=self._step_count, duration_ms=ms,
                nbytes=ckpt.checkpoint_bytes(path), overlapped=False,
                step_thread_ms=ms, pass_id=pass_id,
                path=os.path.basename(path))
            # timeline mirror of the commit (observe/trainview.py)
            ctx["slog"].log_elastic_event(
                "checkpoint_commit",
                worker=observe_trainview.worker_id(),
                step=self._step_count, checkpoint=os.path.basename(path))

    def _checkpoint_close(self, ctx):
        """Drain + stop the writer; re-raises a writer error so a
        checkpointing run cannot silently lose durability."""
        self._ckpt_writer = None
        if ctx["writer"] is not None:
            ctx["writer"].close()

    def _resume_restore(self, directory, mode=True):
        """Restore the newest valid checkpoint for ``train(resume=...)``.
        Returns ``(start_pass, start_cursor)``: the pass to continue and
        the batches of it already trained (skipped on the resumed
        stream). ``mode="pass"`` restarts the interrupted pass from its
        first batch — the elastic re-deal case, where the shard set
        changed and the old cursor does not map onto the new stream."""
        import os

        # resume=True on a first launch (or an elastic reform before the
        # first commit): the directory save_checkpoint would create does
        # not exist yet — train from scratch rather than letting
        # load_checkpoint treat the missing dir as one torn checkpoint
        if not os.path.isdir(directory):
            logger.info("resume: checkpoint dir %s does not exist yet; "
                        "training from scratch", directory)
            return 0, 0
        meta = self.restore_checkpoint(directory)
        if meta is None:
            logger.info("resume: no valid checkpoint under %s; training "
                        "from scratch", directory)
            return 0, 0
        ts = (meta.get("extra") or {}).get("trainer_state")
        if not ts:
            logger.warning(
                "resume: checkpoint has no trainer_state (pre-elastic "
                "format): weights/optimizer restored, but the data "
                "stream and rng restart from pass 0 — the resumed "
                "trajectory will NOT continue the original one")
            return 0, 0
        self._rng = jnp.asarray(np.asarray(ts["rng_key"], dtype=np.uint32))
        start_pass = int(ts["pass"])
        cursor = 0 if mode == "pass" else int(ts["pass_cursor"])
        logger.info(
            "resume: restored step %d (pass %d, batch cursor %d) — "
            "continuing the fixed-seed trajectory", self._step_count,
            start_pass, cursor)
        return start_pass, cursor

    # -- checkpoint/resume (pserver doCheckpoint + ParamUtil parity) --------
    def save_checkpoint(self, directory, pass_id=0, keep=3,
                        coordinator=None, resume_at=None):
        """Durable checkpoint of parameters + optimizer state. With a
        ``coordinator`` client, participates in the save election so exactly
        one worker writes (reference: RequestSaveModel).

        ``resume_at=(pass, cursor)`` embeds the trainer_state block a
        deterministic ``train(resume=True)`` needs — e.g. an EndPass
        handler saving pass ``p`` passes ``(p + 1, 0)``, the position the
        next batch would come from."""
        from paddle_tpu.distributed import checkpoint as ckpt

        if coordinator is not None and not coordinator.request_save_model():
            return None
        self._sync_back()
        # the checkpoint wire format stays per-parameter (round-1
        # compatible): pooled optimizer slots are split back by name
        opt_state = self._opt_state
        if getattr(self, "_pool", None) is not None:
            opt_state = self._pool.unpool_state(jax.device_get(opt_state))
        extra = None
        if resume_at is not None:
            extra = {"trainer_state": ckpt.trainer_state_meta(
                jax.device_get(self._rng), resume_at[0], resume_at[1],
                self._step_count)}
        return ckpt.save_checkpoint(
            directory, self.parameters, opt_state=jax.device_get(opt_state),
            step=self._step_count, pass_id=pass_id, keep=keep,
            extra_meta=extra)

    def restore_checkpoint(self, directory_or_path):
        """Resume parameters + optimizer state from the newest valid
        checkpoint; returns the meta dict (or None if nothing found)."""
        import os

        from paddle_tpu.distributed import checkpoint as ckpt

        path = directory_or_path
        if os.path.isdir(path) and not os.path.exists(
                os.path.join(path, "meta.json")):
            path = ckpt.latest_checkpoint(path)
            if path is None:
                return None
        params, opt_flat, meta = ckpt.load_checkpoint(path)
        restored, skipped = 0, []
        for name in params.names():
            if name in self.parameters:
                self.parameters.set(name, params.get(name))
                restored += 1
            else:
                skipped.append(name)
        if restored == 0:
            raise ValueError(
                "checkpoint %s shares no parameter names with this model "
                "(checkpoint has %s)" % (path, sorted(params.names())[:8]))
        if skipped:
            from paddle_tpu.utils.logger import logger

            logger.warning(
                "restore_checkpoint: %d checkpoint parameter(s) not in "
                "model, skipped: %s", len(skipped), skipped[:8])
        self._materialize_device_state()
        if opt_flat is not None:
            # per-name template (the wire format), then re-pool if pooled
            template = self.optimizer.init_state(self._expanded_trainable(),
                                                 self._param_meta)
            restored_state = ckpt.unflatten_state(template, opt_flat)
            if getattr(self, "_pool", None) is not None:
                restored_state = self._pool.pool_state(restored_state)
            self._opt_state = jax.tree_util.tree_map(
                jnp.asarray, restored_state)
        self._step_count = int(meta.get("step", 0))
        return meta


def default_event_handler(evt):
    pass


def _fmt_metrics(metrics):
    parts = []
    for key, val in metrics.items():
        if isinstance(val, float):
            parts.append("%s=%.5f" % (key, val))
    return " ".join(parts)
