"""How often the program's router and the plain reference's choose
another expert: `python chipbench/routing_agreement.py --workload <cell>
--seed <n>`.

bfloat16 rounding moves a token's last choice to the next expert where
the two scores nearly tie, so some (token, choice) pairs of a run differ
from the float32 reference's, and the experts' leaves read further off
than a dense leaf does (`PERF.md` section 6). This prints, for one seed's
first batch and uncompared, the share of the valid tokens' pairs whose
expert the reference did not choose, layer by layer and in all, and the
same share among the pairs on the experts held here. The program's side
is what its own `ops/moe.py route` calls return at the cell's precision
(`hybrid_lm.from_config` without recomputed blocks, so that the choices
are values of one trace); the reference's is
`reference/<config>.py choices_of`, which reads nothing of the program.
Nothing here decides `correct`, and the benchmark's runs never run it.
"""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def shares(program, reference, valid, first, held):
    """{"all": share of the valid tokens' pairs whose expert the
    reference did not choose, "here": the same among the program's pairs
    on experts first .. first + held - 1}; choices [B, T, k] each."""
    import numpy as np

    program, reference = np.asarray(program), np.asarray(reference)
    agreed = (program[..., :, None] == reference[..., None, :]).any(-1)
    counted = np.broadcast_to(np.asarray(valid)[..., None], agreed.shape)
    here = counted & (program >= first) & (program < first + held)
    return {"all": float(1.0 - agreed[counted].mean()),
            "here": float(1.0 - agreed[here].mean()) if here.any() else 0.0}


def read_seed(cell, cfg, seed, rehearsal=False):
    """{"layers": {layer: shares}, "all", "here"} for the seed's first
    batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from chipbench import traffic
    from paddle_tpu import layer as L
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.ops import moe as moe_ops
    from paddle_tpu.topology import Topology, convert_feed

    model = importlib.import_module("chipbench.models." + cfg["model"])
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    precision = cfg["precision"]
    paddle.init(use_tpu=not rehearsal, seed=int(seed) % (2 ** 31),
                compute_dtype=precision["compute_dtype"],
                matmul_precision=precision["matmul_precision"])
    batch = traffic.make_pool(cfg["inputs"], cell, seed)[0]
    weights, state = ref.init_weights(seed, cfg)
    names = model.program_names(cfg)
    L.reset_name_counters()
    cost = hybrid_lm.from_config(cfg, recompute=False,
                                 prefix=model.PREFIX)[3]
    topo = Topology(cost)
    sparse = [i for i, (_, is_sparse) in enumerate(ref.layers_of(cfg))
              if is_sparse]
    _, held, first = ref.experts_of(cfg)

    @jax.jit
    def programs_choices(params, feed):
        """The choices the program's own `route` calls make, layer by
        layer: without recomputed blocks they are values of this trace."""
        seen, route = [], moe_ops.route

        def recording(*a, **kw):
            chosen, weights = route(*a, **kw)
            seen.append(chosen)
            return chosen, weights

        moe_ops.route = recording
        try:
            topo.apply(params, feed, mode="train")
        finally:
            moe_ops.route = route
        return dict(zip(sparse, seen))

    params = {names[k]: v for k, v in {**weights, **state}.items()}
    mine = jax.device_get(programs_choices(params,
                                           convert_feed(topo, batch)))
    del params
    tokens, _, lengths = ref.batch_arrays(batch, cfg)
    # the feed may pad a row past the batch's longest
    mine = {i: v.reshape(tokens.shape[0], -1, v.shape[-1])[:, :tokens.shape[1]]
            for i, v in mine.items()}
    with jax.default_matmul_precision("highest"):
        theirs = jax.device_get(jax.jit(
            lambda w, s, t: ref.choices_of(w, s, t, cfg))(
                weights, state, jnp.asarray(tokens)))
    valid = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
    layers = {i: shares(mine[i], theirs[i], valid, first, held)
              for i in sparse}
    every = shares(np.stack([mine[i] for i in sparse]),
                   np.stack([theirs[i] for i in sparse]),
                   np.broadcast_to(valid, (len(sparse),) + valid.shape),
                   first, held)
    return {"layers": {str(i): v for i, v in layers.items()}, **every}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from chipbench import run as run_mod

    rehearsal = args.rehearse is not None
    cell, cfg, _ = run_mod.load_cell(
        args.workload, os.path.abspath(args.rehearse) if rehearsal else HERE)
    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        raise SystemExit("chipbench: the routing of %s is read on a TPU, "
                         "jax.devices() is %r" % (cell["name"], devices))
    out = read_seed(cell, cfg, args.seed, rehearsal)
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "platform": devices[0].platform,
                      **({"rehearsal": True} if rehearsal else {}),
                      "pairs_chosen_otherwise": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
