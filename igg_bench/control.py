"""The control of the check: the plain reference put in the program's place
and computed one precision lower (bfloat16 for the configurations' float32).

    python3 igg_bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

Each seed is one run of the cell through `harness.run`, with `place` as its
``wrap_step``: the window drives the reference in bfloat16 instead of the
program, from the harness's own seeded state, and the harness's own check
decides `correct`.  A sound limit makes every such run come out not correct.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place(cell, dtype):
    """A ``wrap_step`` for `harness.run`: one call of the reference in
    ``dtype`` in the program's place.  It takes the block-layout state to
    the global de-duplicated grid, advances it, and lays it out again."""
    import jax
    import jax.numpy as jnp

    from igg_bench import fields

    g = cell.grid

    def body(*state):
        maps = [fields.layout_maps(a.shape, g.dims, g.overlaps, g.periodic,
                                   g.local_shape) for a in state]
        glob = []
        for a, (to_global, _) in zip(state, maps):
            for d in range(3):
                a = jnp.take(a, jnp.asarray(to_global[d]), axis=d)
            glob.append(a)
        out = cell.solver.reference(glob, g, cell.cfg, cell.traffic, dtype)
        res = []
        for a, o, (_, to_block) in zip(state, out, maps):
            for d in range(3):
                o = jnp.take(o, jnp.asarray(to_block[d]), axis=d)
            res.append(o.astype(a.dtype))
        return tuple(res)

    class InPlace:
        """Compiled on first use for the state's own shardings, so every
        call keeps the program's layout."""

        def __init__(self):
            self.fn = None

        def _fn(self, state):
            if self.fn is None:
                self.fn = jax.jit(body, out_shardings=tuple(a.sharding for a in state))
            return self.fn

        def lower(self, *state):
            return self._fn(state).lower(*state)

        def __call__(self, *state):
            return self._fn(state)(*state)

    return lambda step: InPlace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from igg_bench import harness, run

    run.use_checkout_cache()
    import jax

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.load_cell(ROOT, spec, args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        line = harness.run(ROOT, spec, args.workload, seed, args.seconds, False,
                           jax.devices(), t0=time.perf_counter(),
                           wrap_step=place(cell, jax.numpy.bfloat16))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": line["correct"], "checks": line["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
