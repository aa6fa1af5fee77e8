"""The control: the reference one precision lower, in the system's place.

``correct`` must call it not correct.  It is the exact brute force of the
configuration's reference (``control_answers``) computed in bfloat16 (the
precision below the float32 that the configurations state), answering the
same requests through the same window, loop and comparison as a run of
the cell.  Readings on the chip, at a cell's own size:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

prints one JSON line per seed with the numbers compared; the benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


class ControlEngine:
    """Answers ``serve`` calls with a reference's bfloat16 brute force."""

    def __init__(self, x, k: int, reference):
        self.x, self.k, self.reference = x, k, reference
        self.stats = types.SimpleNamespace(requests=0)

    def serve(self, queries):
        import jax.numpy as jnp
        ids, dists = self.reference.control_answers(
            self.x, jnp.asarray(queries), self.k)
        base = self.stats.requests
        self.stats.requests += len(queries)
        return [types.SimpleNamespace(rid=base + i, ids=ids[i],
                                      distances=dists[i], cost=None,
                                      batch=None)
                for i in range(len(queries))]


def system(parts: dict, seed: int):
    """``run.run_cell``'s ``system``: the corpus and the control engine."""
    data = parts["generator"]
    spec = data.Spec(parts["config"])
    return (ControlEngine(data.corpus(spec),
                          parts["config"]["pipeline"]["final_k"],
                          parts["reference"]), spec)


def main(argv=None) -> int:
    sys.path.insert(0, str(ROOT))
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    run.configure()
    import jax
    parts = run.cell_parts(run.manifest(), args.workload)
    run.device_info(jax, parts["cell"]["chips"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(parts, seed, args.seconds, False,
                           t_start=time.perf_counter(), system=system)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]},
                         default=lambda o: o.item()
                         if isinstance(o, np.generic) else str(o)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
