"""Readings of a data generator and a reference without the system under
test, at a configuration's full shape: what a configuration that would use
them needs before its limits and its cell can be set.

    python3 chipbench/pair.py --generator ood_ip --reference ip \
        --rows 10000000 --dim 200 --corpus-seeds 0,1,2

The data block is the generator's ``DEFAULTS`` with each corpus seed in
turn; k is 10.  For each seed it prints one JSON line: the time to make the
corpus (the first seed's compiles), the reference's and the control's time
per call of 64 queries, and over the first QUERIES queries of the pool:

* ``sound``: the checks of the reference's own answers (``exact_answers``:
  ids and distances as its scoring matmul gave them), recomputed by
  ``compare`` against the existing configurations' limits: the reading a
  sound system can reach;
* ``control``: the checks of ``control_answers``, one precision lower;
* ``l2_overlap``: the share of the exact top-k by the reference that the
  exact squared-L2 top-k holds (``references/l2.py``, blocked over rows);
* ``mahalanobis``: under the mean and covariance of the first FIT_ROWS
  rows, the median Mahalanobis distance of the queries over that of the
  last HELD_ROWS rows (held out of the fit);
* ``distinct_ids``: the share of distinct ids among all top-k answers;
* the device's peak memory.

It needs a TPU, as the benchmark does; the benchmark's own runs never run
it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
K, QUERIES = 10, 384
LIMITS = {"malformed_answers": 0, "dist_gap": 1e-4, "recall_at_10_min": 0.8}
FIT_ROWS, HELD_ROWS = 200_000, 4096


def l2_topk(x, q, k: int, row_block: int) -> np.ndarray:
    """(Q, k) ids of the exact squared-L2 top-k: ``references/l2.py`` on
    each block of rows, merged by its float32 distances."""
    from chipbench import run
    l2 = run.load("references", "l2")
    best_i = best_d = None
    for r0 in range(0, x.shape[0], row_block):
        xb = x[r0:r0 + row_block]
        ids = l2.exact_topk(xb, q, min(k, xb.shape[0]))
        d = l2.exact_dist(xb, q, ids)
        ids = ids + r0
        if best_i is not None:
            ids = np.concatenate([best_i, ids], axis=1)
            d = np.concatenate([best_d, d], axis=1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        best_i = np.take_along_axis(ids, order, axis=1)
        best_d = np.take_along_axis(d, order, axis=1)
    return best_i


def mahalanobis_ratio(fit: np.ndarray, held: np.ndarray,
                      q: np.ndarray) -> dict:
    """Median Mahalanobis distance of ``q`` and of ``held`` rows under the
    mean and covariance of ``fit`` rows, in float64, and their ratio."""
    fit = np.asarray(fit, np.float64)
    mu = fit.mean(axis=0)
    w, v = np.linalg.eigh(np.cov(fit, rowvar=False))
    proj = v / np.sqrt(w)

    def median(a):
        return float(np.median(np.linalg.norm(
            (np.asarray(a, np.float64) - mu) @ proj, axis=1)))

    rows, queries = median(held), median(q)
    return {"rows": rows, "queries": queries, "ratio": queries / rows}


def _timed(fn, *args, **kw):
    t = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t


def readings(gen, ref, cfg: dict) -> dict:
    """One corpus's readings (see the module's docstring)."""
    import jax
    from chipbench import common
    k, spec = K, gen.Spec(cfg)
    x, corpus_s = _timed(lambda: jax.block_until_ready(gen.corpus(spec)))
    q = np.asarray(gen.queries(spec, np.arange(QUERIES)))
    blocks = [q[i:i + common.QUERY_BLOCK]
              for i in range(0, QUERIES, common.QUERY_BLOCK)]
    ref.exact_answers(x, blocks[0], k)                 # compiles
    ref_s = [_timed(ref.exact_answers, x, b, k)[1] for b in blocks]
    ref.control_answers(x, blocks[0], k)
    ctl_s = [_timed(ref.control_answers, x, b, k)[1] for b in blocks]

    ids, dists = ref.exact_answers(x, q, k)
    sound = ref.compare(list(zip(ids, dists)), x, q, rows=spec.rows, k=k,
                        limits=LIMITS)
    control = ref.compare(list(zip(*ref.control_answers(x, q, k))), x, q,
                          rows=spec.rows, k=k, limits=LIMITS)
    out = {
        "rows": spec.rows, "dim": spec.dim, "queries": QUERIES, "k": k,
        "data": cfg["data"], "corpus_s": corpus_s,
        "reference_s_per_64": statistics.median(ref_s),
        "control_s_per_64": statistics.median(ctl_s),
        "sound": sound["checks"], "sound_correct": sound["correct"],
        "control": control["checks"], "control_correct": control["correct"],
        "l2_overlap": common.recall(l2_topk(x, q, k, ref.ROW_BLOCK), ids,
                                    k),
        "distinct_ids": len(set(ids.ravel().tolist())) / ids.size,
        "mahalanobis": mahalanobis_ratio(
            np.asarray(x[:FIT_ROWS]), np.asarray(x[spec.rows - HELD_ROWS:]),
            q),
    }
    del x
    return out


def main(argv=None) -> int:
    sys.path.insert(0, str(ROOT))
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--generator", required=True)
    ap.add_argument("--reference", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--dim", type=int, required=True)
    ap.add_argument("--corpus-seeds", default="0")
    args = ap.parse_args(argv)
    run.configure()
    import jax
    run.device_info(jax, 1)
    gen = run.load("generators", args.generator)
    ref = run.load("references", args.reference)
    for seed in [int(s) for s in args.corpus_seeds.split(",")]:
        cfg = {"rows": args.rows, "dim": args.dim,
               "data": dict(gen.DEFAULTS, corpus_seed=seed)}
        out = readings(gen, ref, cfg)
        out["peak_bytes_in_use"] = run.memory(jax, 1, "peak_bytes_in_use")
        out["device"] = jax.devices()[0].device_kind
        print(json.dumps({"generator": args.generator,
                          "reference": args.reference, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
