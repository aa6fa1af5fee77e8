"""Record the small device trace that ``test_xtrace.py`` reduces.

Run on a TPU from the root of a checkout:

    python3 chipbench/tests/record_trace.py <out_dir>

It runs the tiny cell of ``tiny.py`` traced for half a second and copies the
profiler's ``.xplane.pb`` to ``<out_dir>/tiny.xplane.pb``, with what the
window did (``<out_dir>/tiny.json``: its micro-batches' counters and the
per-layer readings at recording time).
"""

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run, xtrace
    from chipbench.tests import tiny
    p = tiny.parts()
    res = run.run_cell(p, tiny.SEED, 0.5, True, t_start=time.perf_counter())
    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(xtrace.find(str(run.CACHE / "trace")),
                out_dir / "tiny.xplane.pb")
    (out_dir / "tiny.json").write_text(json.dumps(
        {"metrics": res["metrics"], "device": res["device"],
         "batches": list(res["window"].batches.values()),
         "correct": res["correct"]},
        indent=1))
    print(json.dumps(res["metrics"]))


if __name__ == "__main__":
    main(sys.argv[1])
