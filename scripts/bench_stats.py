"""Run one benchmark cell (``benchmark/run.py``, same arguments) and,
before the cluster drains, print what the result line does not carry:
each tier's GET /stats ``tick`` and ``prefill`` blocks (the resident
share of PR 36, the riding chunks of PR 32).

    python3 scripts/bench_stats.py --workload smollm2-1.7b.decode-closed \
        --seed 7 --seconds 50 --trace 0

From the root of a checkout, on the chip.  Nothing of ``benchmark/`` is
edited: ``cluster.Served.drain`` is wrapped in this process only.
"""

import json
import os
import runpy
import sys

ROOT = os.getcwd()
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import cluster                                   # noqa: E402

_drain = cluster.Served.drain


def _drain_after_stats(self) -> None:
    try:
        tiers = self.get_json("/stats").get("tiers", {})
        for name in self.entries:
            block = tiers.get(name, {})
            for key in ("tick", "prefill"):
                print(f"[bench:stats] tiers.{name}.{key} = "
                      f"{json.dumps(block.get(key))}", flush=True)
    finally:
        _drain(self)


cluster.Served.drain = _drain_after_stats
sys.argv = [os.path.join("benchmark", "run.py")] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
