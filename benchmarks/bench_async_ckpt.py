"""Secondary benchmark: async-checkpoint step-time overhead %.

Driver metric #2 (BASELINE.json), target <5%.  Thin wrapper over the
paired-stall measurement in the repo-root ``bench.py`` (which emits this
number alongside the detection metric in the driver-captured line): the
per-save costs (snapshot-dispatch call + post-save drain stall) are measured
against ADJACENT baseline step groups — robust to slow throughput drift
over a run — then amortized over a save cadence sized to the measured D2H
bandwidth.

Prints ONE JSON line: {"metric": "async_ckpt_step_overhead_pct", ...}.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from bench import bench_async_ckpt

    overhead_pct, d2h_mbps, state_bytes, save_every = bench_async_ckpt()
    print(
        json.dumps(
            {
                "metric": "async_ckpt_step_overhead_pct",
                "value": round(overhead_pct, 3),
                "unit": "%",
                "vs_baseline": round(overhead_pct / 5.0, 3),
                "d2h_mbps": round(d2h_mbps, 1),
                "state_mb": round(state_bytes / 1e6, 1),
                "save_every": save_every,
            }
        )
    )


if __name__ == "__main__":
    main()
