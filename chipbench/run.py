"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, without a TPU (or for a cell on more
than one chip).  See ``harness.py`` for the steps of a run.
"""
import time

T_PROCESS = time.perf_counter_ns()  # set-up is timed from here

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    res = harness.main(t_process=T_PROCESS)
    print(json.dumps(res), flush=True)
