#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Measures one cell of ``BENCHMARK.json`` on the chip this process is given
and prints one JSON object as the last line of stdout.  Exits non-zero,
printing no result line, when JAX finds no TPU, fewer chips than the cell
asks for, or a chip that ``benchmark/peaks.json`` does not know.
``--rehearse`` runs the same control flow on the CPU at tiny sizes and
prints a line marked ``"rehearsal": true`` instead: no device metric ever
comes from it.
"""
import time

_T_PROCESS = time.perf_counter()        # setup_s counts from process start

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness.runner import main

    sys.exit(main(t_process=_T_PROCESS))
