#!/usr/bin/env python3
"""``bench/tools/control.py`` for the MoE training cell: the same runs and
output, with the faults and control of ``bench/faults_moe.py`` planted by
name.

    python3 bench/tools/control_moe.py --workload delta2-kanana2-30b-a3b-8k \
        --seeds 1,2,3 --faults none,control_fp8 [--seconds 1]
"""

from __future__ import annotations

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

if __name__ == "__main__":
    from bench import faults, faults_moe
    faults.BY_DRIVER.setdefault("delta_train_moe", faults_moe.MOE)
    runpy.run_path(os.path.join(HERE, "control.py"), run_name="__main__")
