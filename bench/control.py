#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload glm4-9b.decode --seconds 30 \\
        --seeds 11,12,13

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, the measured window at the cell's own load, the check), then the
control on the same served requests: the float32 reference computed with
every matmul's operands rounded to float8_e4m3fn, the precision below the
configuration's bfloat16 (``model.control_logits``).  The control's
reading (the reference's gap of the token the fp8 forward puts first) goes
through the same comparison with the cell's limit
(``checks/<workload>.json``) as the program's.  Prints one JSON line per
seed with both readings and both verdicts; exits 1 if the control reads
correct on any seed, since the limit then does not separate the two.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    jax = run.prepare()
    if jax is None:
        return 2
    import harness
    bench = harness.load_benchmark()
    if not run.chips_ok(jax, harness.find_cell(bench, args.workload)):
        return 3
    control_passed = []
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.time()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_process=t0, bench=bench, control=True)
        ctrl = out["control"]
        print(json.dumps({
            "seed": seed, "limit": out["check"]["max_logit_gap"]["limit"],
            "program": out["check"]["max_logit_gap"]["value"],
            "program_correct": out["correct"],
            "control": ctrl["max_logit_gap"]["value"],
            "control_correct": ctrl["correct"],
            "tokens": out["check"]["tokens_compared"]["value"],
            "metrics": out["metrics"]}), flush=True)
        if ctrl["correct"]:
            control_passed.append(seed)
    if control_passed:
        print(f"[control] FAILED: the control reads correct on seeds "
              f"{control_passed}", file=sys.stderr, flush=True)
        return 1
    print("[control] the control reads not correct on every seed",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
