#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--seconds 2] [--out readings.jsonl]

Each seed is one ``run_cell`` of ``bench/run.py`` in this process, with a
short window at the cell's own load, and prints one JSON line:

``program``   every number ``check`` reads, for the program (those the
              limits leave out too), and whether the run came out
              ``correct``.
``variants``  (control seeds) the same judgement with one part put in
              the program's place: the training steps in bfloat16
              (``control_step``), the fold on bfloat16 inputs
              (``control_fold``), the codec's restore and quantize at int4
              (``control_codec``), the training steps over the first half
              of each batch (``half_batch``, a planted fault). Each has to
              come out not correct.

The benchmark's own runs never run this. It runs on the accelerator the
process holds, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cellbase  # noqa: E402
import run  # noqa: E402


def readings(workload: str, seed: int, seconds: float, control: bool,
             **kw):
    rec = {}
    r = run.run_cell(workload, seed, seconds, False,
                     variants=cellbase.VARIANTS if control else (),
                     record=rec, **kw)
    out = {"workload": workload, "seed": seed,
           "program": {"correct": r["correct"], **rec["numbers"]}}
    if control:
        out["variants"] = {
            name: {"correct": r["variants"][name]["correct"], **nums}
            for name, nums in rec["variants"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    sink = open(args.out, "a") if args.out else None
    for seed in seeds + ctl:
        line = json.dumps(readings(args.workload, seed, args.seconds,
                                   seed in ctl))
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
