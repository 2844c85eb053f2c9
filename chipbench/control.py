"""Readings of the output check, of its control and of planted faults, over
many seeds.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--fault <fault>]

For each seed: one run of the cell as ``run.py`` makes it (set-up, the
window, the comparison with the float32 reference), and then the control:
the same reference computed with float8 operands, at the same positions of
the same prompts and served tokens, read as the gap of the token the float8
reference puts first, and judged by the cell's own verdict. With
``--fault`` the run's timed path has that fault (``chipbench/faults.py``)
and there is no control. One JSON line per seed on standard output. The
benchmark's own runs never run this; the limits in ``cells/<cell>.json``
are set from it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402

from chipbench import drive, faults, run, spec  # noqa: E402
from chipbench.check import NUMBERS  # noqa: E402


def summary(r, control: bool) -> dict:
    """The numbers a limit can be set from, for the served path and (with
    ``control``) the control: every number of ``check.NUMBERS``, with the
    share of compared tokens whose gap is above 0 and how many distinct
    tokens were served."""
    out = {"tokens": r.tokens, "distinct": r.distinct,
           "requests": r.requests, "seconds": r.seconds}
    for side, c in (("served", False), ("control", True))[:1 + control]:
        g = np.concatenate(list((r.control_gaps if c else r.gaps).values()))
        out[side] = {name: r.number(name, c) for name in NUMBERS}
        out[side]["share_above_0"] = float(np.mean(g > 0))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS + faults.MESH_FAULTS)
    args = ap.parse_args(argv)
    control = args.fault is None
    if not control:
        vocab = spec.load_cell(REPO, args.workload).config["vocab_size"]
        drive.build_engine = faults.broken(drive.build_engine, args.fault,
                                           vocab)
    for i, seed in enumerate(args.seeds):
        # the first seed's set-up runs from the process's start, as a
        # benchmark run's does; the later ones from their own start
        res = run.run_cell(REPO, args.workload, seed, args.seconds, False,
                           t_start=run.T_START if i == 0
                           else time.perf_counter(), control=control,
                           keep_readings=True)
        readings = res.pop("readings")
        line = dict(seed=seed, fault=args.fault, correct=res["correct"],
                    check=res["check"], metrics=res["metrics"],
                    **summary(readings, control))
        if control:
            line["control_verdict"] = res["control"]
        print(json.dumps(line), flush=True)
        del res, readings
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
