"""Readings a cell's correctness limit is set from, and the proof that
the control and the planted faults fail it (run on the chip when the
cell is defined or its limit is reviewed; the benchmark's own runs never
run the control or a fault).

  python3 chipbench/limits.py --workload xlstm-350m.decode-batch \\
      --seeds 11,12,13 --control-seeds 11,12,13 \\
      --faults token,stale --fault-seeds 14 --seconds 51

One process.  For each seed: weights and traffic from the seed, the
cell's window at its own load, the same sample of served requests a run
compares, and the widest logit gap of the served tokens against the
float32 reference (the program's reading).  For the control seeds also
the widest gap of the token the float8 control puts first at the same
positions.  For each fault (``chipbench.faults``) and fault seed, a
window of the program with the fault planted, read as a run reads it.
Every reading is judged against the cell's limit as a run judges it.
One JSON line per reading and a summary: the lower reading is the
largest program reading, the upper the smallest control reading.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def _summary(who, per_request):
    """Widest gap (the number compared), and beside it the mean gap and
    the share of positions whose token is not the reference's first."""
    import numpy as np
    if not per_request:
        return {}
    allg = np.concatenate(per_request)
    return {f"{who}_gap": float(allg.max()),
            f"{who}_mean_gap": float(allg.mean()),
            f"{who}_flip_share": float((allg > 0).mean())}


def _seeds(text):
    return [int(x) for x in text.split(",") if x]


def reading(cell, seed, seconds, counter, *, fault=None, control=False):
    """One window on the program (with ``fault`` planted), then the
    reference over its sample; the control too where asked."""
    from chipbench import check, faults, harness
    family = cell.config["model"]["family"]
    ctx = (faults.planted(fault, family) if fault
           else contextlib.nullcontext())
    with ctx:
        s = harness.setup(cell, seed, counter)
        v = harness.serve(cell, s, seed=seed, seconds=seconds,
                          trace=False, t_process=time.perf_counter())
    picked = check.sample(v.reqs, seed, cell.cell["sample_requests"])
    n_tok = sum(len(r.tokens) for r in picked)
    # the window's end-to-end numbers (set-up is shared here, so not
    # comparable with a run's, and left out)
    row = {"seed": seed, "fault": fault, "requests": len(picked),
           "tokens": n_tok,
           "metrics": {m["name"]: harness.read_metric(m["name"], v)
                       for m in cell.e2e if m["name"] != "setup_s"}}
    params = s.params
    del s, v
    gc.collect()
    ref = check.Reference(cell.config["reference"], cell.config["model"],
                          params)
    pos = check.position_gaps(ref, picked)
    row.update(_summary("program", pos))
    row["program_correct"] = check.judge(
        [float(g.max()) for g in pos], n_tok, cell.cell)[0]
    if control:
        ctl = check.Reference(cell.config["reference"],
                              cell.config["model"], params, control=True)
        cpos = check.control_position_gaps(ref, ctl, picked)
        row.update(_summary("control", cpos))
        row["control_correct"] = check.judge(
            [float(g.max()) for g in cpos], n_tok, cell.cell)[0]
        del ctl
    del ref, params
    gc.collect()
    print(json.dumps(row), flush=True)
    return row


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--faults", default="")
    p.add_argument("--fault-seeds", default="")
    p.add_argument("--seconds", type=float, default=51.0)
    args = p.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    counter = harness.CompileCounter()
    ctl_seeds = set(_seeds(args.control_seeds))
    program, control = [], []
    for seed in sorted(set(_seeds(args.seeds)) | ctl_seeds):
        row = reading(cell, seed, args.seconds, counter,
                      control=seed in ctl_seeds)
        if "program_gap" in row:
            program.append(row["program_gap"])
        if "control_gap" in row:
            control.append(row["control_gap"])
    faulted = [reading(cell, seed, args.seconds, counter, fault=f)
               for f in args.faults.split(",") if f
               for seed in _seeds(args.fault_seeds)]
    print(json.dumps({
        "workload": cell.name, "limit": cell.cell["logit_gap"],
        "lower": max(program, default=None),
        "upper": min(control, default=None),
        "program": program, "control": control,
        "faults": [[r["fault"], r["seed"], r.get("program_gap"),
                    r["program_correct"]] for r in faulted]}), flush=True)


if __name__ == "__main__":
    main()
