"""Readings that set a cell's correctness limit, on the chip at the cell's
own size: for each seed, serve the cell's traffic for a short window, then
read the widest logit gap of the served tokens against the float32
reference (the program's reading) and of the tokens that the float8 control
puts first (the control's reading).  The benchmark's own runs do not run
this.

    python bench/control.py --workload yi34b.chat --seconds 30 \
        --seeds 11 12 13 ... --out control-yi34b.chat.json
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def control_gaps(model, cfg, seed, picked):
    """The numbers compared, read for the tokens that the model's float8
    reference puts first at the positions the program served (their gaps
    in the float32 reference)."""
    import numpy as np
    from harness import gap_numbers, served
    from reference import logit_gaps
    seqs, rows, _ = served(picked)
    ref = model.served_logits(seed, cfg, seqs, rows)
    low = model.served_logits(seed, cfg, seqs, rows, precision="fp8")
    gaps = [logit_gaps(a, b.argmax(-1)) for a, b in zip(ref, low)]
    return gap_numbers(np.concatenate(gaps))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    import harness
    cell = harness.load_cell(harness.ROOT, a.workload)
    device = harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    rows = []
    for seed in a.seeds:
        t0 = time.perf_counter()
        cfg, engine, plan = harness.setup(cell, seed, a.seconds)
        run = harness.RunData(cell, cfg, {})
        harness.drive(engine, plan, a.seconds, run)
        picked = harness.sample(run, seed)
        del engine
        gc.collect()
        row = {"seed": seed,
               "program": harness.compare(cell.model, cfg, seed, picked),
               "control": control_gaps(cell.model, cfg, seed, picked)}
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    out = {"workload": a.workload, "device": device, "rows": rows}
    for name in ("max_logit_gap", "mean_logit_gap"):
        out[name] = {"lower": max(r["program"][name] for r in rows),
                     "upper": min(r["control"][name] for r in rows)}
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
