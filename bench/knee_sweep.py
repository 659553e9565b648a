"""Find the knee of a rate cell once, on the chip: serve the cell's traffic
at each offered rate in turn (one engine, cancelled back to empty between
rates) and print, per rate, what came out and how far the queue fell
behind.  The knee is the highest rate whose queue wait stays flat through
the window; the cell's mix file then fixes 0.8 of it as a number.

    python bench/knee_sweep.py --workload yi34b.chat --seconds 45 \
        --rates 0.2 0.4 0.6 0.8 --seed 5
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def summarize(run, rate):
    from stats import percentile
    close = run.window_s
    recs = run.recs
    waits = [((r.req.t_admit - r.req.arrival_time) if r.req.t_admit >= 0
              else close - r.planned.due_s) for r in recs]
    half = len(recs) // 2
    ttft = [((r.tokens[0] if r.tokens else close) - r.planned.due_s)
            for r in recs]
    itl = [b - a for r in recs for a, b in zip(r.tokens, r.tokens[1:])]
    return {
        "rate": rate, "submitted": len(recs),
        "done": sum(1 for r in recs if r.req.outcome == "done"),
        "queued_at_close": sum(1 for r in recs if r.req.t_admit < 0),
        "wait_first_half_p50_s": percentile(waits[:half], 50),
        "wait_second_half_p50_s": percentile(waits[half:], 50),
        "ttft_p50_s": percentile(ttft, 50), "ttft_p90_s": percentile(ttft, 90),
        "itl_p50_ms": (percentile(itl, 50) or 0) * 1e3,
        "itl_p95_ms": (percentile(itl, 95) or 0) * 1e3,
        "out_tok_per_s": sum(len(r.tokens) for r in recs) / close,
        "steps": len(run.step_walls),
        "step_p50_ms": (percentile(run.step_walls, 50) or 0) * 1e3,
        "step_max_ms": max(run.step_walls, default=0) * 1e3,
        "step_max_cpu_ms": (run.step_cpus[run.step_walls.index(
            max(run.step_walls))] * 1e3 if run.step_walls else 0),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    a = ap.parse_args()
    import harness
    import traffic
    cell = harness.load_cell(harness.ROOT, a.workload)
    harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    cfg, engine, _ = harness.setup(cell, a.seed, a.seconds)
    print(f"setup {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    for rate in a.rates:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        plan = traffic.generate(mix, a.seed, a.seconds, cfg.vocab_size)
        run = harness.RunData(cell, cfg, {})
        harness.drive(engine, plan, a.seconds, run, rid_prefix=f"{rate}-")
        print(json.dumps(summarize(run, rate)), flush=True)
        for r in run.recs:           # back to an empty engine
            if not r.req.outcome:
                r.req.cancel()
        while engine.step():
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
