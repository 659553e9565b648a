"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload yi34b.chat --seed 7 --seconds 51 --trace 0

Loads the cell named in ``BENCHMARK.json``, makes weights and traffic from
``--seed``, warms every shape, measures for ``--seconds``, checks what was
served against the plain float32 reference, and prints as the last line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number compared beside its limit
(also the last lines of standard error).  Exits non-zero, with no result
line, without enough TPU chips.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    import harness
    try:
        cell = harness.load_cell(harness.ROOT, a.workload)
        device = harness.require_chip(cell.chips)
        import repro.serving  # noqa: F401  (the program under test)
    except (harness.BenchError, OSError, ImportError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(f"bench: {a.workload} seed {a.seed} on {device['kind']} "
          f"x{device['count']}; compile cache "
          f"{harness.enable_compile_cache()}", file=sys.stderr, flush=True)
    out = harness.run_cell(cell, a.seed, a.seconds, bool(a.trace), T_START,
                           device, log=lambda m: print(
                               f"bench: {m}", file=sys.stderr, flush=True))
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
