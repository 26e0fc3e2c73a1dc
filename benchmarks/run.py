"""python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once on the machine it is started on and
prints the result as the last line of standard output. Exits non-zero,
with no result, when JAX finds no TPU that peaks.json knows or fewer
chips than the cell asks for.
"""

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402


def _process_age_s() -> float:
    """Seconds since this process started, from /proc (10 ms ticks);
    0 where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None) -> int:
    t_start = _T_IMPORT - _process_age_s()
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import logging
    logging.getLogger("bigdl_tpu").setLevel(logging.WARNING)
    from benchmarks.harness import run_cell
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
