"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic and
metrics are looked up by name from ``BENCHMARK.json``. The last line of
standard output is the result object; the numbers that decide ``correct``
are also the last lines of standard error, each beside its limit. With no
TPU, or fewer chips than the cell asks for, the command prints no result
and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)

    import jax

    from chipbench import harness
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # keep every program, small ones too, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(a.workload)
    try:
        harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, a.seed, a.seconds, bool(a.trace), T_START)
    for name, c in out["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['floor']}")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
