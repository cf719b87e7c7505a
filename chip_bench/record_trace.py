#!/usr/bin/env python3
"""Record the small device trace the trace reduction is tested on.

    python3 chip_bench/record_trace.py [--out chip_bench/testdata]

On a TPU: a 4x4 grid at the paper's widths (3.2 M synapses), compiled and
warmed up, then two one-step chunks traced with the benchmark's own spans
(`window` around both, `chunk` around each).  Writes trace_4x4.xplane.pb
under --out and prints what the trace holds (planes, lines, the first
events of each line with their stats), for reading by hand.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def describe(path: str, events_per_line: int = 12) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:events_per_line]:
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {e.name[:100]!r} start {e.start_ns} dur "
                      f"{e.duration_ns} {stats}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata"))
    args = ap.parse_args()

    import jax

    from repro import compile_cache
    from repro.core.params import EngineConfig, GridConfig
    from repro.core.step_program import StepProgram

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: nothing recorded")
    compile_cache.enable()
    sp = StepProgram(GridConfig(grid_x=4, grid_y=4), EngineConfig())
    state = sp.place(sp.init_state())
    state, _, _ = jax.block_until_ready(sp.run(state, 0, 1))
    state, _, _ = jax.block_until_ready(sp.run(state, 1, 1))
    tmp = tempfile.mkdtemp(prefix="chip_bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            for t in (2, 3):
                with jax.profiler.TraceAnnotation("chunk"):
                    state, _, _ = jax.block_until_ready(sp.run(state, t, 1))
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        os.makedirs(args.out, exist_ok=True)
        dst = os.path.join(args.out, "trace_4x4.xplane.pb")
        shutil.copy(path, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dst}: {os.path.getsize(dst)} bytes")
    describe(dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
