#!/usr/bin/env python3
"""Record the 4x4 trace the scope join is tested on, with its program's text.

    python3 chip_bench/record_scoped_trace.py [--out chip_bench/testdata] \
        [--shards 4]

On a TPU, as record_trace.py: a 4x4 grid at the paper's widths (3.2 M
synapses), compiled and warmed up, then two one-step chunks traced with
the benchmark's own spans (`window` around both, `chunk` around each),
through `StepProgram.run`, which adds its own `run_call` span.  Writes
trace_4x4_scoped.xplane.pb and, beside it, trace_4x4_scoped.hlo.txt: the
compiled text of the run program the trace ran (`lower_run(...).compile()
.as_text()`), whose op metadata carry the program's scopes (scopes.py).
Prints what the trace holds, as record_trace.py does.

With --shards 4 the same grid runs as 4 block shards on a `cells` mesh of
the first 4 chips, under the halo wire and the pipelined schedule (the
layout of ring3_12x12_h4.paper_drive), and the files are named
trace_4x4_h4.*: one device plane a chip, each with the exchange's
collectives.
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
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

NAME = "trace_4x4_scoped"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata"))
    ap.add_argument("--shards", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np

    from chip_bench.record_trace import describe
    from repro import compile_cache
    from repro.core.params import EngineConfig, GridConfig
    from repro.core.step_program import StepProgram

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: nothing recorded")
    compile_cache.enable()
    name, eng, mesh = NAME, EngineConfig(), None
    if args.shards > 1:
        from jax.sharding import AxisType, Mesh
        name = f"trace_4x4_h{args.shards}"
        eng = EngineConfig(n_shards=args.shards, exchange="halo",
                           exchange_schedule="pipelined")
        mesh = Mesh(np.array(jax.devices()[:args.shards]), ("cells",),
                    axis_types=(AxisType.Auto,))
    sp = StepProgram(GridConfig(grid_x=4, grid_y=4), eng, mesh=mesh)
    state = sp.place(sp.init_state())
    text = sp.lower_run(state, 0, 1).compile().as_text()
    state, _, _ = jax.block_until_ready(sp.run(state, 0, 1))
    state, _, _ = jax.block_until_ready(sp.run(state, 1, 1))
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, name + ".xplane.pb")
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
        shutil.copy(path, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(args.out, name + ".hlo.txt"), "w") as f:
        f.write(text)
    print(f"wrote {dst}: {os.path.getsize(dst)} bytes, and its program's "
          f"text: {len(text)} characters")
    describe(dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
