"""Record the small device trace that ``test_trace.py`` reads.

    python benchmarks/chip/tests/record_trace.py OUT_DIR

Runs on one TPU chip: a jitted matmul loop inside a host span, a host-only
gap inside another span, and a second jitted module, all under the JAX
profiler.  Writes ``OUT_DIR/sample.xplane.pb`` and prints, per plane and
line, the event count and the first events, so the layout the reduction
reads can be checked by eye.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def work(x):
        def body(c, _):
            return jnp.tanh(c @ c), None

        return jax.lax.scan(body, x, None, length=8)[0]

    @jax.jit
    def other(x):
        return jnp.sum(x * 2.0)

    x = jnp.ones((1024, 1024), jnp.float32) * 1e-3
    work(x).block_until_ready()
    other(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out)
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            y = work(x)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_batch"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.eval"):
            other(y).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out / "sample.xplane.pb")
    shutil.rmtree(tmp)

    pd = jax.profiler.ProfileData.from_file(str(out / "sample.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, [(ln.name, len(list(ln.events))) for ln in lines])
        for ln in lines:
            for ev in list(ln.events)[:4]:
                stats = [(k, v) for k, v in ev.stats][:6]
                print("   ", ln.name, "|", ev.name, ev.start_ns, ev.duration_ns, stats)
    print("sample.xplane.pb bytes", (out / "sample.xplane.pb").stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
