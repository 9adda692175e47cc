"""How ``data/tiny_tpu_scoped.xplane.pb`` was recorded (on a TPU v5e, PR
24): a jitted ``scan`` of a matmul under the stage scope ``es.policy`` and a sort
under ``es.env`` (the package's own ``stage``; two matmuls came out as ONE
fusion, booked whole to the stage of its root), run
three times between fence annotations, each call inside the ``dispatch``
and ``device`` phases of a ``Telemetry`` (each phase is a trace annotation
that carries the generation).  The stage reduction then has two stages to
tell apart, and host spans to read from the trace itself.

    python3 benchmark/rehearse/record_tiny_scoped_trace.py <out-dir>
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import stage_reduce, trace_reduce  # noqa: E402
from estorch_tpu.obs.spans import Telemetry  # noqa: E402
from estorch_tpu.obs.trace import ENV, POLICY, stage  # noqa: E402


@jax.jit
def work(x, w):
    def body(x, _):
        with stage(POLICY):
            h = jnp.tanh(x @ w)
        with stage(ENV):
            x = jnp.sort(h, axis=-1) * 0.5 + 0.1
        return x, None

    return jax.lax.scan(body, x, None, length=8)[0]


def main(out_dir: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)
    w = jnp.full((512, 512), 1e-3, jnp.float32)
    work(x, w).block_until_ready()
    obs = Telemetry()
    jax.profiler.start_trace(out_dir)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation(trace_reduce.FENCE):
                pass
            with obs.phase("dispatch"):
                y = work(x, w)
            with obs.phase("device"):
                y.block_until_ready()
            obs.take_phases()
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation(trace_reduce.FENCE):
            pass
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    pd = trace_reduce.load(path)
    marks = trace_reduce.fence_times(pd)
    reduced = trace_reduce.reduce(pd, window=(marks[0], marks[-1]))
    print(f"{path}: {os.path.getsize(path)} bytes; fences {marks}")
    if reduced:
        d = trace_reduce.busiest_device(reduced)
        out = stage_reduce.read_trace(path, d["gaps"])
        print({"busy_s": d["busy_s"], "events": d["events"],
               "stage_s": stage_reduce.busiest_device(
                   out["staged"])["stage_s"],
               "spans": out["spans"], "tail_gaps": out["tail_gaps"]})
        print("\n".join(stage_reduce.describe(out)))


if __name__ == "__main__":
    main(sys.argv[1])
