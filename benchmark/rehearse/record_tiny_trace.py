"""How ``data/tiny_tpu.xplane.pb`` was recorded (on a TPU v5e, PR 23): a
jitted ``while`` of two matmul fusions, run three times between fence
annotations, so that the reduction has containers to leave out, leaf
operations to add up and idle gaps between the calls to name.

    python3 benchmark/rehearse/record_tiny_trace.py <out-dir>
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


@jax.jit
def work(x):
    def body(_, x):
        return jnp.tanh(x @ x) * 0.5 + 0.1

    return jax.lax.fori_loop(0, 8, body, x)


def main(out_dir: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)
    work(x).block_until_ready()
    fences = []
    jax.profiler.start_trace(out_dir)
    try:
        for _ in range(4):
            with jax.profiler.TraceAnnotation(trace_reduce.FENCE):
                fences.append(time.perf_counter())
            work(x).block_until_ready()
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    pd = trace_reduce.load(path)
    marks = trace_reduce.fence_times(pd)
    reduced = trace_reduce.reduce(pd, window=(marks[0], marks[-1]))
    print(f"{path}: {os.path.getsize(path)} bytes; fences {marks}; "
          f"host fences {fences}")
    if reduced:
        d = reduced["devices"][reduced["busiest"]]
        print({k: d[k] for k in ("busy_s", "window_s", "events",
                                 "collective_s")})
        print(trace_reduce.top_ops(d["per_op"]))
        print(sorted(d["gaps"], key=lambda g: g[0] - g[1])[:5])


if __name__ == "__main__":
    main(sys.argv[1])
