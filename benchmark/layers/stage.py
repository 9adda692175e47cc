"""Layers inside the generation program, by the stage scopes the program
names itself with (``estorch_tpu/obs/trace.py``: ``es.<stage>``).  Source:
the device trace, reduced by ``stage_reduce.py``: seconds of the busiest
chip's leaf operations by the innermost stage of each operation's name
stack, as shares of that chip's busy seconds in the traced window.  A
fusion is booked whole to the stage of its root.  With ``es.sample`` and
``es.gather`` (printed, no metric of their own) the shares sum to 1.

Nothing is reported for a program from before the scopes (no operation
names a stage and no ``Telemetry.phase`` is an annotation).  Where the
program's phases ARE in the trace and still no operation names a stage, the
executable came from a compile-cache entry that a build without the scopes
wrote (the cache key leaves metadata out; PERF.md §3): the trace is
reported as it reads, ``stage.unscoped_share`` 1 and every other share 0,
and the log says why.  Trace on a cache directory of its own.
"""

from benchmark import stage_reduce

SHARES = {
    "stage.noise_share": ("noise",),
    "stage.perturb_share": ("perturb",),
    "stage.policy_share": ("policy",),
    "stage.env_share": ("env",),
    "stage.update_share": ("rank", "grad", "update"),
    "stage.unscoped_share": (stage_reduce.UNSCOPED,),
}
UNMETERED = ("sample", "gather")


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    if not d["scoped_ops"]:
        if not out["spans"]:
            print("[stage] no operation of the trace names an es.<stage> and "
                  "no phase is an annotation: a program without stage "
                  "scopes", flush=True)
            return {}
        print("[stage] the program's phases are in the trace but no "
              "operation names an es.<stage>: the executable came from a "
              "compile-cache entry written without the scopes; every share "
              "reads unscoped.  Trace on a cache directory of its own",
              flush=True)
    seconds, busy = d["stage_s"], d["busy_s"]
    other = set(seconds) - {s for names in SHARES.values() for s in names}
    if other - set(UNMETERED):
        print(f"[stage] stages no metric reads: {sorted(other)}", flush=True)
    return {metric: sum(seconds.get(s, 0.0) for s in names) / busy
            for metric, names in SHARES.items()}
