"""Layer: compile.  Source: jax's monitoring counters as the program's
``utils.backend.compile_event_counts`` keeps them, snapshotted by the runner
at the start of set-up, at the window's first fence and at its last; and the
AOT seconds ``ES.train`` records for the generation program."""


def read(run):
    c = run["compile"]
    return {"compile.programs_in_window": c["window"]["programs"],
            "compile.fresh_programs_setup": c["setup"]["fresh"],
            "compile.aot_s": c["aot_s"]}
