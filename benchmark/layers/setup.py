"""Layer: device, the part of a process's start that ``setup_s`` leaves
out.  ``setup.bring_up_s`` is the first ``jax.devices()`` call: the
runtime's own bring-up of the chips, timed by the runner on the host
clock.  It is the machine's and not the program's (5.4 to 16.1 s for the
same code on four machines; chip runs, PR 23), so it is recorded here
where a change to it is seen and is not held to a bound."""


def read(run):
    if "bring_up_s" not in run:
        return {}
    return {"setup.bring_up_s": run["bring_up_s"]}
