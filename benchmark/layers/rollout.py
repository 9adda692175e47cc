"""Layer: rollout.  Source: the records' ``env_steps`` (alive steps) over
the scanned member-steps.  It says how much of the scan is masked; it moves
the end-to-end number only once a change skips masked steps."""


def read(run):
    alive = sum(r["env_steps"] for r in run["records"])
    return {"rollout.alive_share":
            alive / (len(run["records"]) * run["steps_per_generation"])}
