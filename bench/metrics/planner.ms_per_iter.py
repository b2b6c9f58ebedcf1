"""Host planning time per plan built in the traced window: the self time
of the program's ``plan.build`` spans (sampling, dedup, index
translation, bucketing), without the ``upload.commit`` spans nested in
them. A faster planner raises ``roots_per_s`` where the host sets the
pace."""
LAYER = "planner"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    host = run.record["host"]
    builds = [s for s in host if s[0] == "plan.build"]
    if not builds:
        return None
    uploads = sum(s[3] for s in host if s[0] == "upload.commit")
    return (sum(s[3] for s in builds) - uploads) / len(builds) / 1e6
