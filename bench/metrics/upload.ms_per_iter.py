"""Host time per plan to commit its device arguments (the program's
``upload.commit`` spans of ``PlanUploader``), on the prefetch thread. It
adds to the planner's time per plan, so a cheaper upload raises
``roots_per_s`` where the host sets the pace."""
LAYER = "upload"
MOVES = "roots_per_s"
UNIT = "ms"


def read(run):
    ups = [s[3] for s in run.record["host"] if s[0] == "upload.commit"]
    if not ups:
        return None
    return sum(ups) / len(ups) / 1e6
