"""Share of the planner's leaf work time in which its threads were off
the CPU: one minus the CPU time over the wall time of the traced
window's leaf planner spans (the pool items ``plan.sample`` and
``plan.translate``, and the serial stages ``planner.dedup`` and
``planner.account``). The program records each span's thread CPU time
(``SpanRecord.cpu_ns``, ``time.thread_time_ns``); the rest is GIL wait,
preemption and I/O. Less waiting shortens ``planner.ms_per_iter`` and
raises ``roots_per_s`` where the host planner sets the pace."""
LAYER = "planner"
MOVES = "roots_per_s"
UNIT = "%"
LEAVES = ("plan.sample", "plan.translate", "planner.dedup",
          "planner.account")


def read(run):
    from repro.obs import trace
    lo, hi = (int(run.window["t_open"] * 1e9),
              int(run.window["t_close"] * 1e9))
    spans = [r for r in trace.records()
             if r.kind == "X" and r.name in LEAVES
             and r.t1_ns >= lo and r.t0_ns <= hi]
    wall = sum(r.t1_ns - r.t0_ns for r in spans)
    cpu = [getattr(r, "cpu_ns", None) for r in spans]
    if not wall or None in cpu:
        return None
    return 100.0 * (1.0 - sum(cpu) / wall)
