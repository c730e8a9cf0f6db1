"""Share of the learner thread's update cycles it spent off the CPU outside its
known waits: cycle wall time less its CPU time (``cycle_cpu_ns`` of
``host:dispatch``), less ``host:wait_data`` and ``rl:dispatch.fence`` inside
the cycle. It holds every other wait (inside ``device_put`` or the jitted
call, a lock, the GIL, the scheduler), so the one-thread cells are the
baseline and only the excess over them can be contention."""

from benchmark import program_trace


def read(run):
    return program_trace.learner_offcpu_pct(run)
