"""Which thread of a process has the CPU: each named thread's on-CPU and
run-queue time, read by ONE thread of the process for all of them.

``time.thread_time_ns`` is the calling thread's clock only. The kernel keeps
the same number for every thread, and a second one no Python clock gives:
``/proc/self/task/<tid>/schedstat`` holds the nanoseconds the thread ran on a
CPU (field 1) and the nanoseconds it stood RUNNABLE on a run queue and did
not run (field 2). The second is the scheduler's share of a thread's time
off the CPU; a thread that waits for a lock — the GIL — sleeps on a
condition variable and is on no run queue, so the two tell a lock's wait
from a crowded host's. Where the kernel keeps no ``schedstat`` the CPU half
comes from the thread's own POSIX clock and the run-queue half is left out (a
ledger asks once, when it is built: a failed ``open`` a thread a refresh is
35 us of system call on such a host, and hands the interpreter's lock away
in the middle of a dispatch).

:class:`ThreadLedger` sums the threads of a role (the learner's one, the N
staging threads, whoever calls ``on_trajectory``, the publisher's) and
writes absolute totals into a ledger dict (``server.timings``) at each
``refresh``: ``cpu_<role>_s``, ``runq_<role>_s`` for the roles asked for, and
``cpu_process_s`` (``time.process_time``: every thread of the process, named
or not). The training server refreshes once an update dispatch.
"""

from __future__ import annotations

import os
import threading
import time

TASK_DIR = "/proc/self/task"


def has_schedstat() -> bool:
    """Whether this kernel keeps ``schedstat`` for the calling thread."""
    return os.path.exists(
        f"{TASK_DIR}/{threading.get_native_id()}/schedstat")


def read_ns(thread: threading.Thread, schedstat: bool = True
            ) -> tuple[int, int | None] | None:
    """``(on-CPU ns, run-queue ns | None)`` of a live thread of this
    process; None for one that has not started or has exited (its native id
    may by now be another thread's). ``schedstat`` False goes straight to
    the thread's POSIX clock."""
    if not thread.is_alive():
        return None
    if schedstat:
        try:
            with open(f"{TASK_DIR}/{thread.native_id}/schedstat", "rb") as f:
                cpu, runq = f.read().split()[:2]
            return int(cpu), int(runq)
        except (OSError, ValueError):
            pass
    try:
        return time.clock_gettime_ns(
            time.pthread_getcpuclockid(thread.ident)), None
    except (AttributeError, OSError):
        return None


class ThreadLedger:
    """Last-read clocks of the watched threads, by role. A thread that has
    exited keeps what it last read, so a role's total never falls."""

    def __init__(self, roles: tuple[str, ...], runq_roles: tuple[str, ...]):
        self._runq_roles = runq_roles
        self._schedstat = has_schedstat()
        self._read: dict[str, dict] = {role: {} for role in roles}

    def watch(self, role: str, thread: threading.Thread) -> None:
        """Any thread may call this; ``refresh`` has one caller."""
        self._read[role].setdefault(thread, (0, None))

    def refresh(self, ledger: dict) -> None:
        for role, read in self._read.items():
            for thread in list(read):
                got = read_ns(thread, self._schedstat)
                if got is not None:
                    read[thread] = got
            ledger[f"cpu_{role}_s"] = 1e-9 * sum(
                cpu for cpu, _ in read.values())
            runq = [q for _, q in read.values() if q is not None]
            if runq and role in self._runq_roles:
                ledger[f"runq_{role}_s"] = 1e-9 * sum(runq)
        ledger["cpu_process_s"] = time.process_time()
