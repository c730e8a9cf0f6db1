"""Which thread of the learner process has the CPU, and which one waits: the
one source of the per-layer readers that read a span's CPU time or the
server's per-thread CPU ledger.

Two sources, both the program's:

* a traced span carries ``cpu_ns``, its thread's CPU time inside it
  (``relayrl_tpu/telemetry/spans.py``: every span of a name, but no two
  within 5 ms of each other — a read of that clock is dear on the chip
  machine's host) and ``cpu_wall_ns``, the wall time between the same two
  clock reads, so ``cpu_wall_ns - cpu_ns`` is the time the thread stood off
  the CPU inside the span — runnable and not running, or blocked. For a span
  whose body does no I/O (``rl:batch.pad``) that is a wait for the CPU or
  for the interpreter's lock. SELF CPU time is a span's own less its direct
  children's, as ``program_trace`` has self time;
* ``server.timings["cpu_<role>_s"]`` / ``["runq_<role>_s"]``, the kernel's
  on-CPU and run-queue time of the learner, staging, receive and publisher
  threads and ``cpu_process_s`` of the whole process, absolute totals the
  loop drivers hand over as the measured window's deltas (``run.timings``).

**The attribution rule** (``pad_wait_on_decode_pct``,
``pad_wait_on_ingest_pct``). A span NAME's on-CPU share ``R(name)`` is its
self CPU time over its self time in the traced window (:func:`account` has
how both come from the spans that carry ``cpu_ns``). For each
``rl:batch.pad`` span P, ``off(P) = dur(P) x (1 - R(rl:batch.pad))``; for
each other thread class T — staging: ``rl:ingest.decode`` by self time (the
native call inside it, ``rl:ingest.decode_native``, runs without the lock
and is left out); receive: ``rl:ingest.recv``, ``rl:ingest.admit``;
publisher: ``rl:publish`` and what it nests — ``busy_T(P)`` is the sum, over
the self pieces of T's spans S that overlap P, of ``overlap(P, piece) x
R(S's name)``: how long T was on the CPU, in its own Python, while P ran.
P's ``off`` goes to the classes in proportion to ``busy_T``, never more than
``off(P)`` in all (``busy`` beyond it is scaled down); what no class's
``busy`` covers stays unattributed (the scheduler, XLA's and libzmq's
threads, anything unnamed). The metrics are the sums of what was attributed
over the sum of ``off``.

The shares are a name's and not a span's because of the clock: ISSUE 70
wrote the rule with each span's own ``self_cpu / self_dur``, and on the chip
machine's kernel a thread's CPU clock advances in steps of 10 ms (it has no
``schedstat`` either) and costs 6 us a read, so one span of 0.1-1 ms
reads 0 or 10 ms, only a sum over many spans says anything (a span's
``cpu_ns`` may therefore pass its duration by one step), and the program
stamps a sample of them. On a kernel with a fine clock the same rule reads
the same.

It is an estimate: a thread on the CPU is not thereby the one that holds
the lock. ``cpu_process_pct`` (how many cores the process had in all) bounds
how wrong it can be. The learner thread's run-queue share (runnable and not
running is the scheduler's and not a lock's: a lock's wait sleeps on a
condition variable) would bound it from the other side; the chip machine's
kernel does not keep it, so no metric reads it, and where a kernel does it
is in the note's table (``threads_pct_of_window.learner.runq``).

The arithmetic is checked without a chip against a hand-made event list
(``benchmark/tests/test_thread_account.py``,
``tests/test_thread_account_arithmetic.py``).
A program whose spans carry no ``cpu_ns`` and whose server keeps no thread
ledger (the parent of the PR that added them) gives ``None`` everywhere.
"""

from __future__ import annotations

import bisect

from benchmark import program_trace

PAD = "rl:batch.pad"
DECODE = "rl:ingest.decode"
DECODE_NATIVE = "rl:ingest.decode_native"
UPDATE = "host:dispatch"
# thread classes whose Python may hold the lock while ``rl:batch.pad`` waits
CLASSES = {
    "decode": (DECODE,),
    "ingest": ("rl:ingest.recv", "rl:ingest.admit"),
    "publish": ("rl:publish", "rl:publish.gather", "rl:publish.encode",
                "rl:publish.send"),
}
ROLES = ("learner", "staging", "ingest", "publish", "process")


def account(spans: dict[str, list[dict]]) -> dict | None:
    """``program_trace.reduce_events(...)["spans"]`` -> per span name the
    window's totals (``n``, ``wall``, ``self`` and, where some of its spans
    carry ``cpu_ns``, ``stamped`` — how many —, ``cpu``, ``off``,
    ``self_cpu``; ns), the number of updates dispatched inside the window,
    and the split of ``rl:batch.pad``'s off-CPU time by the rule above. None
    where no span carries ``cpu_ns``.

    The program stamps a sample of a name's spans (at most one in 5 ms), so
    everything is a NAME's: ``R(name)`` is ``cpu_ns`` over ``cpu_wall_ns``
    (the wall time between the same two clock reads; a span's duration
    where the program gives none) summed over the spans that carry them (a
    span the window clips counts for its part inside), ``cpu = wall x R``
    over all of the name's spans, ``off = wall - cpu``, and ``self_cpu`` is
    ``cpu`` less, for each name nested directly in it, that name's wall time
    there times ITS ``R``."""
    nodes = _nodes(spans)
    names: dict[str, dict] = {}
    stamped: dict[str, list] = {}   # name -> [cpu, wall, count] of those
    nested: dict[str, dict[str, float]] = {}  # name -> child name -> wall
    for n in nodes:
        row = names.setdefault(n["name"], {"n": 0, "wall": 0.0, "self": 0.0})
        row["n"] += 1
        row["wall"] += n["wall"]
        row["self"] += n["self"]
        if n["cpu"] is not None:
            both = stamped.setdefault(n["name"], [0.0, 0.0, 0])
            both[0] += n["cpu"]
            both[1] += n["cpu_wall"]
            both[2] += 1
        inside = nested.setdefault(n["name"], {})
        for child in n["children"]:
            inside[child["name"]] = inside.get(child["name"], 0.0) + (
                child["wall"])
    share = {name: cpu / wall for name, (cpu, wall, _n) in stamped.items()
             if wall > 0}
    if not share:
        return None
    for name in share:
        row = names[name]
        row["stamped"] = stamped[name][2]
        row["cpu"] = row["wall"] * share[name]
        row["off"] = row["wall"] - row["cpu"]
        row["self_cpu"] = row["cpu"] - sum(
            wall * share.get(child, 0.0)
            for child, wall in nested[name].items())
    updates = sum(1 for s in spans.get(UPDATE, []) if s["inside"])
    return {"updates": updates, "names": names,
            "pad_wait": _pad_wait(nodes, names)}


def _nodes(spans: dict[str, list[dict]]) -> list[dict]:
    """One node a span, with its direct children found again (by thread,
    parents before children)."""
    by_thread: dict[int, list[dict]] = {}
    for name, found in spans.items():
        for s in found:
            # the wall time of the bracket the CPU time was read over: the
            # program's own (``cpu_wall_ns``), else the span's duration;
            # both count for the part of the span the window holds
            full = s["end"] - s["start"]
            part = s["dur"] / full if full > 0 else 0.0
            cpu = s["args"].get("cpu_ns")
            by_thread.setdefault(s["thread"], []).append({
                "name": name, "thread": s["thread"], "start": s["start"],
                "end": s["end"], "wall": s["dur"], "self": s["self"],
                "cpu": None if cpu is None else cpu * part,
                "cpu_wall": s["args"].get("cpu_wall_ns", full) * part,
                "children": []})
    nodes = []
    for found in by_thread.values():
        stack: list[dict] = []
        for n in sorted(found, key=lambda n: (n["start"], -n["end"])):
            while stack and n["start"] >= stack[-1]["end"]:
                stack.pop()
            if stack:
                stack[-1]["children"].append(n)
            stack.append(n)
            nodes.append(n)
    return nodes


def _self_pieces(n: dict) -> list[tuple[float, float]]:
    """The intervals of a span that none of its direct children covers."""
    pieces, at = [], n["start"]
    for child in n["children"]:
        if child["start"] > at:
            pieces.append((at, child["start"]))
        at = max(at, child["end"])
    if n["end"] > at:
        pieces.append((at, n["end"]))
    return pieces


def _pad_wait(nodes: list[dict], names: dict[str, dict]) -> dict | None:
    """``{"off": ns, "decode": ns, "ingest": ns, "publish": ns,
    "unattributed": ns}`` summed over the window's ``rl:batch.pad`` spans;
    None where none carries ``cpu_ns``."""
    if "self_cpu" not in names.get(PAD, {}):
        return None
    # a name's on-CPU share of its SELF time
    on_cpu = {name: min(1.0, max(0.0, row["self_cpu"] / row["self"]))
              for name, row in names.items()
              if "self_cpu" in row and row["self"] > 0}
    # per class and thread: self pieces (start, end, the name's share),
    # disjoint and in order
    pieces: dict[str, dict[int, list]] = {c: {} for c in CLASSES}
    for n in nodes:
        for cls, members in CLASSES.items():
            if n["name"] in members and n["name"] in on_cpu:
                pieces[cls].setdefault(n["thread"], []).extend(
                    (a, b, on_cpu[n["name"]]) for a, b in _self_pieces(n))
    ends = {}
    for cls, threads in pieces.items():
        for thread, found in threads.items():
            found.sort()
            ends[cls, thread] = [b for _a, b, _r in found]
    out = dict.fromkeys(("off", *CLASSES, "unattributed"), 0.0)
    for p in (n for n in nodes if n["name"] == PAD):
        off = p["wall"] * (1.0 - on_cpu.get(PAD, 0.0))
        if off <= 0:
            continue
        busy = {}
        for cls, threads in pieces.items():
            total = 0.0
            for thread, found in threads.items():
                if thread == p["thread"]:
                    continue
                i = bisect.bisect_right(ends[cls, thread], p["start"])
                while i < len(found) and found[i][0] < p["end"]:
                    a, b, share = found[i]
                    total += share * (min(b, p["end"]) - max(a, p["start"]))
                    i += 1
            busy[cls] = total
        scale = min(1.0, off / sum(busy.values())) if any(
            busy.values()) else 0.0
        out["off"] += off
        for cls, b in busy.items():
            out[cls] += scale * b
        out["unattributed"] += off - scale * sum(busy.values())
    return out


def of(run) -> dict | None:
    """The account of this run's traced sub-window, once a run."""
    if not hasattr(run, "_thread_account"):
        t = program_trace.of(run)
        run._thread_account = account(t["spans"]) if t else None
    return run._thread_account


# -- what the readers share --------------------------------------------------

def per_update_ms(run, name: str, field: str, per: str = UPDATE
                  ) -> float | None:
    """``field`` of the spans ``name`` summed over the traced window, per
    span ``per`` that started inside it, in ms."""
    acct, t = of(run), program_trace.of(run)
    if not acct or field not in acct["names"].get(name, {}):
        return None
    n = sum(1 for s in t["spans"].get(per, []) if s["inside"])
    return acct["names"][name][field] / n / 1e6 if n else None


def decode_gil_ms(run) -> float | None:
    """The staging thread's Python half of a decode: ``rl:ingest.decode``
    less the native call it nests. None for a program that does not name
    the native call (the whole span would read as Python)."""
    acct = of(run)
    if not acct or DECODE_NATIVE not in acct["names"]:
        return None
    return per_update_ms(run, DECODE, "self")


def pad_wait_pct(run, cls: str) -> float | None:
    """Of ``rl:batch.pad``'s off-CPU time, the share attributed to class
    ``cls`` of ``CLASSES`` (or ``"unattributed"``)."""
    acct = of(run)
    if not acct or not acct["pad_wait"] or not acct["pad_wait"]["off"]:
        return None
    return 100.0 * acct["pad_wait"][cls] / acct["pad_wait"]["off"]


def ledger_pct(run, key: str) -> float | None:
    """``100 * timings[key] / window_s``: a thread's (or the process's)
    share of the measured window, of one core."""
    if key not in run.timings or not run.window_s:
        return None
    return 100.0 * run.timings[key] / run.window_s


def note(run) -> None:
    """The whole table into the result line's ``notes.thread_account``: per
    thread its CPU and run-queue share of the measured window, per span
    name its count (``n``; ``stamped`` of them carry ``cpu_ns``) and ms an
    update (wall, self, CPU, off-CPU, self CPU) in the traced one, and the
    four shares of ``rl:batch.pad``'s off-CPU time."""
    acct = of(run)
    threads = {
        role: {kind: round(pct, 3) for kind in ("cpu", "runq")
               if (pct := ledger_pct(run, f"{kind}_{role}_s")) is not None}
        for role in ROLES}
    threads = {role: row for role, row in threads.items() if row}
    if not acct and not threads:
        return
    table: dict = {"threads_pct_of_window": threads}
    if acct:
        per = max(1, acct["updates"])
        table["updates"] = acct["updates"]
        table["spans_ms_per_update"] = {
            name: {k: round(v if k in ("n", "stamped") else v / per / 1e6, 3)
                   for k, v in row.items()}
            for name, row in sorted(acct["names"].items())}
        if acct["pad_wait"] and acct["pad_wait"]["off"]:
            table["pad_wait_pct"] = {
                cls: round(pad_wait_pct(run, cls), 3)
                for cls in (*CLASSES, "unattributed")}
    run.notes["thread_account"] = table
