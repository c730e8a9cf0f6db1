"""The per-thread CPU ledger (telemetry/thread_clock.py): one thread reads
the on-CPU and run-queue time of the others, from ``schedstat`` where the
kernel keeps it and from the thread's POSIX clock where it does not."""

import threading
import time

import pytest
from _util import burn_cpu

from relayrl_tpu.telemetry import thread_clock
from relayrl_tpu.telemetry.thread_clock import ThreadLedger, read_ns


class Worker:
    """A thread that burns ``seconds`` of CPU each time it is told to."""

    def __init__(self):
        self._go, self._done = threading.Event(), threading.Event()
        self._quit = False
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def _loop(self):
        while True:
            self._go.wait()
            self._go.clear()
            if self._quit:
                return
            burn_cpu(self._seconds)
            self._done.set()

    def burn(self, seconds):
        self._seconds = seconds
        self._done.clear()
        self._go.set()
        assert self._done.wait(30)

    def stop(self):
        self._quit = True
        self._go.set()
        self.thread.join(30)
        assert not self.thread.is_alive()


@pytest.fixture
def worker():
    w = Worker()
    yield w
    if w.thread.is_alive():
        w.stop()


@pytest.fixture
def no_schedstat(tmp_path, monkeypatch):
    monkeypatch.setattr(thread_clock, "TASK_DIR", str(tmp_path / "absent"))


@pytest.fixture
def fake_schedstat(tmp_path, monkeypatch):
    """``write(thread, cpu_ns, runq_ns)`` plants a thread's schedstat."""
    monkeypatch.setattr(thread_clock, "TASK_DIR", str(tmp_path))

    def write(thread, cpu_ns, runq_ns):
        task = tmp_path / str(thread.native_id)
        task.mkdir(exist_ok=True)
        (task / "schedstat").write_text(f"{cpu_ns} {runq_ns} 17\n")

    return write


def test_read_ns_follows_another_threads_cpu_time(worker, no_schedstat):
    cpu0, runq0 = read_ns(worker.thread)
    worker.burn(0.05)
    cpu1, runq1 = read_ns(worker.thread)
    assert runq0 is None and runq1 is None
    assert 0.05e9 <= cpu1 - cpu0 < 0.2e9


def test_read_ns_takes_both_halves_from_schedstat(worker, fake_schedstat):
    fake_schedstat(worker.thread, 123_000, 456)
    assert read_ns(worker.thread) == (123_000, 456)


@pytest.mark.parametrize("text", ["", "12\n", "a b c\n"])
def test_a_schedstat_it_cannot_read_falls_back_to_the_clock(
        worker, tmp_path, monkeypatch, text):
    monkeypatch.setattr(thread_clock, "TASK_DIR", str(tmp_path))
    task = tmp_path / str(worker.thread.native_id)
    task.mkdir()
    (task / "schedstat").write_text(text)
    cpu, runq = read_ns(worker.thread)
    assert cpu >= 0 and runq is None


def test_a_thread_not_started_or_exited_reads_none(worker):
    assert read_ns(threading.Thread(target=lambda: None)) is None
    worker.stop()
    assert read_ns(worker.thread) is None


def test_read_ns_matches_the_real_schedstat_where_the_kernel_has_one(worker):
    try:
        open(f"/proc/self/task/{worker.thread.native_id}/schedstat").close()
    except OSError:
        pytest.skip("this kernel keeps no schedstat")
    worker.burn(0.05)
    cpu, runq = read_ns(worker.thread)
    assert cpu >= 0.05e9 and runq >= 0


def test_a_ledger_asks_for_schedstat_once_when_it_is_built(
        worker, fake_schedstat, monkeypatch):
    """No failed ``open`` a thread a refresh on a kernel without it."""
    absent = ThreadLedger(("staging",), runq_roles=("staging",))
    fake_schedstat(threading.current_thread(), 1, 2)
    present = ThreadLedger(("staging",), runq_roles=("staging",))
    fake_schedstat(worker.thread, 5_000_000_000, 1_000_000_000)
    opened = []
    real_open = open

    def counting_open(path, *a, **k):
        opened.append(path)
        return real_open(path, *a, **k)

    monkeypatch.setattr("builtins.open", counting_open)
    out = {}
    for ledger in (absent, present):
        ledger.watch("staging", worker.thread)
        ledger.refresh(out := {})
        ledger.refresh(out)
        if ledger is absent:
            assert not opened and "runq_staging_s" not in out
            assert out["cpu_staging_s"] < 5.0
    assert len(opened) == 2
    assert (out["cpu_staging_s"], out["runq_staging_s"]) == (5.0, 1.0)


def test_ledger_without_schedstat_has_cpu_keys_and_no_runq_keys(
        worker, no_schedstat):
    ledger = ThreadLedger(("learner", "staging"), runq_roles=("learner",))
    ledger.watch("learner", worker.thread)
    worker.burn(0.02)
    out = {}
    ledger.refresh(out)
    assert sorted(out) == ["cpu_learner_s", "cpu_process_s", "cpu_staging_s"]
    assert out["cpu_learner_s"] >= 0.02 and out["cpu_staging_s"] == 0.0
    assert out["cpu_learner_s"] <= out["cpu_process_s"]


def test_ledger_sums_a_roles_threads_and_keeps_runq_for_its_roles(
        worker, fake_schedstat):
    other = Worker()
    try:
        fake_schedstat(threading.current_thread(), 3_000_000_000, 1)
        ledger = ThreadLedger(("staging", "publish"),
                              runq_roles=("staging",))
        ledger.watch("staging", worker.thread)
        ledger.watch("staging", other.thread)
        ledger.watch("staging", other.thread)      # twice: counted once
        ledger.watch("publish", threading.current_thread())
        fake_schedstat(worker.thread, 2_000_000_000, 500_000_000)
        fake_schedstat(other.thread, 1_000_000_000, 250_000_000)
        fake_schedstat(threading.current_thread(), 3_000_000_000, 1)
        out = {}
        ledger.refresh(out)
        assert out["cpu_staging_s"] == pytest.approx(3.0)
        assert out["runq_staging_s"] == pytest.approx(0.75)
        assert out["cpu_publish_s"] == pytest.approx(3.0)
        assert "runq_publish_s" not in out
        assert out["cpu_process_s"] == pytest.approx(time.process_time(),
                                                     abs=1.0)
    finally:
        other.stop()


def test_an_exited_thread_keeps_its_last_reading_and_raises_nothing(
        worker, no_schedstat):
    ledger = ThreadLedger(("staging",), runq_roles=())
    ledger.watch("staging", worker.thread)
    ledger.watch("staging", threading.Thread(target=lambda: None))
    worker.burn(0.02)
    first, second = {}, {}
    ledger.refresh(first)
    worker.stop()
    ledger.refresh(second)
    assert second["cpu_staging_s"] == first["cpu_staging_s"] >= 0.02
    assert second["cpu_process_s"] >= first["cpu_process_s"]


def test_totals_never_fall_while_threads_come_and_go(no_schedstat):
    ledger = ThreadLedger(("ingest",), runq_roles=())
    totals = []
    for _ in range(3):
        w = Worker()
        ledger.watch("ingest", w.thread)
        w.burn(0.01)
        out = {}
        ledger.refresh(out)
        w.stop()
        ledger.refresh(out)
        totals.append(out["cpu_ingest_s"])
    assert totals == sorted(totals) and totals[-1] >= 0.03
