"""Shared helpers for the soak/fuzz tools (stress_soak, concurrency_fuzz).

A progress-based wedge watchdog whose dumps carry BOTH every thread's
Python stack and each OS thread's in-flight syscall + kernel wait channel
(/proc/self/task) — the evidence set that root-caused the round-5
SimpleQueue wedge — plus a validated dataset cache so a
killed first run can never turn later runs into non-reproducible
invariant failures.
"""
import faulthandler
import os
import threading
import time


def capture_os_thread_state(out):
    """Append each OS thread's syscall args and kernel wait channel.

    /proc/<tid>/syscall shows the blocked syscall number and its raw args -
    for futex waits, whether a timeout struct was passed (arg4 != 0).
    """
    me = os.getpid()
    for tid in sorted(os.listdir(f"/proc/{me}/task")):
        base = f"/proc/{me}/task/{tid}"
        try:
            with open(f"{base}/comm") as f:
                comm = f.read().strip()
            with open(f"{base}/wchan") as f:
                wchan = f.read().strip()
            with open(f"{base}/syscall") as f:
                syscall = f.read().strip()
        except OSError:
            continue
        out.write(f"tid {tid} [{comm}] wchan={wchan} syscall={syscall}\n")


def start_progress_watchdog(progress, wedge_after_s, dump_path, label=""):
    """Daemon thread: if ``progress[0]`` does not advance for
    ``wedge_after_s`` seconds, dump full evidence to ``dump_path`` and
    ``os._exit(3)``.  Wall-clock slowness never fires it; only a genuine
    absence of progress does."""

    def monitor():
        last, last_t = progress[0], time.time()
        while True:
            time.sleep(10)
            if progress[0] != last:
                last, last_t = progress[0], time.time()
                continue
            if time.time() - last_t > wedge_after_s:
                with open(dump_path, "w") as f:
                    f.write(f"WEDGE{': ' + label if label else ''}:"
                            f" no progress for {time.time() - last_t:.0f}s"
                            f" at progress={last}\n\n")
                    faulthandler.dump_traceback(file=f, all_threads=True)
                    f.write("\n-- OS thread state --\n")
                    capture_os_thread_state(f)
                print(f"WEDGED - evidence in {dump_path}", flush=True)
                os._exit(3)

    t = threading.Thread(target=monitor, daemon=True)
    t.start()
    return t


def validated_dataset(url, expected_rows, build_fn):
    """Build the dataset at ``url`` unless one with exactly
    ``expected_rows`` readable rows already exists; a partial directory
    left by a killed run is rebuilt, never trusted (it would turn every
    later invariant failure into a non-reproducible artifact)."""
    import shutil

    if os.path.exists(url):
        try:
            import pyarrow.dataset as pads

            if pads.dataset(url, format="parquet").count_rows() == expected_rows:
                return url
        except Exception:
            pass
        shutil.rmtree(url, ignore_errors=True)
    build_fn(url)
    return url
