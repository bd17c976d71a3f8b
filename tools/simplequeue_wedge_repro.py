#!/usr/bin/env python
"""Standalone reproduction of the CPython SimpleQueue timed-get wedge.

This is the minimal form of the bug that froze a full test-suite run
(round-5 post-mortem) and motivated moving ThreadedExecutor's
input queue from ``queue.SimpleQueue`` to ``queue.Queue``
(petastorm_tpu/pool.py).  Pure stdlib, no petastorm_tpu imports.

Mechanism (confirmed by disassembling the installed CPython 3.12.12
``_queue`` extension):

``SimpleQueue.get(timeout=t)`` waits by acquiring an internal lock that
``put`` releases.  When a waiter's blocking acquire SUCCEEDS (a put
landed late in its window) but a sibling consumer — already executing
inside ``get()`` on the GIL — pops the item before the winner reacquires
the GIL, the winner loops, finds the queue empty, and recomputes its
remaining timeout as ``deadline - now`` WITHOUT clamping at zero.  Once
the deadline expired during the GIL-reacquisition gap, that remainder is
negative, and ``PyThread_acquire_lock_timed`` treats a negative timeout
as INFINITE.  The "timed" get then blocks until the next ``put`` — or
forever, if no put ever comes (exactly the epoch-end/teardown state of a
worker pool, which is why the bug presents as a terminal hang).

Hit-rate levers (why this script fires in minutes while naive hammers
run clean): tiny get timeouts make "a put lands inside the waiter's
window, near its deadline" near-certain per put; several churning
consumers supply the in-``get()`` thief; producer silences remove the
rescuing put so the wedge becomes observable.

Exit 3 = wedge observed (a consumer stuck in get(timeout=1ms) for >3 s).
Typical time-to-wedge on a 1-core host: 1-10 minutes.
"""
import queue
import random
import sys
import threading
import time

N_CONSUMERS = 8
GET_TIMEOUT_S = 0.001
STUCK_THRESHOLD_S = 3.0

q = queue.SimpleQueue()
stop = threading.Event()
stuck_since = [None] * N_CONSUMERS


def consumer(i):
    while not stop.is_set():
        stuck_since[i] = time.monotonic()
        try:
            q.get(timeout=GET_TIMEOUT_S)
        except queue.Empty:
            pass
        stuck_since[i] = None


def producer():
    rnd = random.Random(7)
    while not stop.is_set():
        q.put(1)
        time.sleep(rnd.uniform(0.0005, 0.002))
        if rnd.random() < 0.02:
            time.sleep(4.0)  # silence: a wedged getter has no rescuer


def main():
    budget = float(sys.argv[1]) if len(sys.argv) > 1 else 1800
    threads = [threading.Thread(target=consumer, args=(i,), daemon=True)
               for i in range(N_CONSUMERS)]
    threads.append(threading.Thread(target=producer, daemon=True))
    for t in threads:
        t.start()
    t0 = time.time()
    while time.time() - t0 < budget:
        time.sleep(1)
        now = time.monotonic()
        held = [(i, round(now - s, 2)) for i, s in enumerate(stuck_since)
                if s and now - s > STUCK_THRESHOLD_S]
        if held:
            print(f"WEDGED: SimpleQueue.get(timeout={GET_TIMEOUT_S}) stuck"
                  f" for {held} (elapsed {time.time() - t0:.0f}s)",
                  flush=True)
            sys.exit(3)
    print(f"no wedge in {budget:.0f}s (probabilistic - rerun or raise the"
          " budget)", flush=True)
    stop.set()


if __name__ == "__main__":
    main()
