"""Shared clock helpers — the single source of timing truth.

Every interval in the package (tracer spans, metric timers, benchmark
clocks, listener throughput) reads ``monotonic_s()`` so measurements are
immune to wall-clock steps (NTP slew, DST); ``wall_s()`` exists for
timestamps that must be correlated with the outside world (event-log
records, scrape timestamps).  graftlint JX011 enforces this split:
``time.time()`` arithmetic is a lint error in library code.
"""
from __future__ import annotations

import os
import time
from typing import Optional

__all__ = ["monotonic_s", "process_age_s", "wall_s"]


def monotonic_s() -> float:
    """Monotonic seconds for interval measurement (never steps backwards)."""
    return time.perf_counter()


def wall_s() -> float:
    """Wall-clock seconds since the epoch — timestamps only, never
    intervals."""
    return time.time()


def process_age_s() -> Optional[float]:
    """Seconds since this process started, by the kernel's record of it
    (field 22 of ``/proc/self/stat``, in clock ticks since boot, against
    ``CLOCK_BOOTTIME``): the interpreter's start and every import before
    the caller's, which no clock read in Python can reach back to.  None
    where there is no ``/proc``."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        # the command (field 2) may hold spaces and brackets: count from
        # its closing bracket, after which field 3 comes first
        started_ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
