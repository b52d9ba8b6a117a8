"""Host spans and the profiler's window of a traced run."""
from __future__ import annotations

import glob
import os
import shutil
import time

import jax

import trace_reduce

_HERE = os.path.dirname(os.path.abspath(__file__))
#: inside the checkout, git-ignored, emptied before every traced run
TRACE_DIR = os.path.join(os.path.dirname(_HERE), ".bench_trace")


def annotate(name: str):
    """A host span in the profiler's own trace, around the harness's calls
    into the program (none sits inside the program in this PR)."""
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Turns the profiler on for the last few seconds of the window. The
    driver stops it once the window has closed (`finish`), so of the
    profiler's own stalls only its start falls inside the window; how
    long both took is kept in `stall_s`."""

    def __init__(self, args):
        self.length = min(4.0, 0.5 * args.seconds)
        self.start = args.seconds - self.length
        self.on_at = self.off_at = None
        self.stall_s = [0.0, 0.0]

    def poll(self, now: float) -> None:
        if self.on_at is None and now >= self.start:
            t = time.perf_counter()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0    # see trace_reduce's header
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.stall_s[0] = time.perf_counter() - t
            self.on_at = now + self.stall_s[0]

    def finish(self, now: float) -> None:
        if self.on_at is not None and self.off_at is None:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stall_s[1] = time.perf_counter() - t
            self.off_at = now

    def reduce(self, chips: int, kind: str) -> dict:
        if self.on_at is None:
            raise RuntimeError("the window closed before the trace began")
        paths = glob.glob(os.path.join(
            TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace to {TRACE_DIR}")
        out = trace_reduce.reduce_file(paths[0], chips, kind)
        out["host_window"] = (self.on_at, self.off_at)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return out
