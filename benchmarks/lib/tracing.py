"""The traced slice of a run: start and stop the profiler around part of
the window, reduce the trace in the process, keep only numbers."""
from __future__ import annotations

import glob
import os
import shutil
import time

from .common import OUT
from . import reduce_trace


class TraceSlice:
    """``start()`` ... ``stop()`` around the slice; ``reduced`` after."""

    SPAN = "bench/trace_slice"

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT, f"trace_{cell_name}")
        self.reduced = None
        self.t_start = self.t_stop = None
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the host's own frames: off
        options.host_tracer_level = 2        # TraceAnnotation spans: on
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(self.SPAN)
        self._span.__enter__()
        self.t_start = time.time()

    def stop(self) -> None:
        import jax

        self.t_stop = time.time()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> None:
        """After the window: the reduction is not part of the measured
        time. The trace itself is deleted; only numbers leave."""
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if found:
            self.reduced = reduce_trace.reduce_trace(found[0], self.SPAN)
        shutil.rmtree(self.dir, ignore_errors=True)
