"""The traced slice of a window: ``jax.profiler`` switched on for
``length_s`` seconds from ``start_s`` into the window, by whoever owns the
window's loop calling ``poll``.  A traced run gives the per-layer numbers;
end-to-end numbers come from runs with the profiler off."""
import os
import shutil

from . import common, trace_reduce


class Tracer:
    def __init__(self, enabled, name, start_s, length_s):
        self.enabled = bool(enabled)
        self.dir = os.path.join(common.OUT_DIR, "trace", name)
        self.start_s, self.stop_s = start_s, start_s + length_s
        self.state = "idle" if self.enabled else "done"

    def poll(self, elapsed_s, sync=None):
        """``sync`` (a loop that feeds the device itself passes one) is
        called before the profiler starts and before it stops, so that the
        slice holds whole runs only and no stall of the profiler's own."""
        import jax
        if self.state == "idle" and elapsed_s >= self.start_s:
            if sync:
                sync()
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            # host TraceMe spans and the device, not every Python call:
            # the Python tracer doubled the serving host loop's time per
            # step (my chip run, PR 24) and would be read as the program's
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.state = "on"
        elif self.state == "on" and elapsed_s >= self.stop_s:
            if sync:
                sync()
            self.finish()

    def finish(self):
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"

    def reduced(self):
        """The reduced trace, or None where nothing was traced."""
        path = trace_reduce.find_xplane(self.dir) if self.enabled else None
        return trace_reduce.load_xplane(path) if path else None
