"""Device idle time, per step of the traced window, whose gap begins while
the host is in ``mx:train_step.scalars`` (first device; the rule of
``trace_reduce.idle_gaps``).  It says where the host stood, which is the
gap's cause only where the host was late."""
import span_reduce


def read(run):
    return span_reduce.read_idle(run, "scalars")
