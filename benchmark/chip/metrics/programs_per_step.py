"""Programs the first device began in the traced window (its ``XLA
Modules`` events) over the ``mx:train_step`` spans in the window."""
import span_reduce


def read(run):
    return span_reduce.read(run, span_reduce.programs_per_step)
