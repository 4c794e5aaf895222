"""``trace_s`` of the set-up program with the largest total built under
``mx:train_step.build``: jax's trace of the step, the Python-unrolled layers."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.step_trace")
